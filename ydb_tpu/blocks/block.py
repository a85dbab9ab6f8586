"""Fixed-shape device column blocks — the unit of TPU columnar execution.

The reference's execution unit is an Arrow RecordBatch flowing through block
operators (ydb/library/yql/minikql/comp_nodes/mkql_blocks.cpp, block infra
computation/mkql_block_impl.h). XLA wants static shapes, so the TPU analog is
a ``TableBlock``: every column padded to a common ``capacity`` with an int32
``length`` scalar giving the live row count. Rows in [length, capacity) are
padding; kernels mask them out via ``row_mask``.

TableBlock is a pytree, so it flows through jit / vmap / shard_map / psum
directly. The schema and capacity are static (part of the treedef): changing
either triggers recompilation, matching the compiled-pattern-cache design
(reference: mkql_computation_pattern_cache.h — here the XLA compile cache).

NULLs: each column carries a validity bitmask (bool array). Kernels follow
Arrow/Kleene semantics where the reference does.
"""

from __future__ import annotations

import dataclasses
from typing import Mapping

import jax
import jax.numpy as jnp
import numpy as np

from ydb_tpu import dtypes


def _round_up(n: int, mult: int) -> int:
    return ((n + mult - 1) // mult) * mult


def host_ok(reason: str):
    """Dispatch-purity marker, same shape as ``analysis.host_ok`` —
    redeclared here because this module sits below the analysis
    package in the import graph (analysis.verify -> ssa -> blocks) and
    cannot import it. The hotpath analyzer matches the decorator by
    name; the runtime attribute is identical."""

    def mark(fn):
        fn.__host_ok__ = reason
        return fn

    return mark


def budget_ok(reason: str):
    """Device-memory marker, same shape as ``analysis.budget_ok`` —
    redeclared here for the same import-graph reason as ``host_ok``
    above. The devmem analyzer matches the decorator by name; the
    runtime attribute is identical."""

    def mark(fn):
        fn.__budget_ok__ = reason
        return fn

    return mark


# Pad capacities to a lane-friendly multiple; keeps layouts tileable on the
# VPU (8x128 lanes) and stabilizes jit cache keys across slightly different
# batch sizes.
DEFAULT_CAPACITY_QUANTUM = 1024


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Column:
    """One device column: physical values + validity mask.

    ``data`` is the physical representation per ydb_tpu.dtypes (strings are
    int32 dictionary ids, decimals scaled int64). ``validity`` is True for
    non-null rows; padding rows have validity False.
    """

    data: jax.Array
    validity: jax.Array

    def tree_flatten(self):
        return (self.data, self.validity), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def capacity(self) -> int:
        return self.data.shape[0]


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class TableBlock:
    """A batch of rows as named device columns, padded to ``capacity``.

    Dynamic leaves: per-column data/validity arrays + ``length`` scalar.
    Static treedef: schema (names + logical types) and capacity.
    """

    columns: dict[str, Column]
    length: jax.Array  # int32 scalar: live rows
    schema: dtypes.Schema

    def tree_flatten(self):
        names = tuple(self.columns.keys())
        children = tuple(self.columns[n] for n in names) + (self.length,)
        return children, (names, self.schema)

    @classmethod
    def tree_unflatten(cls, aux, children):
        names, schema = aux
        cols = dict(zip(names, children[:-1]))
        return cls(cols, children[-1], schema)

    # ---- construction ----

    @staticmethod
    @host_ok("host->device ingest boundary: stages already-materialized"
             " host arrays (tail-padding them is part of the transfer)")
    def from_numpy(
        arrays: Mapping[str, np.ndarray],
        schema: dtypes.Schema,
        validity: Mapping[str, np.ndarray] | None = None,
        capacity: int | None = None,
    ) -> "TableBlock":
        """Build a block from host numpy arrays (already physically encoded).

        Low-copy staging: a capacity-aligned array passes straight to the
        device transfer (on CPU backends ``jnp.asarray`` can even alias
        aligned owning arrays — zero host copies); only a short tail is
        ever padded, instead of zero-filling and re-copying a
        full-capacity buffer per column. Callers must therefore not
        mutate ``arrays``/``validity`` after handing them over — the
        scan pipeline's payloads are single-owner by construction.
        """
        # deferred import: blocks sits below the analysis package in
        # the import graph (analysis.verify -> ssa -> blocks)
        from ydb_tpu.analysis import memsan
        names = schema.names
        n = len(next(iter(arrays.values()))) if arrays else 0
        cap = capacity if capacity is not None else _round_up(
            max(n, 1), DEFAULT_CAPACITY_QUANTUM
        )
        if cap < n:
            raise ValueError(f"capacity {cap} < rows {n}")
        cols = {}
        with memsan.seam("staging"):
            for name in names:
                f = schema.field(name)
                a = np.asarray(arrays[name], dtype=f.type.physical)
                v = None if validity is None else validity.get(name)
                if v is None:
                    v = np.ones(n, dtype=np.bool_)
                else:
                    v = np.asarray(v, dtype=np.bool_)
                if cap != n:
                    # tail-only padding; padding validity stays False so
                    # it can never leak live rows
                    a = np.concatenate(
                        [a, np.zeros(cap - n, dtype=f.type.physical)])
                    v = np.concatenate(
                        [v, np.zeros(cap - n, dtype=np.bool_)])
                cols[name] = Column(jnp.asarray(a), jnp.asarray(v))
            blk = TableBlock(cols, jnp.asarray(n, dtype=jnp.int32),
                             schema)
        if memsan.armed():
            memsan.charge(memsan.nbytes_of(blk), "staging",
                          owner="from_numpy")
        return blk

    # ---- views ----

    @property
    def capacity(self) -> int:
        return next(iter(self.columns.values())).capacity if self.columns else 0

    @budget_ok("capacity-length index mask: fused away under jit;"
               " eager use is one bounded int32[capacity] vector")
    def row_mask(self) -> jax.Array:
        """bool[capacity]: True for live (non-padding) rows."""
        return jnp.arange(self.capacity, dtype=jnp.int32) < self.length

    def column(self, name: str) -> Column:
        return self.columns[name]

    def select(self, names) -> "TableBlock":
        return TableBlock(
            {n: self.columns[n] for n in names},
            self.length,
            self.schema.select(names),
        )

    def with_column(
        self, name: str, col: Column, typ: dtypes.LogicalType
    ) -> "TableBlock":
        cols = dict(self.columns)
        cols[name] = col
        sch = self.schema
        if name not in sch:
            sch = sch.with_field(dtypes.Field(name, typ))
        return TableBlock(cols, self.length, sch)

    # ---- host materialization (tests / result delivery) ----

    # device->host slicing quantum: live-row counts round up to this
    # before the device-side slice, so the tiny slice program re-traces
    # per QUANTIZED length, not per exact length
    _SLICE_QUANTUM = 8192

    @classmethod
    def _clip(cls, arr, n: int):
        """Device-side slice to (about) the live prefix when the saving
        is substantial. Aggregate outputs are padded to the block
        capacity (a 2M-row block with 4 live groups); pulling the whole
        padded buffer over a slow device link dwarfs the query."""
        cap = arr.shape[0]
        if cap > 4 * cls._SLICE_QUANTUM and n <= cap // 4:
            m = -(-n // cls._SLICE_QUANTUM) * cls._SLICE_QUANTUM
            arr = arr[:min(cap, m)]
        return arr

    @host_ok("deliberate result fetch: every column rides ONE batched"
             " device_get (one link round trip per statement)")
    def host_columns(
        self, validity: bool = True
    ) -> "tuple[dict[str, np.ndarray], dict[str, np.ndarray]]":
        """(data, validity) of live rows in ONE batched device fetch.

        Per-array fetches pay a full device-link round trip EACH; on a
        high-latency link that — not bandwidth — dominates small
        results, so every column (and its validity) rides one
        ``jax.device_get``."""
        from ydb_tpu.obs import tracing  # deferred: import graph

        n = self.live_rows()
        pack = {
            k: ((self._clip(c.data, n), self._clip(c.validity, n))
                if validity else (self._clip(c.data, n),))
            for k, c in self.columns.items()
        }
        with tracing.span("device.get"):
            got = jax.device_get(pack)
        data = {k: v[0][:n] for k, v in got.items()}
        valid = ({k: v[1][:n] for k, v in got.items()} if validity
                 else {})
        return data, valid

    @host_ok("deliberate result fetch: the sync of host_columns")
    def live_rows(self) -> int:
        """``length`` on the host: the sync that waits for whatever
        program computes this block (a ``device.wait`` span)."""
        from ydb_tpu.obs import tracing  # deferred: import graph

        with tracing.span("device.wait"):
            return int(self.length)

    @host_ok("deliberate result fetch (delegates to host_columns)")
    def to_numpy(self) -> dict[str, np.ndarray]:
        """Live rows only, as physical numpy arrays (nulls not decoded)."""
        return self.host_columns(validity=False)[0]

    @host_ok("deliberate result fetch: one batched validity device_get")
    def validity_numpy(self) -> dict[str, np.ndarray]:
        from ydb_tpu.obs import tracing  # deferred: import graph

        n = self.live_rows()
        with tracing.span("device.get"):
            got = jax.device_get(
                {k: self._clip(c.validity, n)
                 for k, c in self.columns.items()})
        return {k: v[:n] for k, v in got.items()}


@host_ok("one-time aux staging at compile/first-dispatch time; values"
         " already device-resident are passed through untouched")
def device_aux(aux: Mapping[str, object]) -> dict:
    """Stage a compiled program's aux tables (dict masks, gather tables)
    on the device, skipping values that already live there — the aux
    dict crosses every fragment boundary, and re-staging device-resident
    arrays on each hop costs a transfer for nothing."""
    from ydb_tpu.analysis import memsan  # deferred: import graph
    out = {}
    staged = 0
    with memsan.seam("staging"):
        for k, v in aux.items():
            if isinstance(v, jax.Array):
                out[k] = v
            else:
                out[k] = jnp.asarray(v)
                staged += int(getattr(out[k], "nbytes", 0) or 0)
    if staged and memsan.armed():
        memsan.charge(staged, "staging", owner="device_aux")
    return out


@host_ok("host-side concat for readers/tests; the warm scan path"
         " merges on device (merge_blocks_device) instead")
def concat_blocks(blocks: list[TableBlock], capacity=None) -> TableBlock:
    """Host-side concat of live rows into one block (used by readers/tests).

    ``capacity``: the result's, or a function of its live rows (the
    walk passes ``plan_fuse.shape_class``, so that what it compiles over
    the result is not shaped by the selected row count); by default the
    rows rounded up to the capacity quantum."""
    if not blocks:
        raise ValueError("concat of no blocks")
    schema = blocks[0].schema
    if len(blocks) > 1:
        # a row may come from any branch, so a column is nullable as
        # soon as ANY branch's is (branch schemas share names/types)
        schema = dtypes.Schema(tuple(
            dtypes.Field(
                f.name, f.type,
                any(b.schema.field(f.name).nullable for b in blocks))
            for f in schema.fields))
    from ydb_tpu.obs import tracing  # deferred: import graph

    # the walk ends a scan with no final program here, and the host
    # does the work: every block out of the device once per column,
    # numpy's concatenate, and the result staged back
    def fetched(get, name):
        # one column of every block out of the device: one leaf span,
        # not one per block
        with tracing.leaf("device.get"):
            return [get(b)[name] for b in blocks]

    arrays: dict[str, np.ndarray] = {}
    validity: dict[str, np.ndarray] = {}
    with tracing.span("host.concat", blocks=len(blocks)) as sp:
        for name in schema.names:
            arrays[name] = np.concatenate(
                fetched(TableBlock.to_numpy, name))
            validity[name] = np.concatenate(
                fetched(TableBlock.validity_numpy, name))
        rows = len(next(iter(arrays.values()))) if arrays else 0
        sp.set(rows=rows, bytes=sum(
            a.nbytes for a in (*arrays.values(), *validity.values())))
        if callable(capacity):
            capacity = capacity(rows)
        return TableBlock.from_numpy(arrays, schema, validity,
                                     capacity=capacity)
