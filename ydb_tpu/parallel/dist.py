"""Mesh-parallel scan execution: SPMD partial aggregation + ICI merge.

The TPU-native equivalent of the reference's distributed aggregate pipeline
(SURVEY.md §2.11): per-tablet partial states + inter-node shuffle/merge over
DQ channels become ONE SPMD program under shard_map:

  device-local partial SSA program (filters/assigns/group-by states)
    → state merge over the ``shard`` mesh axis:
        dense/keyless group layouts: elementwise psum / pmin / pmax of
          slot-aligned states (the gradient-psum-shaped path — BASELINE
          north star)
        generic layouts: all_gather of compacted partial rows + local
          re-aggregation (the DQ UnionAll-then-final-agg shape)
    → final SSA program (AVG fixups, HAVING, ORDER BY) replicated.

Everything here is jit-compiled once per (program, block shape, mesh) — the
whole distributed query step is a single XLA executable with fused
collectives, not a message exchange.
"""

from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ydb_tpu import dtypes
from ydb_tpu.analysis import memsan
from ydb_tpu.blocks.block import Column, TableBlock
from ydb_tpu.blocks.dictionary import DictionarySet
from ydb_tpu.engine.oracle import OracleTable
from ydb_tpu.engine.scan import ColumnSource, required_columns
from ydb_tpu.obs import tracing
from ydb_tpu.parallel.mesh import SHARD_AXIS, make_mesh, shard_map
from ydb_tpu.ssa import twophase
from ydb_tpu.ssa.compiler import compile_program
from ydb_tpu.ssa.ops import Agg
from ydb_tpu.ssa.program import Program


#: named scope of the collectives that merge the shards' partial states
#: (psum / pmin / pmax, or all_gather): what only a mesh trace shows
MERGE_SCOPE = "ydb.mesh_merge"


def stack_blocks(blocks: list[TableBlock]) -> TableBlock:
    """Stack per-shard blocks along a leading device axis."""
    sch = blocks[0].schema
    cols = {}
    with memsan.seam("stack"):
        for n in sch.names:
            cols[n] = Column(
                jnp.stack([b.columns[n].data for b in blocks]),
                jnp.stack([b.columns[n].validity for b in blocks]),
            )
        length = jnp.stack([b.length for b in blocks])
    out = TableBlock(cols, length, sch)
    if memsan.armed():
        memsan.charge(memsan.nbytes_of(out), "stack",
                      owner="stack_blocks")
    return out


@functools.partial(jax.jit, static_argnums=1)
def _fit_with_device_axis(block: TableBlock, capacity: int) -> TableBlock:
    """Cut or zero-pad every column to ``capacity`` rows (live rows sit
    in the prefix; padding validity is False) and give every leaf a
    leading size-1 device axis — ONE program per block shape and
    device, where the eager spelling is several per column."""
    def fit(a):
        n = a.shape[0]
        if n > capacity:
            a = a[:capacity]
        elif n < capacity:
            a = jnp.concatenate(
                [a, jnp.zeros((capacity - n,), dtype=a.dtype)])
        return a[None]

    cols = {n: Column(fit(c.data), fit(c.validity))
            for n, c in block.columns.items()}
    return TableBlock(cols, jnp.asarray(block.length)[None], block.schema)


def place_shards(blocks: list[TableBlock], mesh,
                 capacity: int | None = None,
                 owner: str = "mesh_place") -> TableBlock:
    """Per-shard blocks -> one stacked block of ``capacity`` rows per
    shard (default: the blocks' own, which must then agree), sharded
    over the mesh's shard axis, each shard put on ITS device.

    ``device_put(stack_blocks(blocks), sharding)`` stages the whole
    stack on one device first, and cannot even stack blocks that
    already live on different devices (the resident tier binds each
    shard's columns to the device that scans it): assemble the global
    arrays from the single-device pieces instead."""
    sharding = NamedSharding(mesh, P(SHARD_AXIS))
    devices = [row[0] for row in mesh.devices]
    assert len(blocks) == len(devices) == mesh.devices.size, \
        (len(blocks), mesh.devices.shape)
    if capacity is None:
        capacity = blocks[0].capacity
    with tracing.span("dispatch", program="mesh_place"), \
            memsan.seam("staging"):
        pieces = [_fit_with_device_axis(jax.device_put(b, d), capacity)
                  for b, d in zip(blocks, devices)]
        out = jax.tree_util.tree_map(
            lambda *xs: jax.make_array_from_single_device_arrays(
                (len(xs),) + xs[0].shape[1:], sharding, list(xs)),
            *pieces)
    if memsan.armed():
        memsan.charge(memsan.nbytes_of(out), "staging", owner=owner)
    return out


def _local(stacked: TableBlock) -> TableBlock:
    """Inside shard_map: strip the (size-1) leading device axis."""
    cols = {
        n: Column(c.data[0], c.validity[0])
        for n, c in stacked.columns.items()
    }
    return TableBlock(cols, stacked.length[0], stacked.schema)


def _relocal(block: TableBlock) -> TableBlock:
    """Inside shard_map: re-add the singleton device axis so per-shard
    outputs concatenate under out_specs=P(shard)."""
    cols = {
        n: Column(c.data[None], c.validity[None])
        for n, c in block.columns.items()
    }
    return TableBlock(cols, block.length[None], block.schema)


def _merge_states(cols_in, merge_kinds, rank_tables, red_max, red_min,
                  red_sum, red_any):
    """Shared state-merge core: per-column masked reduction by aggregate
    kind, with string MIN/MAX ids re-packed as (lexicographic rank << 32
    | id) around the reduction (ids do not order like the strings;
    ``rank_tables`` ships the plan-time rank arrays). The reduction ops
    are injected: mesh collectives for the cross-shard merge
    (_merge_slots), elementwise folds for the streaming pairwise merge
    (_merge_pair) — one logic, two execution shapes."""
    cols = {}
    for name, (d, v) in cols_in.items():
        kind = merge_kinds[name]
        packed = kind in (Agg.MIN, Agg.MAX) and name in rank_tables
        if packed:
            rt = rank_tables[name]
            rank = rt[jnp.clip(d, 0, rt.shape[0] - 1)]
            d = (rank.astype(jnp.int64) << 32) | d.astype(jnp.int64)
        if kind in ("key", Agg.SOME, Agg.MAX):
            lo = _neutral(d.dtype, maximum=False)
            d = red_max(jnp.where(v, d, lo))
        elif kind is Agg.MIN:
            hi = _neutral(d.dtype, maximum=True)
            d = red_min(jnp.where(v, d, hi))
        else:  # SUM / COUNT / COUNT_ALL states
            d = red_sum(jnp.where(v, d, jnp.zeros_like(d)))
        v = red_any(v)
        if packed:
            d = (d & 0xFFFFFFFF).astype(jnp.int32)
        cols[name] = Column(d, v)
    return cols


def _merge_slots(
    block: TableBlock,
    merge_kinds: dict[str, Agg | str],
    rank_tables: dict[str, jax.Array],
):
    """Elementwise merge of slot-aligned partial states across the mesh."""
    with jax.named_scope(MERGE_SCOPE):
        cols = _merge_states(
            {n: (c.data, c.validity) for n, c in block.columns.items()},
            merge_kinds, rank_tables,
            red_max=lambda x: jax.lax.pmax(x, SHARD_AXIS),
            red_min=lambda x: jax.lax.pmin(x, SHARD_AXIS),
            red_sum=lambda x: jax.lax.psum(x, SHARD_AXIS),
            red_any=lambda v: jax.lax.pmax(v, SHARD_AXIS),
        )
    return TableBlock(cols, block.length, block.schema)


def _live_prefix_host(block: TableBlock):
    """(host arrays dict, host validity dict, schema) of the live rows."""
    n = int(block.length)
    arrays = {m: np.asarray(c.data)[:n] for m, c in block.columns.items()}
    valid = {m: np.asarray(c.validity)[:n]
             for m, c in block.columns.items()}
    return arrays, valid, block.schema


def _concat_states(parts: list) -> TableBlock:
    """Concatenate host live-prefix states (from _live_prefix_host)."""
    sch = parts[0][2]
    arrays = {
        n: np.concatenate([p[0][n] for p in parts]) for n in sch.names
    }
    validity = {
        n: np.concatenate([p[1][n] for p in parts]) for n in sch.names
    }
    return TableBlock.from_numpy(arrays, sch, validity)


def _merge_pair(a: TableBlock, b: TableBlock, merge_kinds, rank_tables):
    """Pairwise (device-local) twin of _merge_slots: fold two slot-aligned
    partial-state blocks into one. Drives the streaming per-shard state
    accumulation — each shard folds its block stream into ONE bounded
    state before the mesh-wide collective merge."""
    cols = _merge_states(
        {
            n: (jnp.stack([ca.data, b.columns[n].data]),
                jnp.stack([ca.validity, b.columns[n].validity]))
            for n, ca in a.columns.items()
        },
        merge_kinds, rank_tables,
        red_max=lambda x: jnp.max(x, axis=0),
        red_min=lambda x: jnp.min(x, axis=0),
        red_sum=lambda x: jnp.sum(x, axis=0),
        red_any=lambda v: jnp.any(v, axis=0),
    )
    return TableBlock(cols, jnp.maximum(a.length, b.length), a.schema)


def _neutral(dtype, maximum: bool):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.array(jnp.inf if maximum else -jnp.inf, dtype)
    if dtype == jnp.bool_:
        return jnp.array(maximum, dtype)
    info = jnp.iinfo(dtype)
    return jnp.array(info.max if maximum else info.min, dtype)


def merge_spec(partial_prog: Program, partial_out_schema, dicts):
    """(merge_kinds, rank_tables) for cross-shard partial-state merges:
    per-column reduction kind from the partial program's group-by, plus
    lexicographic rank tables for string MIN/MAX (dictionary ids do not
    order like the strings they intern). Shared by MeshScan and the
    fused mesh lowering (parallel/mesh_fuse)."""
    merge_kinds: dict[str, Agg | str] = {}
    rank_tables: dict[str, jax.Array] = {}
    gb = partial_prog.group_by
    if gb is not None:
        for k in gb.keys:
            merge_kinds[k] = "key"
        for spec in gb.aggs:
            merge_kinds[spec.out_name] = spec.func
            if (
                spec.func in (Agg.MIN, Agg.MAX)
                and spec.column is not None
                and partial_out_schema.field(spec.out_name).type.is_string
            ):
                rt = jnp.asarray(dicts[spec.column].sort_rank())
                if memsan.armed():
                    memsan.charge(memsan.nbytes_of(rt), "staging",
                                  owner="rank_tables")
                rank_tables[spec.out_name] = rt
    return merge_kinds, rank_tables


def _gather_rows(block: TableBlock) -> TableBlock:
    """all_gather compacted partial rows from every shard into one block."""
    cap = block.capacity
    cols = {}
    with jax.named_scope(MERGE_SCOPE):
        for n, c in block.columns.items():
            d = jax.lax.all_gather(c.data, SHARD_AXIS)      # (ndev, cap)
            v = jax.lax.all_gather(c.validity, SHARD_AXIS)
            cols[n] = Column(d.reshape(-1), v.reshape(-1))
        lens = jax.lax.all_gather(block.length, SHARD_AXIS)  # (ndev,)
    ndev = lens.shape[0]
    row = jnp.arange(cap, dtype=jnp.int32)
    mask = (row[None, :] < lens[:, None]).reshape(-1)
    big = TableBlock(cols, jnp.int32(ndev * cap), block.schema)
    from ydb_tpu.ssa import kernels

    return kernels.compact(big, mask)


class MeshScan:
    """A distributed scan+aggregate program over a device mesh."""

    def __init__(
        self,
        program: Program,
        schema: dtypes.Schema,
        dicts: DictionarySet | None = None,
        key_spaces: dict[str, int] | None = None,
        mesh=None,
        dict_aliases: dict[str, str] | None = None,
    ):
        self.mesh = mesh if mesh is not None else make_mesh()
        self.read_cols = required_columns(program, schema)
        in_schema = schema.select(self.read_cols)
        partial_prog, final_prog = twophase.split(
            program, with_row_counts=True
        )
        aliases = dict(dict_aliases or {})
        self.partial = compile_program(
            partial_prog, in_schema, dicts, key_spaces, partial_slots=True,
            dict_aliases=aliases,
        )
        self.final = (
            compile_program(final_prog, self.partial.out_schema, dicts,
                            key_spaces,
                            dict_aliases={
                                **aliases,
                                **twophase.dict_aliases(partial_prog),
                            })
            if final_prog is not None
            else None
        )
        self.out_schema = (
            self.final.out_schema if self.final else self.partial.out_schema
        )
        layout = self.partial.group_layout[0]
        self._use_slots = layout in ("dense_slots", "keyless")

        merge_kinds, rank_tables = merge_spec(
            partial_prog, self.partial.out_schema, dicts)
        self._merge_kinds = merge_kinds
        self._rank_tables = rank_tables

        with memsan.seam("staging"):
            paux = {k: jnp.asarray(v)
                    for k, v in self.partial.aux.items()}
            faux = (
                {k: jnp.asarray(v) for k, v in self.final.aux.items()}
                if self.final
                else {}
            )
        if memsan.armed():
            memsan.charge(memsan.nbytes_of((paux, faux)), "staging",
                          owner="mesh_aux")

        def merge_final(part: TableBlock) -> TableBlock:
            if self.final is None:
                return _gather_rows(part)
            if self._use_slots:
                merged = _merge_slots(
                    part, self._merge_kinds, self._rank_tables
                )
                # drop dead group slots (keyless keeps its single row:
                # COUNT()=0 over empty input is still one output row)
                if (
                    self.partial.group_layout[0] == "dense_slots"
                    and "__rows" in merged.columns
                ):
                    from ydb_tpu.ssa import kernels

                    live = merged.columns["__rows"].data > 0
                    merged = kernels.compact(merged, live & merged.row_mask())
            else:
                merged = _gather_rows(part)
            with jax.named_scope("ydb.mesh_final"):
                return self.final.run(merged, faux)

        def step(stacked: TableBlock) -> TableBlock:
            block = _local(stacked)
            with jax.named_scope("ydb.mesh_partial"):
                part = self.partial.run(block, paux)
            return merge_final(part)

        self._step = jax.jit(
            shard_map(
                step,
                mesh=self.mesh,
                in_specs=P(SHARD_AXIS),
                out_specs=P(),
                check_vma=False,
            )
        )
        # merge+final over PRE-COMPUTED per-shard partial states (the
        # streaming driver computes states shard-locally block by block)
        self._merge_final_step = jax.jit(
            shard_map(
                lambda st: merge_final(_local(st)),
                mesh=self.mesh,
                in_specs=P(SHARD_AXIS),
                out_specs=P(),
                check_vma=False,
            )
        )
        self._partial_jit = jax.jit(
            lambda blk: self.partial.run(blk, paux))
        self._pair_jit = jax.jit(
            lambda a, b: _merge_pair(a, b, self._merge_kinds,
                                     self._rank_tables))

    # ---- host-side drivers ----

    def run_stacked(self, stacked: TableBlock) -> TableBlock:
        """stacked: leading device axis == mesh shard count."""
        sharding = NamedSharding(self.mesh, P(SHARD_AXIS))
        with tracing.span("dispatch", program="mesh_place"), \
                memsan.seam("staging"):
            stacked = jax.device_put(stacked, sharding)
        if memsan.armed():
            memsan.charge(memsan.nbytes_of(stacked), "staging",
                          owner="mesh_place")
        with tracing.span("dispatch", program="mesh_step"):
            return self._step(stacked)

    def execute_sources(self, sources, block_rows: int = 1 << 20
                        ) -> OracleTable:
        """Streaming SPMD scan over per-shard block-stream sources (the
        portion store feeding the mesh — VERDICT r4 item 4).

        Each shard's stream (e.g. a PortionStreamSource over its on-disk
        portions) folds block-by-block into ONE bounded partial state on
        its device (slot layouts: pairwise merge; compact layouts:
        concatenated partial rows), then a single collective step merges
        states across the mesh and finalizes. Host memory per shard stays
        bounded by the stream's working set — out-of-core and multi-chip
        compose."""
        n_shards = self.mesh.shape[SHARD_AXIS]
        if len(sources) != n_shards:
            raise ValueError(
                f"{len(sources)} sources for a {n_shards}-shard mesh")
        layout = self.partial.group_layout[0]
        foldable = layout in ("keyless", "dense_slots")
        states = []
        for sub in sources:
            st = None
            parts = []
            for blk in sub.blocks(block_rows, self.read_cols):
                part = self._partial_jit(blk)
                if not foldable:
                    # keep only the live prefix ON HOST: holding every
                    # full-capacity device block would grow device memory
                    # linearly with the stream
                    parts.append(_live_prefix_host(part))
                elif st is None:
                    st = part
                else:
                    st = self._pair_jit(st, part)
            states.append(st if foldable else _concat_states(parts))
        # compact (non-foldable) states vary in size shard-to-shard:
        # pad to the common capacity
        out = self._merge_final_step(place_shards(
            states, self.mesh,
            capacity=max(s.capacity for s in states)))
        return OracleTable.from_block(out)

    def execute(self, source: ColumnSource) -> OracleTable:
        """Partition a host table across the mesh and run one SPMD step."""
        n_shards = self.mesh.shape[SHARD_AXIS]
        n = source.num_rows
        per = -(-n // n_shards)
        blocks = []
        sch = source.schema.select(self.read_cols)
        for s in range(n_shards):
            lo, hi = min(s * per, n), min((s + 1) * per, n)
            arrays = {m: source.columns[m][lo:hi] for m in self.read_cols}
            validity = None
            if source.validity:
                validity = {
                    m: source.validity[m][lo:hi]
                    for m in self.read_cols
                    if m in source.validity
                }
            blocks.append(
                TableBlock.from_numpy(arrays, sch, validity, capacity=per)
            )
        out = self.run_stacked(stack_blocks(blocks))
        return OracleTable.from_block(out)
