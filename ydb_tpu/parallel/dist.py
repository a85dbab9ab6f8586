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

import collections
import contextlib
import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ydb_tpu import dtypes
from ydb_tpu.analysis import memsan
from ydb_tpu.blocks.block import Column, TableBlock
from ydb_tpu.blocks.dictionary import DictionarySet
from ydb_tpu.chaos import deadline as statement_deadline
from ydb_tpu.engine.oracle import OracleTable
from ydb_tpu.engine.scan import (
    DEFAULT_BLOCK_ROWS,
    ColumnSource,
    _pulled,
    required_columns,
)
from ydb_tpu.obs import tracing
from ydb_tpu.obs.probes import StageTimer
from ydb_tpu.parallel.mesh import SHARD_AXIS, make_mesh, shard_map
from ydb_tpu.plan.executor import pruning_since, source_counters
from ydb_tpu.ssa import twophase
from ydb_tpu.ssa.compiler import compile_program
from ydb_tpu.ssa.ops import Agg
from ydb_tpu.ssa.program import Program


#: named scope of the collectives that merge the shards' partial states
#: (psum / pmin / pmax, or all_gather): what only a mesh trace shows
MERGE_SCOPE = "ydb.mesh_merge"
#: named scope of the fold of a block's partial state into its shard's
#: running state, inside the block's program (MeshScan.run_sources)
FOLD_SCOPE = "ydb.mesh_fold"
#: programs a shard's device may have queued before the streaming
#: driver waits for the oldest (each pins its input block)
INFLIGHT_BLOCKS = 8


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


def _assemble(pieces: list[TableBlock], mesh) -> TableBlock:
    """Single-device pieces, each with a leading size-1 device axis and
    on ITS mesh device, as one block sharded over the mesh's shard
    axis: no copy, no program."""
    sharding = NamedSharding(mesh, P(SHARD_AXIS))
    return jax.tree_util.tree_map(
        lambda *xs: jax.make_array_from_single_device_arrays(
            (len(xs),) + xs[0].shape[1:], sharding, list(xs)),
        *pieces)


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
    devices = [row[0] for row in mesh.devices]
    assert len(blocks) == len(devices) == mesh.devices.size, \
        (len(blocks), mesh.devices.shape)
    if capacity is None:
        capacity = blocks[0].capacity
    with tracing.span("dispatch", program="mesh_place"), \
            memsan.seam("staging"):
        out = _assemble(
            [_fit_with_device_axis(jax.device_put(b, d), capacity)
             for b, d in zip(blocks, devices)], mesh)
    if memsan.armed():
        memsan.charge(memsan.nbytes_of(out), "staging", owner=owner)
    return out


def _on_device(block: TableBlock, device) -> TableBlock:
    """``block`` with its columns on ``device``: as it is where they
    already live there (a resident slice's block; its host-made row
    count follows the committed columns into the program), else put
    there. The check is a set comparison; ``device_put`` of a block
    that is already in place walks every leaf, and costs the host as
    much as enqueueing the block's program."""
    col = next(iter(block.columns.values()), None)
    if col is not None and col.data.devices() == {device}:
        return block
    with memsan.seam("staging"):
        block = jax.device_put(block, device)
    if memsan.armed():
        memsan.charge(memsan.nbytes_of(block), "staging",
                      owner="mesh_scan")
    return block


def _set_timer(sub, timer) -> None:
    """Bind a StageTimer (or None) to one device's scan source: a
    shard's portion stream, or a chain of them."""
    for s in getattr(sub, "subs", (sub,)):
        if hasattr(s, "timer"):
            s.timer = timer


@contextlib.contextmanager
def shard_scan_span(sub, table: str | None, device: int, fresh: bool,
                    agg_pushdown: bool = False):
    """One shard's scan on the statement's thread, under a ``scan``
    span as the walk has one a TableScan; yields the shard's
    StageTimer, or None. Under a recording span the shard's source
    charges the timer (``stage_*``) and the span carries the source's
    pruning counters, as the walk's scan span does; ``agg_pushdown=1``
    where the scan aggregates its blocks under their filter masks (the
    walk's attr)."""
    with tracing.span("scan") as sp:
        if agg_pushdown:
            sp.set(agg_pushdown=1)
        timer = StageTimer() if sp.recording else None
        before = source_counters(sub)
        _set_timer(sub, timer)
        try:
            yield timer
        finally:
            _set_timer(sub, None)
        if timer is not None:
            sp.set(table=table, device=device,
                   compile_cache=("miss" if fresh else "hit"),
                   **{f"stage_{k}": v
                      for k, v in timer.snapshot().items()},
                   **pruning_since(sub, before))


def _computing(timer):
    """The ``compute`` stage of a shard's StageTimer, where it has one."""
    return (timer.stage("compute") if timer is not None
            else contextlib.nullcontext())


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
        # the streaming driver's programs, each on the device of the
        # block it is handed (aux rides as an argument, placed once a
        # device: _aux_on). A shard's state keeps the size-1 device
        # axis between them, so the mesh assembles the states as they
        # are. The first block's program is the partial alone; every
        # later block's folds its partial state into the shard's
        # running one, so a shard costs ONE program a block.
        self._devices = [row[0] for row in self.mesh.devices]
        self._aux_by_device: dict = {}
        partial_run = self.partial.run

        def fold(state, blk, aux):
            part = partial_run(blk, aux)
            with jax.named_scope(FOLD_SCOPE):
                return _relocal(_merge_pair(
                    _local(state), part, merge_kinds, rank_tables))

        self._first_jit = jax.jit(
            lambda blk, aux: _relocal(partial_run(blk, aux)))
        self._fold_jit = jax.jit(fold)

    @property
    def folds_partials(self) -> bool:
        """The partial states are slot-aligned (a keyless or dense
        group layout): a shard's blocks fold into one bounded state on
        its device and the states merge elementwise over the mesh."""
        return self._use_slots

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

    def run_sources(self, sources, block_rows: int = DEFAULT_BLOCK_ROWS,
                    table: str | None = None,
                    fresh: bool = False) -> TableBlock:
        """Streaming SPMD scan over per-shard block-stream sources (the
        portion store, or the resident tier's per-device slices,
        feeding the mesh): the driver of the mesh walk's aggregate
        pushdown (parallel/mesh_exec.py) and of ``execute_sources``.
        Slot-aligned states only (``folds_partials``).

        Each shard's stream folds block by block into ONE bounded
        partial state on its device, under a ``scan`` span a shard
        (``table`` and ``fresh`` are that span's ``table`` and
        ``compile_cache``): every block is aggregated under its filter
        mask by one program that also folds. Nothing here waits for a
        device: the host enqueues shard after shard while each device
        works through its own queue. Then one collective step merges
        the states across the mesh and finalizes. Host memory per shard
        stays bounded by the stream's working set — out-of-core and
        multi-chip compose."""
        if not self._use_slots:
            raise ValueError(
                "run_sources folds slot-aligned partial states; a "
                f"{self.partial.group_layout[0]} layout has none")
        n_shards = self.mesh.shape[SHARD_AXIS]
        if len(sources) != n_shards:
            raise ValueError(
                f"{len(sources)} sources for a {n_shards}-shard mesh")
        states = []
        for d, sub in enumerate(sources):
            with shard_scan_span(sub, table, d, fresh,
                                 agg_pushdown=True) as timer:
                states.append(self._fold_shard(
                    sub, self._devices[d], block_rows, timer))
        # the states are where they belong, device axis and all
        with tracing.span("dispatch", program="mesh_place"):
            placed = _assemble(states, self.mesh)
        with tracing.span("dispatch", program="mesh_step"):
            return self._merge_final_step(placed)

    def _aux_on(self, device) -> dict:
        """The partial program's aux tables on ``device`` (put there
        once: an argument that lives elsewhere is copied over on every
        dispatch)."""
        aux = self._aux_by_device.get(device)
        if aux is None:
            with memsan.seam("staging"):
                # staged once a device and kept, not once a dispatch
                # ydb-lint: disable=M007
                aux = jax.device_put(dict(self.partial.aux), device)
            if memsan.armed():
                memsan.charge(memsan.nbytes_of(aux), "staging",
                              owner="mesh_scan")
            self._aux_by_device[device] = aux
        return aux

    def _shard_blocks(self, sub, block_rows: int):
        """``sub``'s blocks of the read columns, each pulled under a
        ``scan.pull`` span; an empty shard (a portion stream yields
        nothing) gives one empty block, so it still has a state."""
        empty = True
        for blk in _pulled(sub.blocks(block_rows, self.read_cols)):
            empty = False
            # block-boundary cancellation point, as in ScanExecutor
            statement_deadline.check_current("scan")
            yield blk
        if empty:
            yield TableBlock.from_numpy(
                {f.name: np.empty(0, dtype=f.type.physical)
                 for f in self._in_schema.fields},
                self._in_schema, capacity=1)

    def _fold_shard(self, sub, device, block_rows: int,
                    timer) -> TableBlock:
        """One shard's blocks folded into one slot-aligned state (with
        its device axis) on ``device``: one program a block, enqueued
        and not waited for. Back-pressure is the shard's own: past
        ``INFLIGHT_BLOCKS`` programs in this device's queue the loop
        waits for the oldest (each pins its input block; a resident
        shard of a handful of blocks never gets there)."""
        aux = self._aux_on(device)
        state = None
        window: collections.deque = collections.deque()
        for blk in self._shard_blocks(sub, block_rows):
            with _computing(timer), tracing.span(
                    "dispatch", program="scan_partial"):
                blk = _on_device(blk, device)
                state = (self._first_jit(blk, aux) if state is None
                         else self._fold_jit(state, blk, aux))
            window.append(state)
            if len(window) > INFLIGHT_BLOCKS:
                with tracing.span("device.wait"):
                    # ydb-lint: disable=H001
                    jax.block_until_ready(window.popleft())
        return state

    def execute_sources(self, sources, block_rows: int = DEFAULT_BLOCK_ROWS
                        ) -> OracleTable:
        """``run_sources``, the answer copied out to the host."""
        return OracleTable.from_block(
            self.run_sources(sources, block_rows))

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
