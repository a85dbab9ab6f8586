"""Single-shard scan execution: stream column blocks through a compiled
SSA program with partial/final aggregation.

This is the minimum end-to-end slice of the reference's ColumnShard scan
(SURVEY.md §3.3): portions → assemble → program steps → merged result.
Here: a host column source is tiled into fixed-capacity device blocks; the
*partial* program (filters + assigns + partial group-by) runs jitted per
block (one XLA compile for all blocks — identical shapes); the small
partial results are merged by the *final* program. Programs without a
GROUP BY concatenate block outputs directly.

The per-block loop is the host-side analog of the scan iterator
(engines/reader/plain_reader/iterator/iterator.h:53) — flow control,
prefetch and credit windows attach here (ydb_tpu.dq channels reuse it).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Iterator

import jax
import jax.numpy as jnp
import numpy as np

from ydb_tpu import dtypes
from ydb_tpu.chaos import deadline as statement_deadline
from ydb_tpu.blocks.block import (
    Column,
    TableBlock,
    concat_blocks,
    device_aux,
)
from ydb_tpu.blocks.dictionary import DictionarySet
from ydb_tpu.engine.oracle import OracleTable
from ydb_tpu.obs import tracing
from ydb_tpu.ssa import kernels, twophase
from ydb_tpu.ssa.compiler import compile_program
from ydb_tpu.ssa.program import Program

DEFAULT_BLOCK_ROWS = 1 << 20


@dataclasses.dataclass
class ColumnSource:
    """A host-resident columnar table (one shard's worth of data)."""

    columns: dict[str, np.ndarray]
    schema: dtypes.Schema
    dicts: DictionarySet | None = None
    validity: dict[str, np.ndarray] | None = None

    @property
    def num_rows(self) -> int:
        return len(next(iter(self.columns.values()))) if self.columns else 0

    def n_blocks(self, block_rows: int = DEFAULT_BLOCK_ROWS) -> int:
        n = self.num_rows
        cap = min(block_rows, max(n, 1))
        return len(range(0, max(n, 1), cap))

    def blocks(
        self, block_rows: int = DEFAULT_BLOCK_ROWS,
        columns: tuple[str, ...] | None = None,
        start_block: int = 0,
    ) -> Iterator[TableBlock]:
        """Tile into equal-capacity blocks (last one padded).
        ``start_block`` seeks without materializing skipped blocks
        (checkpoint-resume path)."""
        names = columns if columns is not None else self.schema.names
        sch = self.schema.select(names)
        n = self.num_rows
        cap = min(block_rows, max(n, 1))
        for off in range(start_block * cap, max(n, 1), cap):
            hi = min(off + cap, n)
            arrays = {m: self.columns[m][off:hi] for m in names}
            validity = None
            if self.validity:
                validity = {
                    m: self.validity[m][off:hi]
                    for m in names if m in self.validity
                }
            yield TableBlock.from_numpy(arrays, sch, validity, capacity=cap)


@jax.named_scope("ydb.merge_blocks_device")
def merge_blocks_device(blocks: list[TableBlock]) -> TableBlock:
    """Trace-time concat of blocks (live rows compacted to the front).

    The device twin of ``concat_blocks``: everything stays on the chip —
    no host round trip (each to_numpy is a blocking D2H sync)."""
    if len(blocks) == 1:
        return blocks[0]
    schema = blocks[0].schema
    live = jnp.concatenate([b.row_mask() for b in blocks])
    cols = {}
    for n in schema.names:
        data = jnp.concatenate([b.columns[n].data for b in blocks])
        val = jnp.concatenate([b.columns[n].validity for b in blocks])
        cols[n] = Column(data, val)
    # live rows sit at each segment's start, not in one prefix: give the
    # concat full-capacity length so compact's row_mask covers them all
    blk = TableBlock(cols, jnp.int32(live.shape[0]), schema)
    return kernels.compact(blk, live)


def required_columns(program: Program, schema: dtypes.Schema) -> tuple[str, ...]:
    """Input columns the program actually reads (scan projection pushdown)."""
    from ydb_tpu.ssa.program import (
        AssignStep, Call, Col, DictMap, DictPredicate, FilterStep,
        GroupByStep, ProjectStep, SortStep, UdfCall,
    )

    used: set[str] = set()
    assigned: set[str] = set()

    def walk(e):
        if isinstance(e, Col):
            if e.name not in assigned:
                used.add(e.name)
        elif isinstance(e, (Call, UdfCall)):
            for a in e.args:
                walk(a)
        elif isinstance(e, (DictPredicate, DictMap)):
            if e.column not in assigned:
                used.add(e.column)

    for s in program.steps:
        if isinstance(s, AssignStep):
            walk(s.expr)
            assigned.add(s.name)
        elif isinstance(s, FilterStep):
            walk(s.expr)
        elif isinstance(s, GroupByStep):
            for k in s.keys:
                if k not in assigned:
                    used.add(k)
            for a in s.aggs:
                if a.column is not None and a.column not in assigned:
                    used.add(a.column)
        elif isinstance(s, SortStep):
            for k in s.keys:
                if k not in assigned:
                    used.add(k)
        elif isinstance(s, ProjectStep):
            for nm in s.names:
                if nm not in assigned:
                    used.add(nm)
    if not used:
        # pure COUNT(*)-style programs still need one column for the row
        # count; read the narrowest physical column (the reference reads a
        # system column)
        if not schema.fields:
            return ()
        cheapest = min(
            schema.fields, key=lambda f: f.type.physical.itemsize
        )
        return (cheapest.name,)
    return tuple(n for n in schema.names if n in used)


_END = object()


def _pulled(blocks):
    """``blocks``, each ``next`` under a ``scan.pull`` span: the time
    the dispatching thread waits for the staging pipeline's next block
    (with no free conveyor worker the staging itself runs here)."""
    it = iter(blocks)
    while True:
        with tracing.span("scan.pull"):
            b = next(it, _END)
        if b is _END:
            return
        yield b


class ScanExecutor:
    """Compiles a program against a source and executes block-streamed.

    Memory discipline (the TChunksLimiter credit idea,
    ydb/library/chunks_limiter/chunks_limiter.h:7, re-expressed for XLA's
    async dispatch): the block loop keeps at most ``inflight_blocks``
    dispatched-but-unfinished device computations — each in-flight
    execution pins its input block's buffers, so an unbounded dispatch
    queue (slow device / starved host) would retain the whole table.
    Aggregation partials additionally fold incrementally every
    ``combine_every`` blocks through the associative combine program
    (twophase.combine_of) whenever the group layout is shape-stable, so
    the partials list never grows with the table either.
    """

    def __init__(
        self,
        program: Program,
        source: ColumnSource,
        block_rows: int = DEFAULT_BLOCK_ROWS,
        key_spaces: dict[str, int] | None = None,
        inflight_blocks: int = 4,
        combine_every: int = 8,
        group_est: float | None = None,
        dict_aliases: dict[str, str] | None = None,
    ):
        self.source = source
        self.block_rows = block_rows
        self.inflight_blocks = inflight_blocks
        self.combine_every = combine_every
        # advisory NDV-based group-count estimate (stats.cost): steers
        # the PARTIAL program's group-by tier choice; the final/combine
        # programs run over small partial blocks and keep their own
        # sizing
        self.group_est = group_est
        # first dispatch of each jitted program (partial / combine /
        # final) = jit trace + XLA compile; measured once per program
        # and summed into first_trace_seconds so scan sites can
        # attribute the compile-vs-execute split that separates cold
        # from warm runs (finalize compiles too — attributing only the
        # partial would leak its compile into "execute")
        self.first_trace_seconds: float | None = None
        self._partial_traced = False
        self._combine_traced = False
        self._finalize_traced = False
        self.read_cols = required_columns(program, source.schema)
        in_schema = source.schema.select(self.read_cols)
        # verify the ORIGINAL program before the two-phase rewrite:
        # diagnostics then point at the caller's step indices, not at
        # synthesized partial/final steps (which compile_program still
        # re-checks as its own precondition). Its nullability also
        # types the RESULT schema below: the original program knows
        # keyed AVG over a non-null input is never NULL, while the
        # rewritten final program only sees a division fixup.
        from ydb_tpu.analysis.verify import check_program

        self._out_nullable = check_program(program, in_schema).out_nullable
        self.partial_prog, self.final_prog = twophase.split(program)
        # renamed string columns (a Transform's dict_aliases, when its
        # program runs here: plan/executor.py's aggregate pushdown)
        # resolve their dictionaries in all three programs; a string
        # aggregate's output carries its input's, itself maybe renamed
        aliases = dict(dict_aliases or {})
        merged_aliases = {
            **{out: aliases.get(col, col) for out, col
               in twophase.dict_aliases(self.partial_prog).items()},
            **aliases,
        }
        self.partial = compile_program(
            self.partial_prog, in_schema, source.dicts, key_spaces,
            group_est=group_est, dict_aliases=aliases,
        )
        self._partial_jit = jax.jit(self.partial.run)
        self._partial_aux = device_aux(self.partial.aux)
        self._combine_jit = None
        self._combine_aux = {}
        if self.final_prog is not None and self.partial.group_layout[0] in (
            "keyless", "dense", "dense_slots"
        ):
            combine_prog = twophase.combine_of(program)
            comb = compile_program(
                combine_prog, self.partial.out_schema, source.dicts,
                key_spaces,
                dict_aliases=merged_aliases,
            )
            comb_run = comb.run

            @jax.jit
            def _combine(parts, aux):
                return comb_run(merge_blocks_device(list(parts)), aux)

            self._combine_jit = _combine
            self._combine_aux = device_aux(comb.aux)
        if self.final_prog is not None:
            self.final = compile_program(
                self.final_prog, self.partial.out_schema, source.dicts,
                key_spaces,
                dict_aliases=merged_aliases,
            )
            self._final_jit = jax.jit(self.final.run)
            self._final_aux = device_aux(self.final.aux)
            self.out_schema = self._stamp_nullability(
                self.final.out_schema)
            final_run = self.final.run

            @jax.jit
            def _finalize(parts, aux):
                return final_run(merge_blocks_device(list(parts)), aux)

            self._finalize_jit = _finalize
        else:
            self.final = None
            self.out_schema = self._stamp_nullability(
                self.partial.out_schema)
            self._final_aux = {}
            self._finalize_jit = jax.jit(
                lambda parts, aux: merge_blocks_device(list(parts)))

    @property
    def folds_partials(self) -> bool:
        """The program aggregates, and its partial states are
        shape-stable from block to block (a keyless, dense or
        dense-slots group layout), so they fold through the combine
        program and the scan ends on the device in a handful of rows."""
        return self._combine_jit is not None

    def detach(self) -> "ScanExecutor":
        """Drop the source reference: compiled state only. Callers that
        cache executors across source replacements (plan executor) must
        not pin the original table's arrays."""
        self.source = None
        return self

    def _timed_first(self, flag: str, program: str, fn, *args):
        """Enqueue one device program under a ``dispatch`` span named
        by its role. A program's first dispatch runs jit trace + XLA
        compile: time it synchronously, once (one-off sync; warm stays
        async), accumulating into ``first_trace_seconds``."""
        t0 = time.perf_counter()
        with tracing.span("dispatch", program=program):
            out = fn(*args)
        if getattr(self, flag):
            return out
        # one-off sync: times the first dispatch's trace+compile (the
        # warm return above stays async)
        with tracing.span("device.wait"):
            # ydb-lint: disable=H001
            jax.block_until_ready(out)
        setattr(self, flag, True)
        self.first_trace_seconds = (
            (self.first_trace_seconds or 0.0)
            + time.perf_counter() - t0)
        return out

    def run_block(self, block: TableBlock) -> TableBlock:
        return self._timed_first("_partial_traced", "scan_partial",
                                 self._partial_jit, block,
                                 self._partial_aux)

    def finalize(self, partials: list[TableBlock]) -> TableBlock:
        """Merge per-block partial results and run the final program —
        one jitted device computation end to end."""
        if self.final is None and len(partials) == 1:
            return partials[0]
        return self._timed_first("_finalize_traced", "scan_finalize",
                                 self._finalize_jit, tuple(partials),
                                 self._final_aux)

    def run_stream(self, blocks, timer=None, consumed_cb=None,
                   concat_capacity=None) -> TableBlock:
        """Drive a block stream with bounded in-flight work; returns the
        result block (merged partials finalized, or concatenated rows).

        The stream contract admits out-of-order-READY production: a
        morsel pipeline (engine.stream_sched) may complete blocks in
        any order underneath, as long as the iterator delivers them in
        order — this loop consumes strictly in order and, via
        ``consumed_cb`` (called once per admitted block), returns the
        in-order consumption credit that lets the producer account its
        double-buffered slabs.

        ``concat_capacity``: ``concat_blocks``' ``capacity``, for a
        program without a final stage, whose block outputs the host
        concatenates.

        ``timer`` (obs.probes.StageTimer) charges device dispatch +
        backpressure waits to the "compute" stage; time spent PULLING
        from ``blocks`` (the staging pipeline) is charged by the
        producer side, so the two stages expose their overlap."""
        import collections
        import contextlib

        window: collections.deque = collections.deque()
        partials: list[TableBlock] = []

        def computing():
            return (timer.stage("compute") if timer is not None
                    else contextlib.nullcontext())

        def admit(out):
            partials.append(out)
            window.append(out)
            if len(window) > self.inflight_blocks:
                # deliberate backpressure: sync ONLY the oldest
                # in-flight block once the window fills — bounded by
                # inflight_blocks, not rows
                with tracing.span("device.wait"):
                    # ydb-lint: disable=H001
                    jax.block_until_ready(window.popleft())

        # the morsel driver loop: iterations are bounded by block
        # count (capacity-quantized morsels), never by rows; each
        # iteration is one async device dispatch
        # ydb-lint: disable=H006
        for b in _pulled(blocks):
            # block-boundary cancellation point (no-op when the
            # statement carries no deadline)
            statement_deadline.check_current("scan")
            with computing():
                admit(self.run_block(b))
                if (
                    self._combine_jit is not None
                    and len(partials) >= self.combine_every
                ):
                    merged = self._timed_first(
                        "_combine_traced", "scan_combine",
                        self._combine_jit, tuple(partials),
                        self._combine_aux)
                    partials = []
                    admit(merged)
            if consumed_cb is not None:
                consumed_cb()
        with computing():
            if self.final is None:
                # pure filter/project program: block outputs concatenate
                out = (partials[0] if len(partials) == 1
                       else concat_blocks(partials, concat_capacity))
            else:
                out = self.finalize(partials)
            from ydb_tpu.obs import timeline
            if timeline.timeline_enabled():
                # movement observatory runs materialize here so the
                # async tail lands on the compute stage interval, not
                # on whichever caller first touches the arrays —
                # occupancy attribution stays exact. Default path
                # stays lazy (cross-query dispatch pipelining).
                with tracing.span("device.wait"):
                    # ydb-lint: disable=H001
                    jax.block_until_ready(out.columns)
            return self._retype(out)

    def _stamp_nullability(self, sch: dtypes.Schema) -> dtypes.Schema:
        """Original-program nullability over a rewritten-program schema
        (the two-phase rewrite's fixups would widen it: AVG restated as
        a division fixup loses never-NULL knowledge)."""
        return dtypes.Schema(tuple(
            dtypes.Field(f.name, f.type,
                         self._out_nullable.get(f.name, f.nullable))
            for f in sch.fields))

    def _retype(self, blk: TableBlock) -> TableBlock:
        sch = self._stamp_nullability(blk.schema)
        if sch == blk.schema:
            return blk
        return TableBlock(blk.columns, blk.length, sch)

    def execute(self) -> OracleTable:
        return OracleTable.from_block(self.run_stream(
            self.source.blocks(self.block_rows, self.read_cols)
        ))


def execute_scan(
    program: Program,
    source: ColumnSource,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    key_spaces: dict[str, int] | None = None,
) -> OracleTable:
    return ScanExecutor(program, source, block_rows, key_spaces).execute()
