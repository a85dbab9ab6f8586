"""DQ compute actors: task execution with credit-based channel flow.

Mirror of the reference's compute-actor framework (SURVEY.md §2.10):
a generic actor hosts one task's program, drives its input/output
channels with a credit protocol (TEvChannelData / TEvChannelDataAck,
dq_compute_actor_channels.h:15), spills backlog beyond the memory quota
(spilling service), and streams the result channel to the executer.

Device work happens inside the task: each arriving block runs the
stage's compiled SSA program on the accelerator. A channel whose
consumer lives on the producer's actor system carries the output as a
device block, hashed, split and packed on the chip; the result channel,
channels to other nodes and every channel of a checkpointed graph carry
host payloads (see "Channel payloads" below).
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import threading
import time
import weakref

import jax
import jax.numpy as jnp
import numpy as np

from ydb_tpu import dtypes
from ydb_tpu.analysis import host_ok
from ydb_tpu.blocks.block import (
    Column,
    TableBlock,
    concat_blocks,
    device_aux,
)
from ydb_tpu.dq.graph import (
    Broadcast,
    ChannelSpec,
    HashPartition,
    ResultOutput,
    SourceInput,
    StageSpec,
    TaskSpec,
    UnionAll,
    build_tasks,
)
from ydb_tpu.dq.spilling import Spiller
from ydb_tpu.engine import hbm
from ydb_tpu.engine.oracle import OracleTable
from ydb_tpu.engine.scan import ColumnSource, merge_blocks_device
from ydb_tpu.obs import tracing
from ydb_tpu.obs.counters import root_counters
from ydb_tpu.parallel.shuffle import hash_rows
from ydb_tpu.runtime.actors import Actor, ActorId
from ydb_tpu.ssa import kernels
from ydb_tpu.ssa.compiler import (
    LAYOUT_NAMES,
    compile_program,
    resolve_whole_input_step,
)
from ydb_tpu.ssa.plan_fuse import shape_class
from ydb_tpu.ssa.program import Program, RollupStep, WindowStep

DEFAULT_WINDOW = 4  # unacked blocks per channel before spilling


# ---- channel protocol messages ----


class DeviceBlock:
    """A channel block that stays on the chip: the block, its live rows
    (read once where it was split, so no consumer waits for them) and
    the bytes it holds against an ``hbm.ChannelBudget``, given back by
    ``release()`` where it is consumed, or once it is dropped (a graph
    torn down with blocks parked or in flight)."""

    def __init__(self, block: TableBlock, rows: int,
                 budget: hbm.ChannelBudget, nbytes: int):
        self.block = block
        self.rows = rows
        self.nbytes = nbytes
        self.release = weakref.finalize(self, budget.release, nbytes)


@dataclasses.dataclass
class ChannelData:
    channel_id: int
    seq: int
    payload: "dict | DeviceBlock | None"
    finished: bool


@dataclasses.dataclass
class ChannelAck:
    channel_id: int
    seq: int


@dataclasses.dataclass
class StartTask:
    pass


@dataclasses.dataclass
class WireTask:
    """Late channel wiring: consumer ActorIds for this task's output
    channels (possibly on other NODES — the targets ride the
    interconnect transparently), plus where results and aborts go.
    Sent by the executer after every task everywhere has registered
    (the two-phase start the reference's executer does when it wires
    TEvChannelData routes across compute nodes)."""

    channel_targets: dict[int, ActorId]
    result_target: ActorId | None = None
    abort_target: ActorId | None = None


@dataclasses.dataclass
class QueryAborted:
    """Fatal query error: propagated to the collector so a dead peer
    (Undelivered channel data) fails the query cleanly instead of
    hanging it (TEvAbortExecution shape, dq_compute_actor.h:41)."""

    reason: str


@dataclasses.dataclass
class _PumpSource:
    """Self-message: consume ONE source block, then re-arm. Keeps the
    mailbox responsive between blocks so checkpoint barriers (and any
    control traffic) interleave with streaming reads."""


@dataclasses.dataclass
class ResultData:
    payload: dict | None
    finished: bool


# ---- payload <-> block ----


# Channel payloads. A channel whose consumer runs on the producer's actor
# system (one process, one chip) carries a DeviceBlock. A hash partition
# over several tasks is split on the chip (_split: the hash bit for bit
# the host's, each consumer's rows compacted in order and cut to their
# shape class); any other output goes whole, cut to its rows' shape
# class; a join's bucket is packed there at the shape class of its rows
# (_pack), the capacity run_equi_join pads the host's concatenation to.
# Four cases carry a part's host payload, copied out once
# (block_to_payload), staged back by its consumer (payload_to_block) or
# concatenated on the host (_assemble): the result channel (the answer
# leaves the device anyway), a channel to another node (the interconnect
# ships bytes), every channel of a checkpointed graph (checkpoints save
# host payloads), and a block that would pass the process's HBM budget
# for channel blocks (hbm.channels()). Both paths run under
# ``dq.exchange`` spans, with the waits on the chip as ``device.wait`` /
# ``device.get`` children.


@tracing.span("dq.exchange")
def block_to_payload(block: TableBlock) -> dict:
    data, valid = block.host_columns()
    out = {}
    for k, v in data.items():
        out[k] = v
        out[f"__v_{k}"] = valid[k]
    return out


@tracing.span("dq.exchange")
def payload_to_block(payload: dict, schema: dtypes.Schema,
                     capacity: int | None = None) -> TableBlock:
    cols = {f.name: payload[f.name] for f in schema.fields}
    validity = {f.name: payload[f"__v_{f.name}"] for f in schema.fields}
    return TableBlock.from_numpy(cols, schema, validity, capacity)


def _payload_rows(payload: dict) -> int:
    return len(next(iter(payload.values()))) if payload else 0


def _span_notes(cp) -> dict:
    """What a stage program's ``dispatch`` span says of its group-by and
    its sort, as the walk's ``transform`` span does: the layout, and once
    the first trace has filled them the tiers (``compiler.CompiledProgram
    .notes``); nothing for a program with neither."""
    if cp.group_layout[0] is not None:
        cp.notes["group_layout"] = LAYOUT_NAMES[cp.group_layout[0]]
    return cp.notes


def _hold(budget: hbm.ChannelBudget, block: TableBlock,
          rows: int) -> "DeviceBlock | None":
    """``block`` held on the chip against ``budget``; None where its
    bytes would pass it."""
    nbytes = sum(c.data.nbytes + c.validity.nbytes
                 for c in block.columns.values())
    if not budget.take(nbytes):
        return None
    root_counters().group(component="dq").counter(
        "channel_device_bytes_peak").set_max(budget.peak)
    return DeviceBlock(block, rows, budget, nbytes)


def _release(items) -> None:
    for p in items:
        if isinstance(p, DeviceBlock):
            p.release()


@functools.partial(jax.jit, static_argnums=(1, 2))
def _route_dest(block: TableBlock, keys: tuple, n: int):
    """Each row's consumer, ``hash % n`` (``n`` past the live rows), and
    the rows each consumer gets. The hash is ``native.hash_rows``'s bit
    for bit, NULL keys folded in by their validity bit as there, so a row
    lands where the host path would send it."""
    h = hash_rows([block.columns[k] for k in keys])
    dest = jnp.where(block.row_mask(),
                     (h % jnp.uint64(n)).astype(jnp.int32), n)
    counts = jnp.sum(dest[None, :] == jnp.arange(n, dtype=jnp.int32)[:, None],
                     axis=1, dtype=jnp.int32)
    return dest, counts


def _cut_to(block: TableBlock, capacity: int) -> TableBlock:
    """The block's first ``capacity`` slots, zeros past its live rows
    (``compact`` leaves its padding's data unspecified), so that a part
    packed into a bucket leaves zeros where the next does not write."""
    live = jnp.arange(capacity, dtype=jnp.int32) < block.length
    return TableBlock(
        {n: Column(jnp.where(live, c.data[:capacity], 0),
                   c.validity[:capacity] & live)
         for n, c in block.columns.items()}, block.length, block.schema)


_cut = jax.jit(_cut_to, static_argnums=(1,))


@functools.partial(jax.jit, static_argnums=(3, 4))
def _take_part(block: TableBlock, dest, d, head: int,
               capacity: int) -> TableBlock:
    """The rows bound for consumer ``d``, in their order (``compact``),
    in ``capacity`` slots. The live rows are a prefix, so the compaction
    runs over the block's first ``head`` slots, the shape class of its
    live rows, and not over its capacity (a join's output has its probe
    side's)."""
    front = TableBlock({n: Column(c.data[:head], c.validity[:head])
                        for n, c in block.columns.items()},
                       block.length, block.schema)
    return _cut_to(kernels.compact(front, dest[:head] == d), capacity)


def _fit(block: TableBlock, rows: int) -> TableBlock:
    """A channel block held on the chip costs HBM for its rows' shape
    class, not its producer's capacity."""
    return _cut(block, min(shape_class(rows), block.capacity))


@host_ok("the split's one small fetch: the rows a consumer gets, which"
         " size its part and its bucket")
def _split(block: TableBlock, keys: tuple, n: int, rider=None):
    """The block as ``n`` parts by ``hash % n``, each in its rows' order
    and cut to their shape class: ``(part or None, rows)`` a consumer;
    and ``rider``, a device scalar or None, read back in the same fetch.

    The hash and the counts are one program, read back in one small
    fetch; then one ``compact`` a consumer, a program keyed by its
    part's shape class. A part's capacity follows its rows, which only
    the counts tell the host, and ``compact`` moves the rows of one
    selection: a stable partition in one pass would take a sort or a
    scatter, both slower on a TPU than a ``compact`` a consumer."""
    dest, counts = _route_dest(block, keys, n)
    with tracing.span("device.wait"):
        counts, rider = jax.device_get((counts, rider))
    counts = counts.tolist()
    head = min(shape_class(sum(counts)), block.capacity)
    return [(_take_part(block, dest, d, head, min(shape_class(rows), head))
             if rows else None, rows)
            for d, rows in enumerate(counts)], rider


@host_ok("the block's rows, the sync live_rows makes, with a rider")
def _live_rows(block: TableBlock, rider=None):
    """``block.live_rows()`` and ``rider``, a device scalar or None, read
    back in the same fetch."""
    if rider is None:
        return block.live_rows(), None
    with tracing.span("device.wait"):
        rows, rider = jax.device_get((block.length, rider))
    return int(rows), rider


class _Part:
    """A consumer group's share of a stage's output and its live rows:
    cut to their shape class for the chip, or copied out for the host,
    once however many consumers take it (a part of a split is cut)."""

    def __init__(self, block: TableBlock, rows: int, split: bool):
        self.block = block
        self.rows = rows
        self._fitted = block if split else None
        self._payload = None

    def fitted(self) -> TableBlock:
        if self._fitted is None:
            self._fitted = _fit(self.block, self.rows)
        return self._fitted

    def payload(self) -> dict:
        if self._payload is None:
            self._payload = block_to_payload(self.block)
        return self._payload


@functools.partial(jax.jit, static_argnums=(0, 1))
def _zeros(dtypes_: tuple, capacity: int) -> tuple:
    return tuple(jnp.zeros(capacity, dt) for dt in dtypes_)


@functools.partial(jax.jit, donate_argnums=(0,))
def _place(buf: tuple, part: TableBlock, at) -> tuple:
    """The part's slots written over the bucket ``buf`` (data then
    validity a column), its rows from slot ``at`` on. A part whose
    padding would run past the bucket takes the bucket's last slots of
    its capacity, its rows moved along to ``at`` there and the slots
    before ``at`` kept: one program a bucket's and a part's shape class,
    whatever the rows."""
    cap = part.capacity
    start = jnp.minimum(at, buf[0].shape[0] - cap)
    shift = at - start
    kept = jnp.arange(cap, dtype=jnp.int32) < shift
    src = [x for c in part.columns.values() for x in (c.data, c.validity)]
    out = []
    for b, s in zip(buf, src):
        s = jax.lax.dynamic_slice(jnp.pad(s.astype(b.dtype), (cap, 0)),
                                  (cap - shift,), (cap,))
        s = jnp.where(kept, jax.lax.dynamic_slice(b, (start,), (cap,)), s)
        out.append(jax.lax.dynamic_update_slice(b, s, (start,)))
    return tuple(out)


@tracing.span("dq.exchange")
def _pack(items: list, schema: dtypes.Schema) -> TableBlock:
    """A join bucket's side as one block. Host payloads alone take
    ``_assemble`` as ever; with a device block among them, the parts are
    written one after another on the chip, in arrival order, into a
    block at the shape class of their rows: what run_equi_join pads the
    host's concatenation to. A host payload is staged at the shape class
    of its rows, as a device part is cut, so the programs follow shape
    classes and not rows. A part's padding is zeros, which the next part
    writes over."""
    if all(isinstance(p, dict) for p in items):
        return _assemble(items, schema)
    total = sum(map(_item_rows, items))
    buf = _zeros(tuple(jnp.dtype(t) for f in schema.fields
                       for t in (f.type.physical, jnp.bool_)),
                 shape_class(total))
    at = 0
    for p in items:
        rows = _item_rows(p)
        if not rows:
            continue
        part = (p.block if isinstance(p, DeviceBlock)
                else payload_to_block(p, schema, shape_class(rows)))
        buf = _place(buf, part.select(schema.names), at)
        at += rows
    cols = {f.name: Column(buf[2 * i], buf[2 * i + 1])
            for i, f in enumerate(schema.fields)}
    return TableBlock(cols, jnp.asarray(total, dtype=jnp.int32), schema)


def _item_rows(item) -> int:
    return item.rows if isinstance(item, DeviceBlock) else \
        _payload_rows(item)


def _as_input(block: TableBlock, schema: dtypes.Schema) -> TableBlock:
    """A device block as its consumer's input: the columns in the
    consumer's schema's order, under that schema, as payload_to_block
    would give them."""
    return TableBlock({n: block.columns[n] for n in schema.names},
                      block.length, schema)


def _split_whole_input(program: Program):
    """A final program as (the steps before its first RollupStep or
    WindowStep, that step, the steps after it), each a Program or None;
    (the program, None, None) without such a step."""
    at = next((i for i, s in enumerate(program.steps)
               if isinstance(s, (RollupStep, WindowStep))), None)
    if at is None:
        return program, None, None
    steps = program.steps
    return (Program(steps[:at]) if at else None, steps[at],
            Program(steps[at + 1:]) if at + 1 < len(steps) else None)


class _WholeInput:
    """A stage's ROLLUP or ranking window, run once over the stage's
    whole input apart from the programs around it, so that its span and
    statement key hold its own work. A rollup's levels are sized by
    their rows (``rollup_counts``, read back in one small fetch) at
    shape classes, where a program traced blind would give each level
    the capacity of the finest; a window reads back nothing it does not
    need (its partitions only for a trace). ``post`` is the program
    after the step, compiled over its output."""

    def __init__(self, step, post, in_schema: dtypes.Schema, ordered,
                 dicts, key_spaces, aliases):
        self.step = step
        self.lowering, aux = resolve_whole_input_step(
            step, in_schema, dicts, key_spaces, aliases)
        self.aux = device_aux(aux)
        if isinstance(step, RollupStep):
            self.kind = "rollup"
            self.ordered = tuple(ordered) == tuple(step.keys)
            counts_of = self.lowering.counts_of
            self.schema = dtypes.Schema(tuple(
                dtypes.Field(n, in_schema.field(n).type, n not in counts_of)
                for n in self.lowering.names))
        else:
            self.kind = "window"
            self.schema = dtypes.Schema(tuple(
                f for f in in_schema.fields if f.name != step.out_name)
                + (dtypes.Field(step.out_name, dtypes.INT64, False),))
        self.post = None
        self.out_schema = self.schema
        if post is not None:
            self.post = compile_program(post, self.schema, dicts,
                                        key_spaces, dict_aliases=aliases)
            self._post_jit = jax.jit(self.post.run)
            self._post_aux = device_aux(self.post.aux)
            self.out_schema = self.post.out_schema

    @functools.cached_property
    def _counts(self):
        return jax.jit(lambda parts: self.lowering.counts(
            merge_blocks_device(list(parts)), self.ordered))

    @functools.cached_property
    def _levels(self):
        def levels(parts, aux, caps, out_cap):
            block, _ = self.lowering.apply(
                merge_blocks_device(list(parts)), aux, caps, out_cap,
                self.ordered)
            return TableBlock(block.columns, block.length, self.schema)

        return jax.jit(levels, static_argnums=(2, 3))

    @functools.cached_property
    def _window(self):
        def window(parts, aux):
            block = merge_blocks_device(list(parts))
            col, partitions = self.lowering.apply(
                block.columns, aux, block.row_mask())
            cols = {n: (col if n == self.step.out_name
                        else block.columns[n]) for n in self.schema.names}
            return TableBlock(cols, block.length, self.schema), partitions

        return jax.jit(window)

    @host_ok("a rollup's level rows, one small fetch that sizes its"
             " levels; a window's partitions, read for a trace's span")
    def run(self, blocks: list, rows_in: int | None,
            traced: bool) -> tuple[TableBlock, dict]:
        """The step over the stage's input blocks (``rows_in`` live rows
        where the caller knows them), then the program after it: (its
        output, what its span says)."""
        blocks = tuple(blocks)
        if self.kind == "rollup":
            with tracing.span("device.wait"):
                counts = jax.device_get(self._counts(blocks)).tolist()
            caps, room = [], sum(b.capacity for b in blocks)
            for rows in reversed(counts[:-1]):   # the finest level first
                room = min(room, shape_class(rows))
                caps.append(room)
            caps[-1] = 1
            out = self._levels(blocks, self.aux, tuple(reversed(caps)),
                               shape_class(sum(counts)))
            notes = {"rollup_levels": len(counts), "rows_in": counts[-1],
                     "groups": counts[::-1]}
        else:
            out, partitions = self._window(blocks, self.aux)
            if rows_in is None:
                rows_in = sum(b.live_rows() for b in blocks)
            notes = {"window": self.step.func, "rows_in": rows_in,
                     "key_words": self.key_words}
            if traced:
                with tracing.span("device.wait"):
                    notes["partitions"] = int(partitions)
        if self.post is not None:
            out = self._post_jit(out, self._post_aux)
        return out, notes

    @property
    def key_words(self) -> int:
        return sum(-(-self.schema.field(k).type.physical.itemsize // 4)
                   for k in self.step.partition + self.step.order_keys)

    def least_bytes(self, notes: dict) -> int:
        """The bytes the step cannot move less of: a rollup reads each
        column of its finest level once and writes every level's rows
        once; a window reads its keys once and writes its column once
        (data and a validity byte each)."""
        def width(names):
            return sum(self.schema.field(n).type.physical.itemsize + 1
                       for n in names)

        if self.kind == "rollup":
            return (notes["rows_in"] + sum(notes["groups"])) * width(
                self.schema.names)
        return notes["rows_in"] * width(
            self.step.partition + self.step.order_keys
            + (self.step.out_name,))


class _CompiledStage:
    """Per-stage compiled programs + schemas (shared by its tasks).

    ``in_schemas`` has one schema per stage input; join stages have two
    (probe, build) and every other stage exactly one shared schema."""

    def __init__(self, spec: StageSpec, in_schemas, dicts, key_spaces):
        self.in_schemas = list(in_schemas)
        in_schema = in_schemas[0]
        self.in_schema = in_schema
        if spec.join is not None:
            self.per_block = None
            self.final = None
            self.join = spec.join
            self.out_schema = _join_out_schema(
                spec.join, in_schemas[0], in_schemas[1])
            self.mid_schema = self.out_schema
            self.block_notes = self.final_notes = {}
            return
        self.join = None
        if spec.program is not None:
            self.per_block = compile_program(
                spec.program, in_schema, dicts, key_spaces,
                dict_aliases=dict(spec.dict_aliases),
            )
            mid = self.per_block.out_schema
            self._pb_jit = jax.jit(self.per_block.run)
            self._pb_aux = device_aux(self.per_block.aux)
            self.block_notes = _span_notes(self.per_block)
        else:
            self.per_block = None
            mid = in_schema
            self.block_notes = {}
        self.mid_schema = mid
        self.final = self.whole = None
        self.final_notes = {}
        self._f_aux = {}
        self.out_schema = mid
        self._finalize_jit = jax.jit(
            lambda parts, aux: merge_blocks_device(list(parts)))
        if spec.final_program is None:
            return
        from ydb_tpu.ssa import twophase

        aliases = dict(spec.dict_aliases)
        if spec.program is not None:
            aliases.update(twophase.dict_aliases(spec.program))
        pre, step, post = _split_whole_input(spec.final_program)
        if pre is not None:
            self.final = compile_program(pre, mid, dicts, key_spaces,
                                         dict_aliases=aliases)
            self._f_aux = device_aux(self.final.aux)
            self.final_notes = _span_notes(self.final)
            self.out_schema = self.final.out_schema
            final_run = self.final.run

            # the stage's whole final phase — merge accumulated partials
            # + final program — is ONE traced computation (the fused
            # finalize the single-chip ScanExecutor uses): partials never
            # round-trip through the host between merge and final
            @jax.jit
            def _finalize(parts, aux):
                return final_run(merge_blocks_device(list(parts)), aux)

            self._finalize_jit = _finalize
        if step is not None:
            self.whole = _WholeInput(
                step, post, self.out_schema,
                self.final.ordered if self.final is not None else (),
                dicts, key_spaces, aliases)
            self.out_schema = self.whole.out_schema

    def run_block(self, block: TableBlock) -> TableBlock:
        if self.per_block is None:
            return block
        return self._pb_jit(block, self._pb_aux)

    def run_join(self, probe: TableBlock, build: TableBlock):
        """Device-local join of this task's hash bucket (grace bucket
        join, mkql_grace_join_imp.cpp bucket processing). Shares the
        exact dispatch with the single-chip executor (run_equi_join);
        returns the joined block and a lookup's path flag (None for an
        expanding join)."""
        from ydb_tpu.ssa import join as join_kernels

        j = self.join
        return join_kernels.run_equi_join_path(
            probe, build, j.probe_keys, j.build_keys, kind=j.kind,
            suffix=j.suffix, expand=j.expand, payload=j.payload,
            probe_payload=j.probe_payload, build_payload=j.build_payload,
        )

    def run_final(self, blocks: list[TableBlock]) -> TableBlock:
        if self.final is None and len(blocks) == 1:
            return blocks[0]
        return self._finalize_jit(tuple(blocks), self._f_aux)


class ComputeActor(Actor):
    """Hosts one task (sync compute actor variant,
    dq_compute_actor_impl.h:95)."""

    def __init__(
        self,
        task: TaskSpec,
        compiled: _CompiledStage,
        channel_targets: dict[int, ActorId],  # my out channel -> consumer
        channel_specs: dict[int, ChannelSpec],
        sources: list[ColumnSource],
        result_target: ActorId | None,
        spiller: Spiller | None = None,
        window: int = DEFAULT_WINDOW,
        block_rows: int = 1 << 16,
        checkpoint_storage=None,
        restore_checkpoint: int | None = None,
        channel_budget: hbm.ChannelBudget | None = None,
    ):
        super().__init__()
        self.task = task
        self.compiled = compiled
        self.channel_targets = channel_targets
        self.channel_specs = channel_specs
        self.sources = sources
        self.result_target = result_target
        self.window = window
        self.block_rows = block_rows
        self.spiller = spiller or Spiller()
        # the HBM channel blocks hold: the process's, or a test's own
        self.budget = (hbm.channels() if channel_budget is None
                       else channel_budget)
        # the rows this task sent on by path, for its graph's dq span
        self.channel_rows = {"device": 0, "host": 0}
        self.abort_target: ActorId | None = None
        self._aborted = False
        # profile span for this task (opened at StartTask when a query
        # trace is active on the executer thread; finished with the
        # task's accumulated device-compute seconds)
        self._span = None
        self._compute_s = 0.0

        self._in_finished: set[int] = set()
        # agg stages accumulate partial states on the chip (DeviceBlock)
        # or, host-side, THROUGH the spiller (a spiller id: operator
        # spilling, beyond the memory quota the partials live in blobs,
        # not RAM — dq_spilling + combiner spill analog)
        self._acc: list = []
        # join stages accumulate their hash bucket per side, as the
        # channels deliver it, until the single device-local bucket join
        self._join_acc: dict[int, list] = {0: [], 1: []}
        self._unacked: dict[int, int] = {c: 0 for c in task.output_channels}
        self._parked: dict[int, collections.deque] = {
            c: collections.deque() for c in task.output_channels
        }
        self._next_seq: dict[int, int] = {c: 0 for c in task.output_channels}
        self._fin_pending: set[int] = set()
        self._done = False
        groups: dict[tuple[int, int], list[int]] = {}
        for c in task.output_channels:
            spec = channel_specs[c]
            groups.setdefault((spec.dst_stage, spec.input_index),
                              []).append(c)
        # hash slot p must land on the consumer task with dst_index p
        self._consumer_groups: list[list[int]] = [
            sorted(chs, key=lambda c: channel_specs[c].dst_index)
            for chs in groups.values()
        ]

        # ---- checkpoint state (IDqTaskRunner Save/Load analog) ----
        self.checkpoint_storage = checkpoint_storage
        self.coordinator_target: ActorId | None = None
        self._source_iter = None
        self._source_pos = 0          # blocks consumed from sources
        self._source_done = not sources
        self._aligned: dict[int, set] = {}   # ckpt id -> aligned channels
        self._barrier_of: dict[int, int] = {}  # channel -> pending ckpt
        # channel -> post-barrier msgs (FIFO; drained with popleft)
        self._held: dict[int, collections.deque] = {}
        if restore_checkpoint is not None and checkpoint_storage:
            state = checkpoint_storage.load_task(
                restore_checkpoint, task.task_id)
            if state is not None:
                self._acc = [self.spiller.put(p) for p in state["acc"]]
                self._join_acc = {
                    int(k): list(v)
                    for k, v in state.get("join_acc", {}).items()
                } or {0: [], 1: []}
                self._source_pos = state["source_pos"]
                self.block_rows = state["block_rows"]
                self._in_finished = set(state["in_finished"])

    # ---- input side ----

    def receive(self, message, sender):
        from ydb_tpu.dq.checkpoint import InjectCheckpoint
        from ydb_tpu.runtime.interconnect import Undelivered

        if isinstance(message, StartTask):
            parent = tracing.current_span()
            if parent is not None and self._span is None:
                # opened here, finished in _finish_output, other
                # tasks' messages in between: not lexical, so not
                # annotated (no profiler event, no part in self time)
                self._span = parent.child(
                    "dq.task", annotated=False).set(
                    stage=self.task.stage, task=self.task.task_id,
                    thread=threading.get_ident())
            self._start_source()
        elif isinstance(message, _PumpSource):
            if not self._aborted:
                self._pump_source()
        elif isinstance(message, WireTask):
            self.channel_targets.update(message.channel_targets)
            if message.result_target is not None:
                self.result_target = message.result_target
            if message.abort_target is not None:
                self.abort_target = message.abort_target
        elif isinstance(message, InjectCheckpoint):
            # source-side barrier injection: snapshot between blocks
            self._take_checkpoint(message.checkpoint_id)
        elif isinstance(message, ChannelData):
            self.send(sender, ChannelAck(message.channel_id, message.seq))
            if not self._aborted:
                self._on_channel_data(message)
        elif isinstance(message, ChannelAck):
            self._on_ack(message)
        elif isinstance(message, Undelivered):
            # a peer died with our channel data in flight: the query
            # cannot complete — abort it at the collector and stop
            # feeding the graph from this task
            self._aborted = True
            if self.abort_target is not None:
                self.send(self.abort_target, QueryAborted(
                    f"task {self.task.task_id}: channel data undelivered "
                    f"({message.reason})"))
        elif isinstance(message, QueryAborted):
            self._aborted = True
        else:
            raise TypeError(message)

    def _on_channel_data(self, message: ChannelData):
        from ydb_tpu.dq.checkpoint import BARRIER_KEY

        ch = message.channel_id
        # anything arriving on a channel that already delivered a
        # barrier for a pending checkpoint belongs to a later epoch:
        # hold it, in arrival order, until that checkpoint is taken.
        # Per-channel FIFO keeps multiple in-flight checkpoints
        # consistent — each release stops at the channel's next barrier.
        if ch in self._barrier_of:
            self._held.setdefault(ch, collections.deque()).append(message)
            return
        payload = message.payload
        if isinstance(payload, dict) and BARRIER_KEY in payload:
            self._register_barrier(int(payload[BARRIER_KEY]), ch)
            return
        self._apply_channel_data(message)

    def _apply_channel_data(self, message: ChannelData):
        payload = message.payload
        if payload is not None:
            if self.compiled.join is not None:
                idx = self.channel_specs[message.channel_id].input_index
                self._join_acc[idx].append(payload)
            elif isinstance(payload, DeviceBlock):
                self._ingest(_as_input(payload.block,
                                       self.compiled.in_schema))
                payload.release()
            else:
                self._ingest(payload_to_block(payload,
                                              self.compiled.in_schema))
        if message.finished:
            self._in_finished.add(message.channel_id)
            self._check_alignment()  # finished counts as aligned
            if self._in_finished >= set(self.task.input_channels):
                self._finish_input()

    # ---- checkpoint protocol ----

    def _register_barrier(self, checkpoint_id: int, channel_id: int):
        self._barrier_of[channel_id] = checkpoint_id
        self._aligned.setdefault(checkpoint_id, set()).add(channel_id)
        self._check_alignment()

    def _check_alignment(self):
        need = set(self.task.input_channels)
        while self._aligned:
            # checkpoints must be taken in id order; per-channel FIFO
            # guarantees the smallest pending id aligns first
            cid = min(self._aligned)
            if not (self._aligned[cid] | self._in_finished) >= need:
                return
            self._take_checkpoint(cid)

    def _take_checkpoint(self, checkpoint_id: int):
        from ydb_tpu.dq.checkpoint import BARRIER_KEY, TaskCheckpointed

        if self.checkpoint_storage is not None:
            self.checkpoint_storage.save_task(checkpoint_id,
                                              self.task.task_id, {
                # a checkpointed graph keeps every block on the host
                "acc": [self.spiller.peek(sid) for sid in self._acc],
                # join stages: both sides' accumulated bucket payloads
                "join_acc": {k: list(v)
                             for k, v in self._join_acc.items()},
                # position is counted in BLOCKS of this block size; the
                # restore pins block_rows so the count stays meaningful
                "source_pos": self._source_pos,
                "block_rows": self.block_rows,
                "in_finished": sorted(self._in_finished),
            })
        # forward the barrier in band on EVERY output channel (parks
        # behind pending data, so it cannot overtake blocks)
        if not isinstance(self.task.stage_spec.output, ResultOutput):
            # numpy value so the credit queue/spiller treat the barrier
            # exactly like a (tiny) data payload
            barrier = {BARRIER_KEY: np.asarray(checkpoint_id)}
            for ch in self.task.output_channels:
                self._send_channel(ch, barrier)
        if self.coordinator_target is not None:
            self.send(self.coordinator_target,
                      TaskCheckpointed(self.task.task_id, checkpoint_id))
        # release each aligned channel's held messages up to (and
        # registering) that channel's next barrier, in arrival order
        chans = self._aligned.pop(checkpoint_id, set())
        for ch in sorted(chans):
            if self._barrier_of.get(ch) == checkpoint_id:
                del self._barrier_of[ch]
            q = self._held.get(ch, collections.deque())
            while q:
                msg = q.popleft()
                payload = msg.payload
                if isinstance(payload, dict) and BARRIER_KEY in payload:
                    self._register_barrier(int(payload[BARRIER_KEY]), ch)
                    break
                self._apply_channel_data(msg)
            if not q:
                self._held.pop(ch, None)

    # ---- source streaming ----

    def _start_source(self):
        # scan stages stream only the program's required columns (the
        # scan-executor projection, ScanExecutor.read_cols): stream
        # sources then skip unread chunks entirely
        names = None
        if self.compiled.per_block is not None:
            names = self.compiled.in_schema.names

        def blocks(skip: int):
            # checkpoint resume: seek in O(1) per source rather than
            # materializing and discarding consumed blocks (n_blocks is
            # only required of sources that actually resume)
            for source in self.sources:
                if skip:
                    nb = source.n_blocks(self.block_rows)
                    if skip >= nb:
                        skip -= nb
                        continue
                yield from source.blocks(self.block_rows, columns=names,
                                         start_block=skip)
                skip = 0

        self._source_iter = blocks(self._source_pos)
        if self.sources:
            self.send(self.self_id, _PumpSource())
        elif not self.task.input_channels:
            self._finish_input()

    def _pump_source(self):
        # block-boundary cancellation: a statement past its deadline
        # stops pumping and aborts the whole graph (the collector turns
        # this into a typed StatementCancelled at the executor)
        from ydb_tpu.chaos import deadline as statement_deadline

        dl = statement_deadline.current()
        if dl is not None and dl.expired():
            self._aborted = True
            if self.abort_target is not None:
                self.send(self.abort_target, QueryAborted(
                    f"task {self.task.task_id}: statement deadline "
                    "exceeded"))
            return
        with tracing.span("scan.pull"):
            blk = next(self._source_iter, None)
        if blk is None:
            if not self.task.input_channels:
                self._finish_input()
            return
        self._source_pos += 1
        self._ingest(blk)
        self.send(self.self_id, _PumpSource())

    def _timed(self, fn, *args, notes=None):
        """Charge a stage-program dispatch to the task's profile span
        (pass-through when no trace is active); the span says what the
        program's ``notes`` say of its group-by and its sort."""
        if self._span is None:
            return fn(*args)
        t0 = time.perf_counter()
        with tracing.span("dispatch", program="dq_stage") as sp:
            out = fn(*args)
            if notes:
                sp.set(**notes)
        self._compute_s += time.perf_counter() - t0
        return out

    def _ingest(self, block: TableBlock):
        spec = self.task.stage_spec
        out = self._timed(self.compiled.run_block, block,
                          notes=self.compiled.block_notes)
        if spec.final_program is not None:
            # aggregate stage: per-block partial, accumulated
            self._acc.append(self._keep(out))
        else:
            self._emit(out)

    def _keep(self, partial: TableBlock):
        """An aggregate's partial held to its final phase: on the chip
        at its rows' shape class where the graph may (the budget
        counting it), else its host payload through the spiller (blocks
        beyond the quota go to blobs)."""
        if self.checkpoint_storage is None:
            rows = partial.live_rows()
            cap = shape_class(rows)  # merged, never packed: no zeros
            if cap < partial.capacity:
                partial = _cut(partial, cap)
            held = _hold(self.budget, partial, rows)
            if held is not None:
                return held
        return self.spiller.put(block_to_payload(partial))

    def _finish_input(self):
        spec = self.task.stage_spec
        if self.compiled.join is not None:
            self._join_bucket()
            self._finish_output()
            return
        if spec.final_program is not None:
            mid = self.compiled.mid_schema
            # empty input still finalizes (COUNT over nothing etc.)
            blocks = [
                _as_input(p.block, mid) if isinstance(p, DeviceBlock)
                else payload_to_block(self.spiller.get(p), mid)
                for p in self._acc
            ] or [_empty_block(mid)]
            if self.compiled.whole is None:
                self._emit(self._timed(self.compiled.run_final, blocks,
                                       notes=self.compiled.final_notes))
            else:
                self._whole_input(blocks)
            _release(self._acc)
            self._acc = []
        self._finish_output()

    def _whole_input(self, blocks: list):
        """A stage whose final program holds a ROLLUP or a ranking
        window: the program before it (the merged group-by a rollup
        rolls up) in one ``dispatch`` span, which, traced, waits for its
        rows; then the step, the program after it and the output's
        routing in a second, which says what the step did
        (``rollup_levels``, ``rows_in``, ``groups`` a level, the finest
        first; ``window``, ``rows_in``, ``key_words`` and, traced,
        ``partitions``). The process counts
        it (``component=rollup | window``) whether or not a trace is
        active; untraced, nothing is read back that the step does not
        need: a window's rows are its input blocks', already known,
        where no program runs before it."""
        whole = self.compiled.whole
        traced = self._span is not None
        rows_in = sum(map(_item_rows, self._acc))
        if self.compiled.final is not None:
            def pre(parts):
                out = self.compiled.run_final(parts)
                if traced:      # the span holds the program's own time
                    out.live_rows()
                return out

            blocks = [self._timed(pre, blocks,
                                  notes=self.compiled.final_notes)]
            rows_in = None
        with tracing.span("dispatch", program="dq_stage") as sp:
            t0 = time.perf_counter()
            out, notes = whole.run(blocks, rows_in, traced)
            self._compute_s += time.perf_counter() - t0
            sp.set(**notes)
            self._emit(out)
        g = root_counters().group(component=whole.kind)
        if whole.kind == "rollup":
            g.counter("rollups").inc()
            g.counter("levels").inc(notes["rollup_levels"])
            g.counter("groups").inc(sum(notes["groups"]))
        else:
            g.counter("windows").inc()
        g.counter("rows_in").inc(notes["rows_in"])
        g.counter("bytes_least").inc(whole.least_bytes(notes))

    def _join_bucket(self):
        """The join stage's one dispatch: the bucket's two sides packed
        on the device, joined there, the output routed, all under the
        stage's ``dispatch`` span, which says the join
        (``join=lookup|expand``, ``kind``, a lookup's ``lookup=direct|
        search``) and the rows of its sides and of its output. The
        process counts the join's rows and a lookup's path
        (``component=join``) whether or not a trace is active; the path
        is read back in the fetch that routes the output."""
        j = self.compiled.join
        probe_rows, build_rows = (sum(map(_item_rows, self._join_acc[i]))
                                  for i in (0, 1))
        with tracing.span("dispatch", program="dq_stage") as sp:
            probe = _pack(self._join_acc[0], self.compiled.in_schemas[0])
            build = _pack(self._join_acc[1], self.compiled.in_schemas[1])
            # the bucket's parts go once packed (the packed sides are the
            # join's own, in the programs' share of the device, and go
            # once joined): only the output lives through its routing
            for side in self._join_acc.values():
                _release(side)
            self._join_acc = {0: [], 1: []}
            t0 = time.perf_counter()
            out, direct = self.compiled.run_join(probe, build)
            self._compute_s += time.perf_counter() - t0
            del probe, build
            out_rows, direct = self._emit(out, direct)
            path = (None if direct is None
                    else "direct" if bool(direct) else "search")
            sp.set(join="expand" if j.expand else "lookup", kind=j.kind,
                   probe_rows=probe_rows, build_rows=build_rows,
                   out_rows=out_rows)
            if path is not None:
                sp.set(lookup=path)
        g = root_counters().group(component="join")
        if path is not None:
            g.group(path=path).counter("lookups").inc()
        g.counter("joins").inc()
        g.counter("probe_rows").inc(probe_rows)
        g.counter("build_rows").inc(build_rows)

    # ---- output side ----

    def _emit(self, block: TableBlock, rider=None) -> tuple:
        """Send a stage's output on: the result leaves the device, the
        other outputs are routed; returns its rows and ``rider``, a
        device scalar or None, read back in the first fetch that sending
        makes (a fetched array keeps its value, so the copy out that
        follows waits for nothing again)."""
        if int(block.capacity) == 0:
            return 0, rider
        out = self.task.stage_spec.output
        if not isinstance(out, ResultOutput):
            return self._route(block, out, rider)
        if rider is not None:
            _, rider = _live_rows(block, rider)
        payload = block_to_payload(block)
        rows = _payload_rows(payload)
        self._count_channel("host", rows, "result")
        self.send(self.result_target, ResultData(payload, False))
        return rows, rider

    def _host_reason(self, ch: int) -> str | None:
        """Why channel ``ch`` carries host payloads, or None where it
        may carry device blocks: a checkpointed graph saves host
        payloads, and a consumer on another node gets bytes."""
        if self.checkpoint_storage is not None:
            return "checkpoint"
        if self.channel_targets[ch].node != self.system.node:
            return "remote"
        return None

    @tracing.span("dq.exchange")
    def _route(self, block: TableBlock, out, rider=None) -> tuple:
        """Each consumer edge gets the whole routed stream: a hash
        partition over several tasks split on the chip, skipping the
        consumers it gives no rows, anything else the whole block, empty
        or not. Returns the block's rows and ``rider`` as ``_emit``."""
        rows = None
        splits: dict[int, list] = {}
        whole = None
        for chans in self._consumer_groups:
            n = len(chans)
            if isinstance(out, HashPartition) and n > 1:
                if n not in splits:
                    split, rider = _split(block, tuple(out.keys), n, rider)
                    splits[n] = [_Part(part, r, split=True)
                                 for part, r in split]
                parts = splits[n]
                rows = sum(p.rows for p in parts)
            else:  # Broadcast/UnionAll, or a single-task hash consumer
                if whole is None:
                    if rows is None:
                        rows, rider = _live_rows(block, rider)
                    whole = _Part(block, rows, split=False)
                parts = [whole] * n
                rows = whole.rows
            for ch, part in zip(chans, parts):
                if part.rows or part is whole:
                    self._send_part(ch, part)
        return rows, rider

    def _send_part(self, ch: int, part: _Part):
        """One consumer's part: a device block where the channel and the
        HBM budget allow, else its host payload."""
        reason = self._host_reason(ch)
        if reason is None:
            held = _hold(self.budget, part.fitted(), part.rows)
            if held is not None:
                self._count_channel("device", part.rows)
                self._send_channel(ch, held)
                return
            reason = "budget"
        self._count_channel("host", part.rows, reason)
        self._send_channel(ch, part.payload())

    def _count_channel(self, path: str, rows: int,
                       reason: str | None = None) -> None:
        self.channel_rows[path] += rows
        g = root_counters().group(component="dq")
        g.group(path=path).counter("channel_blocks").inc()
        g.group(path=path).counter("channel_rows").inc(rows)
        if reason is not None:
            g.group(reason=reason).counter("channel_host_reason").inc()

    def _send_channel(self, ch: int, payload: "dict | DeviceBlock"):
        if self._unacked[ch] >= self.window:
            # a device block waits on the chip, under the budget that
            # admitted it; a host payload in the spiller
            self._parked[ch].append(
                payload if isinstance(payload, DeviceBlock)
                else self.spiller.put(payload))
            return
        self._dispatch(ch, payload, finished=False)

    def _dispatch(self, ch: int, payload: "dict | DeviceBlock | None",
                  finished: bool):
        seq = self._next_seq[ch]
        self._next_seq[ch] += 1
        if payload is not None:
            self._unacked[ch] += 1
        self.send(self.channel_targets[ch],
                  ChannelData(ch, seq, payload, finished))

    def _finish_output(self):
        self._done = True
        if self._span is not None:
            self._span.set(compute_seconds=round(self._compute_s, 6))
            self._span.finish()
            self._span = None
        if isinstance(self.task.stage_spec.output, ResultOutput):
            self.send(self.result_target, ResultData(None, True))
            return
        for ch in self.task.output_channels:
            if self._parked[ch] or self._unacked[ch] > 0:
                self._fin_pending.add(ch)
            else:
                self._dispatch(ch, None, finished=True)

    def _on_ack(self, ack: ChannelAck):
        ch = ack.channel_id
        self._unacked[ch] -= 1
        while self._parked[ch] and self._unacked[ch] < self.window:
            item = self._parked[ch].popleft()
            self._dispatch(ch, item if isinstance(item, DeviceBlock)
                           else self.spiller.get(item), finished=False)
        if (
            ch in self._fin_pending
            and not self._parked[ch]
            and self._unacked[ch] == 0
        ):
            self._fin_pending.discard(ch)
            self._dispatch(ch, None, finished=True)


@tracing.span("dq.exchange")
def _assemble(payloads: list[dict], schema: dtypes.Schema) -> TableBlock:
    """Concat channel payloads into one block (capacity >= 1 so the join
    kernels' searchsorted shapes stay valid on empty sides)."""
    cols = {}
    validity = {}
    for f in schema.fields:
        parts = [p[f.name] for p in payloads]
        vparts = [p[f"__v_{f.name}"] for p in payloads]
        cols[f.name] = (np.concatenate(parts) if parts
                        else np.empty(0, dtype=f.type.physical))
        validity[f.name] = (np.concatenate(vparts) if vparts
                            else np.empty(0, dtype=bool))
    n = len(next(iter(cols.values()))) if cols else 0
    return TableBlock.from_numpy(cols, schema, validity,
                                 capacity=max(n, 1))


def _join_out_schema(j, probe_schema: dtypes.Schema,
                     build_schema: dtypes.Schema) -> dtypes.Schema:
    """Static output schema of a join stage."""
    left = j.kind == "left"  # NULL-extended build payload is nullable
    if not j.expand:
        if j.kind in ("semi", "anti"):
            return probe_schema
        fields = list(probe_schema.fields)
        for n in j.payload:
            f = build_schema.field(n)
            fields.append(dtypes.Field(n + j.suffix, f.type,
                                       f.nullable or left))
        return dtypes.Schema(tuple(fields))
    fields = [probe_schema.field(n) for n in j.probe_payload]
    for n in j.build_payload:
        f = build_schema.field(n)
        fields.append(dtypes.Field(n + j.suffix, f.type,
                                   f.nullable or left))
    return dtypes.Schema(tuple(fields))


@host_ok("zero-row result block: one bounded 0-byte alloc per column,"
         " only when a stage produced no rows")
def _empty_block(schema: dtypes.Schema) -> TableBlock:
    cols = {
        f.name: np.empty(0, dtype=f.type.physical) for f in schema.fields
    }
    return TableBlock.from_numpy(cols, schema, capacity=1)


class ResultCollector(Actor):
    def __init__(self, schema: dtypes.Schema):
        super().__init__()
        self.schema = schema
        self.payloads: list[dict] = []
        self.done = False
        self.error: str | None = None

    def receive(self, message, sender):
        from ydb_tpu.runtime.interconnect import Undelivered

        if isinstance(message, QueryAborted):
            if self.error is None:
                self.error = message.reason
            return
        if isinstance(message, Undelivered):
            # a liveness ping (or any collector-sent envelope) bounced:
            # the peer node is gone — fail the query
            if self.error is None:
                self.error = f"peer unreachable: {message.reason}"
            return
        assert isinstance(message, ResultData)
        if message.payload is not None:
            self.payloads.append(message.payload)
        if message.finished:
            self.done = True

    def result_block(self) -> TableBlock:
        if not self.payloads:
            return _empty_block(self.schema)
        blocks = [payload_to_block(p, self.schema) for p in self.payloads]
        return blocks[0] if len(blocks) == 1 else concat_blocks(blocks)

    def table(self) -> OracleTable:
        return OracleTable.from_block(self.result_block())


def task_partitions(sources: dict[str, list], task: TaskSpec) -> list:
    """Source partitions assigned to one task: task p of an N-task stage
    reads partitions p, p+N, p+2N, … so every partition is read exactly
    once for any task-count / partition-count ratio. The ONE assignment
    rule — local build, remote task start, and the executer all share it
    (changing it anywhere else would silently double-read or drop data)."""
    out: list = []
    for inp in task.stage_spec.inputs:
        if isinstance(inp, SourceInput):
            parts = sources.get(inp.source_id, [])
            out.extend(parts[task.partition::task.stage_spec.tasks])
    return out


def compile_stages(
    stages: list[StageSpec],
    source_schemas: dict[str, dtypes.Schema],
    dicts=None,
    key_spaces=None,
    compile_cache: dict | None = None,
) -> list[_CompiledStage]:
    """Compile every stage, flowing schemas source -> downstream. Needs
    only the SOURCE SCHEMAS, not the data — a remote node re-derives the
    whole compiled chain from the shipped stage specs (the task-start
    path, kqp_node_service.cpp:121)."""
    from ydb_tpu.engine.scan import required_columns

    compiled: list[_CompiledStage] = []
    cache_hits = cache_misses = 0
    for si, spec in enumerate(stages):
        in_schemas = []
        for inp in spec.inputs:
            if isinstance(inp, SourceInput):
                sch = source_schemas[inp.source_id]
                if spec.program is not None:
                    # scan projection: compile (and later stream) only
                    # the program's required columns
                    sch = sch.select(required_columns(spec.program, sch))
                in_schemas.append(sch)
            else:
                in_schemas.append(compiled[inp.from_stage].out_schema)
        if not in_schemas:
            raise ValueError("stage with no inputs")
        if spec.join is not None:
            if len(in_schemas) != 2:
                raise ValueError(
                    f"join stage {si} needs exactly (probe, build) inputs")
        elif any(s != in_schemas[0] for s in in_schemas[1:]):
            # every channel payload decodes with one schema; unequal
            # upstream schemas would silently mislabel columns
            raise ValueError(
                f"stage {si}: all inputs must share one schema, got "
                f"{[s.names for s in in_schemas]}"
            )
        ck = None
        if compile_cache is not None:
            # dicts participate by identity (aux tables bake dictionary
            # contents); key_spaces by value — mixing either across one
            # cache dict must miss, not alias
            ck = ("dq_stage", spec.program, spec.final_program, spec.join,
                  spec.dict_aliases, tuple(in_schemas), id(dicts),
                  tuple(sorted(key_spaces.items()))
                  if key_spaces else None)
            hit = compile_cache.get(ck)
            if hit is not None:
                cache_hits += 1
                compiled.append(hit)
                continue
        cache_misses += 1
        stage = _CompiledStage(spec, in_schemas, dicts, key_spaces)
        if ck is not None:
            compile_cache[ck] = stage
        compiled.append(stage)
    # stage-compile cache effectiveness rides the query trace (the DQ
    # half of the compile-vs-execute attribution)
    tracing.annotate(dq_compile_hits=cache_hits,
                     dq_compile_misses=cache_misses)
    return compiled


@dataclasses.dataclass
class GraphHandle:
    """A built-but-not-finished dataflow: the executer's live view."""

    actors: list
    actor_of_task: dict
    collector: "ResultCollector"
    collector_id: ActorId
    systems: list
    tasks: list
    result_stage: int
    coordinator: object = None
    coordinator_id: ActorId | None = None

    def start(self):
        sys_by_node = {s.node: s for s in self.systems}
        for t in self.tasks:
            aid = self.actor_of_task[t.task_id]
            sys_by_node[aid.node].send(aid, StartTask())

    def channel_rows(self) -> dict[str, int]:
        """The rows the graph's channels carried, by path."""
        return {p: sum(a.channel_rows[p] for a in self.actors)
                for p in ("device", "host")}

    def close(self):
        """Release per-task resources once the graph is finished or
        abandoned. Spillers hold blobs that only ``get`` deletes, so a
        graph torn down with parked/accumulated ids (abort, deadline
        cancellation) must close them here or the blobs leak for the
        store's lifetime. Idempotent."""
        for a in self.actors:
            a.spiller.close()


def build_stage_graph(
    stages: list[StageSpec],
    sources: dict[str, list[ColumnSource]],
    runtime,
    dicts=None,
    key_spaces=None,
    spill_quota_bytes: int = 64 << 20,
    window: int = DEFAULT_WINDOW,
    checkpoint_storage=None,
    restore_checkpoint: int | None = None,
    block_rows: int = 1 << 16,
    compile_cache: dict | None = None,
    channel_budget: hbm.ChannelBudget | None = None,
) -> GraphHandle:
    """Compile stages, place tasks round-robin over the runtime's nodes,
    wire channels (the executer-actor shape, kqp_executer_impl.h:120 +
    planner kqp_planner.cpp:116). With ``checkpoint_storage``, a
    CheckpointCoordinator is attached; with ``restore_checkpoint``,
    every task loads its saved state and sources resume mid-stream.
    ``compile_cache`` memoizes compiled stages across graphs (the
    computation-pattern-cache seam the single-chip executor has).
    ``channel_budget``: what the graph's channel blocks hold HBM
    against, by default the process's (``engine/hbm.channels()``); a
    test passes its own."""
    # unreferenced sources may have zero partitions; referenced ones
    # must not (compile_stages then raises KeyError, as before)
    source_schemas = {sid: parts[0].schema
                      for sid, parts in sources.items() if parts}
    compiled = compile_stages(stages, source_schemas, dicts, key_spaces,
                              compile_cache)

    tasks, channels, result_stage = build_tasks(stages)
    systems = list(runtime.nodes.values()) if hasattr(runtime, "nodes") \
        else [runtime]
    collector = ResultCollector(compiled[result_stage].out_schema)
    collector_id = systems[0].register(collector)

    # place tasks, then wire channel targets
    actor_of_task: dict[int, ActorId] = {}
    actors: list[ComputeActor] = []
    chan_by_id = {c.channel_id: c for c in channels}
    for i, t in enumerate(tasks):
        srcs = task_partitions(sources, t)
        a = ComputeActor(
            t, compiled[t.stage], {}, chan_by_id, srcs,
            collector_id,
            spiller=Spiller(mem_quota_bytes=spill_quota_bytes,
                            prefix=f"spill/task{t.task_id}"),
            window=window,
            block_rows=block_rows,
            checkpoint_storage=checkpoint_storage,
            restore_checkpoint=restore_checkpoint,
            channel_budget=channel_budget,
        )
        sys_i = systems[i % len(systems)]
        actor_of_task[t.task_id] = sys_i.register(a)
        actors.append(a)
    for a in actors:
        for ch in a.task.output_channels:
            a.channel_targets[ch] = actor_of_task[chan_by_id[ch].dst_task]

    handle = GraphHandle(actors, actor_of_task, collector, collector_id,
                         systems, tasks, result_stage)
    if checkpoint_storage is not None:
        from ydb_tpu.dq.checkpoint import CheckpointCoordinator

        source_task_ids = [
            actor_of_task[t.task_id] for t in tasks
            if any(isinstance(i, SourceInput) for i in t.stage_spec.inputs)
        ]
        coord = CheckpointCoordinator(
            checkpoint_storage, source_task_ids, n_tasks=len(tasks),
            start_id=restore_checkpoint or 0)
        coord_id = systems[0].register(coord)
        for a in actors:
            a.coordinator_target = coord_id
        handle.coordinator = coord
        handle.coordinator_id = coord_id
    return handle


def run_stage_graph(
    stages: list[StageSpec],
    sources: dict[str, list[ColumnSource]],
    runtime,
    dicts=None,
    key_spaces=None,
    spill_quota_bytes: int = 64 << 20,
    window: int = DEFAULT_WINDOW,
    checkpoint_storage=None,
    restore_checkpoint: int | None = None,
    block_rows: int = 1 << 16,
    compile_cache: dict | None = None,
    channel_budget: hbm.ChannelBudget | None = None,
) -> OracleTable:
    """Build + run to completion, return the result table."""
    handle = build_stage_graph(
        stages, sources, runtime, dicts, key_spaces, spill_quota_bytes,
        window, checkpoint_storage, restore_checkpoint, block_rows,
        compile_cache, channel_budget)
    try:
        handle.start()
        if hasattr(runtime, "dispatch"):
            runtime.dispatch()
        else:
            runtime.run()
        err = handle.collector.error
        if err is not None and "deadline" in err:
            from ydb_tpu.chaos.deadline import StatementCancelled

            raise StatementCancelled(err)
        if not handle.collector.done:
            raise RuntimeError("stage graph did not complete")
        return handle.collector.table()
    finally:
        handle.close()
