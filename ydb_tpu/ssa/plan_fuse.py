"""Whole-plan single-trace lowering: one jitted computation per plan.

The per-node executor walk dispatches every plan fragment separately —
each scan program, join, partial→final merge and top-k boundary exits
XLA, hops through host Python, and re-enters a separately jitted
function. "Query Processing on Tensor Computation Runtimes" compiles
full TPC-H queries to single tensor programs; this module is that
lowering for the plan tree (ydb_tpu.plan.nodes): walk the tree once at
build time, compile every SSA program (span-free ``_compile_program`` —
the whole build is attributed to ONE ``ssa.compile`` span), and emit a
single traceable function

    run_all(inputs, aux) -> (result TableBlock, expand totals)

over a dict of staged input blocks. ``jax.jit(..., donate_argnums=(0,))``
donates the staged inputs so XLA reuses their buffers for intermediates
— nothing round-trips through the host between fragments.

Shape classes: every scanned table stages into a block whose capacity is
its row count rounded up to a size class (capacity quantum for small
tables, quarter-of-power-of-two steps beyond — at most 25% padding).
The jitted function retraces per (plan fingerprint, shape-class vector)
— the executor caches one FusedPlan per class in the cluster compile
cache, so re-running a plan over different data of the same class reuses
the compiled computation. Capacities only move dead padding around: the
join/group-by kernels mask padding by liveness, so fused results are
bit-identical to the per-fragment walk (asserted by
tests/test_plan_fuse.py).

Fusibility (``plan_signature`` returns None otherwise; the executor
falls back to the per-node walk):

  * every scanned table present in ``db.sources`` with
    ``num_rows <= FUSE_MAX_ROWS`` (beyond that the walk's block
    streaming + two-phase partials bound memory; a fused trace would
    stage the whole table);
  * no ``UdfCall`` in any program — UDFs lower through
    ``jax.pure_callback`` (a host round trip), exactly the boundary
    fusion exists to remove;
  * join shapes the kernels support (<= 2 key columns, lookup
    inner/left/semi/anti, expand inner/left).

Expand joins get a static output capacity (probe bound * fanout_hint);
the traced total match count is returned to the host, and on overflow
the executor grows the capacity (``FusedPlan.grow``) and re-dispatches —
the cached plan keeps the grown capacity for later statements, exactly
like ``run_equi_join``'s retry ladder.

Env gates: ``YDB_TPU_FUSE_PLAN=0`` disables fusion (escape hatch);
``YDB_TPU_FUSE_MAX_ROWS`` moves the streaming cutoff;
``YDB_TPU_FUSE_DONATE=0`` keeps inputs undonated (debugging — a donated
block is dead after the dispatch).
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import os
import threading
import time
from typing import Callable

import jax
import jax.numpy as jnp

from ydb_tpu import dtypes
from ydb_tpu.analysis import host_ok, memsan
from ydb_tpu.blocks.block import (
    DEFAULT_CAPACITY_QUANTUM,
    Column,
    TableBlock,
    device_aux,
)
from ydb_tpu.engine.scan import merge_blocks_device, required_columns
from ydb_tpu.ssa import join as join_kernels
from ydb_tpu.ssa.compiler import _compile_program
from ydb_tpu.ssa.program import (
    AssignStep,
    Call,
    FilterStep,
    Program,
    UdfCall,
)
from ydb_tpu.plan.nodes import (
    Concat,
    ExpandJoin,
    LookupJoin,
    PlanNode,
    TableScan,
    Transform,
)

#: in-process override: True/False forces fusion on/off regardless of the
#: environment (tests pick the executor with it); None defers to
#: YDB_TPU_FUSE_PLAN
FUSE_FORCE: bool | None = None

#: tables above this row count keep the streaming walk. Two reasons the
#: cutoff sits where it does: (1) memory — the walk's block loop +
#: two-phase partials bound residency while a fused trace stages whole
#: tables; (2) regime — fusion pays off where per-fragment dispatch and
#: host hops dominate (short interactive queries: measured ~2x at ~6k
#: probe rows, ~1.6x at ~12k), while past ~10^5 rows the kernels are
#: compute-bound and the walk's tighter 1024-quantum padding edges out
#: the shape-class padding. Well under the walk's scan block size
#: (1 << 20), so any fusible table was a SINGLE block on the
#: per-fragment path anyway — identical operand shapes, bit-identical
#: results, no extra memory.
FUSE_MAX_ROWS = int(os.environ.get("YDB_TPU_FUSE_MAX_ROWS", str(1 << 17)))

_DONATE = os.environ.get("YDB_TPU_FUSE_DONATE", "1") not in (
    "0", "", "off")


def fusion_enabled() -> bool:
    if FUSE_FORCE is not None:
        return FUSE_FORCE
    return os.environ.get("YDB_TPU_FUSE_PLAN", "1") not in (
        "0", "", "off")


def shape_class(n: int) -> int:
    """Static staging capacity for an n-row table.

    Size-class quantization (jemalloc-style): small tables round to the
    capacity quantum; beyond 8 quanta, to quarter-of-power-of-two steps
    (..., 5*2^k, 6*2^k, 7*2^k, 2^(k+3), ...). The class count stays
    logarithmic in table size — growing a table by one row must not
    recompile the plan — while dead padding (staged AND computed on
    every dispatch) is bounded at 25%, where plain next-power-of-two
    classes waste up to 2x."""
    q = DEFAULT_CAPACITY_QUANTUM
    n = max(int(n), 1)
    if n <= 8 * q:
        return -(-n // q) * q
    step = 1 << ((n - 1).bit_length() - 3)
    return -(-n // step) * step


class Unfusible(Exception):
    """Raised at build time when a plan that looked fusible is not (the
    executor falls back to the per-node walk)."""


@functools.partial(jax.jit, static_argnums=(1,))
def fit_blocks(blocks: tuple, capacity: int) -> TableBlock:
    """Merge a scan's streamed blocks and fit them to the shape-class
    capacity, in one traced dispatch: live rows compact to the front
    (merge_blocks_device), columns slice or zero-pad to ``capacity``.
    Live rows never exceed ``capacity`` — the shape class derives from
    the source's num_rows upper bound — so the slice only drops padding.
    The outputs are fresh device buffers even for a single pass-through
    block (no donation here, so XLA cannot alias inputs to outputs):
    staged blocks are safe for the fused dispatch to donate even when
    the source block came from the device block cache."""
    b = merge_blocks_device(list(blocks))
    cols = {}
    for n in b.schema.names:
        c = b.columns[n]
        d, v = c.data, c.validity
        if d.shape[0] > capacity:
            d, v = d[:capacity], v[:capacity]
        elif d.shape[0] < capacity:
            pad = capacity - d.shape[0]
            d = jnp.concatenate([d, jnp.zeros(pad, d.dtype)])
            v = jnp.concatenate([v, jnp.zeros(pad, jnp.bool_)])
        cols[n] = Column(d, v)
    return TableBlock(cols, b.length, b.schema)


def _program_has_udf(program: Program | None) -> bool:
    if program is None:
        return False

    def expr_has(e) -> bool:
        if isinstance(e, UdfCall):
            return True
        if isinstance(e, Call):
            return any(expr_has(a) for a in e.args)
        return False

    for s in program.steps:
        if isinstance(s, (AssignStep, FilterStep)) and expr_has(s.expr):
            return True
    return False


@dataclasses.dataclass(frozen=True)
class ScanSite:
    """One distinct TableScan node's staging contract: which columns to
    stage, under which schema, at which shape-class capacity."""

    key: str                      # input-dict key ("t0", "t1", ...)
    table: str
    node: TableScan
    read_cols: tuple[str, ...]
    in_schema: dtypes.Schema
    capacity: int


@dataclasses.dataclass
class PlanSignature:
    """A fusible plan's shape: scan sites + fragment count. The cache
    key (plan fingerprint + shape-class vector) derives from this."""

    plan: PlanNode
    sites: list[ScanSite]
    fused_stages: int  # plan fragments folded into the one trace

    def cache_key(self, db) -> tuple:
        return (
            "plan_fuse",
            self.plan,
            tuple((s.table, s.capacity, s.read_cols, s.in_schema)
                  for s in self.sites),
            id(db.dicts),
            tuple(sorted(db.key_spaces.items())) if db.key_spaces
            else None,
        )


def plan_signature(plan: PlanNode, db) -> PlanSignature | None:
    """Classify a plan: its scan sites and shape classes when the whole
    tree is fusible, None otherwise. Cheap (no compilation) — the
    executor calls this per statement before consulting the cache."""
    sites: list[ScanSite] = []
    by_node: dict[int, ScanSite] = {}
    stages = 0

    def visit(node) -> bool:
        nonlocal stages
        if id(node) in by_node:
            return True  # shared subtree: one site, traced once
        if isinstance(node, TableScan):
            # dict.get never triggers lazy sys-view materialization
            src = db.sources.get(node.table)
            if src is None or not hasattr(src, "num_rows"):
                return False
            n = int(src.num_rows)
            if n > FUSE_MAX_ROWS:
                return False
            if _program_has_udf(node.program):
                return False
            if node.program is not None:
                read_cols = required_columns(node.program, src.schema)
            else:
                read_cols = tuple(node.columns if node.columns is not None
                                  else src.schema.names)
            site = ScanSite(
                key=f"t{len(sites)}", table=node.table, node=node,
                read_cols=read_cols,
                in_schema=src.schema.select(read_cols),
                capacity=shape_class(n),
            )
            by_node[id(node)] = site
            sites.append(site)
            stages += 1
            return True
        if isinstance(node, LookupJoin):
            if node.kind not in ("inner", "left", "semi", "anti"):
                return False
            if len(node.probe_keys) > 2:
                return False
            stages += 1
            return visit(node.probe) and visit(node.build)
        if isinstance(node, ExpandJoin):
            if node.kind not in ("inner", "left"):
                return False
            if len(node.probe_keys) > 2:
                return False
            stages += 1
            return visit(node.probe) and visit(node.build)
        if isinstance(node, Transform):
            if _program_has_udf(node.program):
                return False
            stages += 1
            return visit(node.input)
        if isinstance(node, Concat):
            stages += 1
            return all(visit(i) for i in node.inputs)
        return False

    if not visit(plan):
        return None
    return PlanSignature(plan=plan, sites=sites, fused_stages=stages)


# plan_signature memo: the classification walk is O(plan nodes) of
# Python per statement, and plans on the warm path come out of the
# cluster plan cache with stable identity — so the walk result is
# recomputed for the same tree thousands of times per second on the
# serving tier. Keyed by id(plan): safe because the memo value holds
# sig.plan (a strong ref), so the id cannot be recycled while the
# entry lives; an ``is`` check guards the lookup anyway. Validators
# re-check the db-dependent inputs (source identity, row count,
# schema identity) in O(sites); any drift recomputes. Only fusible
# results memoize — a None verdict may hinge on sources the walk
# never recorded.
_SIG_CACHE_ENTRIES = 256
_sig_cache: "collections.OrderedDict" = collections.OrderedDict()
_sig_lock = threading.Lock()


def plan_signature_cached(plan: PlanNode, db) -> PlanSignature | None:
    """``plan_signature`` behind an identity-keyed memo with O(sites)
    revalidation — the per-statement entry point for dispatchers."""
    key = id(plan)
    with _sig_lock:
        hit = _sig_cache.get(key)
        if hit is not None:
            sig, validators = hit
            if sig.plan is plan and _sig_valid(validators, db):
                _sig_cache.move_to_end(key)
                return sig
            del _sig_cache[key]
    # signature-cache miss: one classification walk, then memoized
    # ydb-lint: disable=H004
    sig = plan_signature(plan, db)
    if sig is None:
        return None
    # .get throughout: bracket access on lazy source maps can
    # materialize sys views (same contract as the walk above)
    validators = tuple(
        (s.table, id(src), int(src.num_rows), id(src.schema))
        for s in sig.sites
        for src in (db.sources.get(s.table),))
    with _sig_lock:
        _sig_cache[key] = (sig, validators)
        _sig_cache.move_to_end(key)
        while len(_sig_cache) > _SIG_CACHE_ENTRIES:
            _sig_cache.popitem(last=False)
    return sig


def _sig_valid(validators, db) -> bool:
    for table, src_id, n, sch_id in validators:
        src = db.sources.get(table)
        if src is None or id(src) != src_id:
            return False
        if int(src.num_rows) != n or id(src.schema) != sch_id:
            return False
    return True


def _union_nullability(schemas: list[dtypes.Schema]) -> dtypes.Schema:
    """Concat's output schema: a column is nullable as soon as ANY
    branch's is (mirrors blocks.concat_blocks)."""
    base = schemas[0]
    return dtypes.Schema(tuple(
        dtypes.Field(f.name, f.type,
                     any(s.field(f.name).nullable for s in schemas))
        for f in base.fields))


def lookup_schema(node: LookupJoin, p_sch: dtypes.Schema,
                  b_sch: dtypes.Schema) -> dtypes.Schema:
    """run_equi_join's output schema for a lookup join node."""
    if node.kind in ("semi", "anti"):
        return p_sch
    fields = list(p_sch.fields)
    for n in node.payload:
        f = b_sch.field(n)
        fields.append(dtypes.Field(
            n + node.suffix, f.type,
            f.nullable or node.kind == "left"))
    return dtypes.Schema(tuple(fields))


def expand_schema(node: ExpandJoin, p_sch: dtypes.Schema,
                  b_sch: dtypes.Schema) -> dtypes.Schema:
    """expand_join's output schema for an expand join node."""
    fields = [p_sch.field(n) for n in node.probe_payload]
    for n in node.build_payload:
        f = b_sch.field(n)
        fields.append(dtypes.Field(
            n + node.build_suffix, f.type,
            f.nullable or node.kind == "left"))
    return dtypes.Schema(tuple(fields))


class FusedPlan:
    """A compiled whole-plan computation + its staging contract.

    Cached in the cluster compile cache per (plan fingerprint,
    shape-class vector). ``run`` dispatches the single jitted function;
    ``grow`` widens an expand join's static capacity after an overflow
    and re-jits (the cached plan keeps the grown capacity, so later
    statements skip the retry)."""

    def __init__(self, sites, out_schema, aux, run_all, expand_caps,
                 fused_stages, donate):
        self.sites = sites
        self.out_schema = out_schema
        self.aux = aux                  # device-staged, prefixed
        self._run_all = run_all         # python callable (re-jittable)
        self.expand_caps = expand_caps  # mutable: grows on overflow
        self.fused_stages = fused_stages
        self.donate = donate
        self.first_trace_seconds: float | None = None
        self._traced = False
        self._jit = self._make_jit()
        # batch-size -> jitted vmapped dispatch (run_stacked); cleared
        # by grow() with the serial jit — both bake expand capacities
        self._stacked_jits: dict = {}
        self._stacked_traced: set = set()
        # non-donating serial dispatch (run_shared): the batch
        # dispatcher's dedup path hands SHARED staged blocks (scan-share
        # attach) that later members must still be able to read
        self._jit_shared = None
        self._shared_traced = False

    def _make_jit(self):
        # Wrap in a fresh function object per call: jax's tracing cache
        # keys on function *equality*, and bound methods of the same
        # instance compare equal, so ``jax.jit(self._run_all)`` after
        # grow() would silently reuse the old-capacity trace.
        run_all = self._run_all

        def _dispatch(inputs, aux):
            return run_all(inputs, aux)

        return jax.jit(
            _dispatch,
            donate_argnums=(0,) if self.donate else ())

    def run(self, inputs: dict) -> tuple[TableBlock, list[int]]:
        """One dispatch: (result block, expand totals). The first
        dispatch per trace is timed synchronously into
        ``first_trace_seconds`` (jit trace + XLA compile), so profiles
        split compile from execute; warm dispatches stay async. With
        donation on, ``inputs`` is consumed — callers re-stage to
        retry."""
        from ydb_tpu.obs import timeline

        if self._traced:
            if timeline.timeline_enabled():
                # warm dispatch interval (async enqueue — no forced
                # sync; the block boundary shows where results landed)
                t0 = time.perf_counter()
                out, totals = self._jit(inputs, self.aux)
                timeline.RING.record(
                    "plan.dispatch", "dispatch", t0,
                    time.perf_counter(), timeline.current_trace_id())
            else:
                out, totals = self._jit(inputs, self.aux)
        else:
            import warnings

            t0 = time.perf_counter()
            with warnings.catch_warnings():
                # expected: only inputs whose shape/dtype matches some
                # intermediate get reused; the rest "were not usable"
                warnings.filterwarnings(
                    "ignore",
                    message="Some donated buffers were not usable")
                out, totals = self._jit(inputs, self.aux)
            jax.block_until_ready(out)
            self._traced = True
            self.first_trace_seconds = (
                (self.first_trace_seconds or 0.0)
                + time.perf_counter() - t0)
        if memsan.armed():
            # result-block footprint (nbytes is shape metadata — no
            # device sync on the warm async path)
            memsan.charge(memsan.nbytes_of(out), "dispatch",
                          owner="run")
        return out, [int(t) for t in totals]

    def run_shared(self, inputs: dict) -> tuple[TableBlock, list[int]]:
        """Serial dispatch over staged blocks that OTHER statements may
        still read (the batch dispatcher's shared-scan dedup: N queued
        statements whose staged inputs are identical run the plan once
        and every member slices... the same result). Identical XLA
        program to :meth:`run` except donation is off — donating a
        shared block would let the dispatch scribble over a buffer a
        batchmate is about to read."""
        if self._jit_shared is None:
            run_all = self._run_all

            def _dispatch(inputs, aux):
                return run_all(inputs, aux)

            # one-time lazy wrapper creation, cached on the plan (the
            # trace/compile happens on first call, counted there)
            # ydb-lint: disable=H003
            self._jit_shared = jax.jit(_dispatch)
        if self._shared_traced:
            out, totals = self._jit_shared(inputs, self.aux)
        else:
            t0 = time.perf_counter()
            out, totals = self._jit_shared(inputs, self.aux)
            # first-trace timing sync only; warm dispatches stay async
            # ydb-lint: disable=H001
            jax.block_until_ready(out)
            self._shared_traced = True
            self.first_trace_seconds = (
                (self.first_trace_seconds or 0.0)
                + time.perf_counter() - t0)
        if memsan.armed():
            memsan.charge(memsan.nbytes_of(out), "dispatch",
                          owner="run_shared")
        return out, [int(t) for t in totals]

    def _make_stacked_jit(self, batch: int):
        # Fresh wrapper per (batch, capacity generation) for the same
        # function-equality reason as _make_jit. The vmapped body maps
        # ONLY over the stacked inputs; aux (dictionary tables, join
        # constants) is closed over unbatched — every batch member is
        # the same executable, so aux is genuinely shared.
        run_all = self._run_all

        def _dispatch(inputs, aux):
            return jax.vmap(lambda i: run_all(i, aux))(inputs)

        return jax.jit(
            _dispatch,
            donate_argnums=(0,) if self.donate else ())

    def run_stacked(self, inputs_list: list[dict]) \
            -> tuple[TableBlock, list[int]]:
        """One micro-batched dispatch over B compatible statements'
        staged inputs: stack each site's member blocks along a new
        leading axis (TableBlock is a pytree — jnp.stack copies into
        fresh buffers, so donation of the stacked operand never touches
        the per-member staged blocks) and run the vmapped plan once.
        Returns the batched result (leading dim B on every leaf) plus
        per-expand-slot totals MAXed over members — the overflow/grow
        protocol is per-capacity, and the widest member governs.
        Callers slice members off with :func:`slice_member`."""
        batch = len(inputs_list)
        with memsan.seam("stack"):
            stacked = _stack_members(inputs_list)
        # the stack copy is transient: donated into the dispatch (or
        # dropped right after it), so its bytes release once the
        # batched result exists
        ticket = memsan.charge(
            memsan.nbytes_of(stacked), "stack",
            owner="run_stacked") if memsan.armed() else None
        try:
            jf = self._stacked_jits.get(batch)
            if jf is None:
                jf = self._make_stacked_jit(batch)
                self._stacked_jits[batch] = jf
            if batch in self._stacked_traced:
                out, totals = jf(stacked, self.aux)
            else:
                import warnings

                t0 = time.perf_counter()
                with warnings.catch_warnings():
                    warnings.filterwarnings(
                        "ignore",
                        message="Some donated buffers were not usable")
                    out, totals = jf(stacked, self.aux)
                jax.block_until_ready(out)
                self._stacked_traced.add(batch)
                self.first_trace_seconds = (
                    (self.first_trace_seconds or 0.0)
                    + time.perf_counter() - t0)
        finally:
            memsan.release(ticket)
        if memsan.armed():
            memsan.charge(memsan.nbytes_of(out), "dispatch",
                          owner="run_stacked")
        # totals come back shape (B,); the grow protocol keys on the
        # worst member (capacities are trace-time constants shared by
        # the whole batch)
        return out, [int(max(t)) for t in jax.device_get(totals)]

    def overflowed(self, totals: list[int]) -> list[int]:
        """Expand-join indexes whose match total exceeded capacity."""
        return [i for i, t in enumerate(totals)
                if t > self.expand_caps[i]]

    def grow(self, idx: int, total: int) -> None:
        """Widen expand join ``idx`` to hold ``total`` rows (rounded to
        the capacity quantum, run_equi_join's exact-retry step) and
        re-jit — the fresh jit wrapper forces a retrace, since the
        capacity is a trace-time constant, not an input shape."""
        q = DEFAULT_CAPACITY_QUANTUM
        self.expand_caps[idx] = (total + q - 1) // q * q
        self._traced = False
        self._jit = self._make_jit()
        # stacked/shared dispatches bake the same capacities: drop them
        # all so the next batch retraces at the grown size
        self._stacked_jits.clear()
        self._stacked_traced.clear()
        self._jit_shared = None
        self._shared_traced = False


def _stack_members(inputs_list: list[dict]):
    """Stack B members' staged inputs along a new leading axis.
    ``jnp.stack`` copies into fresh buffers, so donation of the stacked
    operand never touches the per-member staged blocks (which may be
    shared with concurrent statements through the scan share)."""
    return jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs), *inputs_list)


def slice_member(out: TableBlock, i: int) -> TableBlock:
    """Member ``i``'s result out of a :meth:`FusedPlan.run_stacked`
    batched block: index the leading batch axis off every leaf (lazy
    device gathers — each waiting session materializes only its own
    slice). The static treedef (names, schema) carries through, so the
    slice is a plain TableBlock indistinguishable from a serial run's."""
    return jax.tree_util.tree_map(lambda x: x[i], out)


def build(sig: PlanSignature, db) -> FusedPlan:
    """Compile a fusible plan into one FusedPlan.

    Every node's SSA program is verified (analysis.verify runs inside
    ``_compile_program``) and lowered up front — the whole pipeline is
    typed end to end before any trace. One ``ssa.compile`` span covers
    the full build (the walk would emit one per fragment)."""
    from ydb_tpu.obs import tracing

    with tracing.span("ssa.compile") as sp:
        fused = _build(sig, db)
        sp.set(fused_stages=fused.fused_stages,
               cols=sum(len(s.read_cols) for s in sig.sites))
    return fused


class PlanLowering:
    """Overridable whole-plan lowering: one walk over the plan tree
    emitting trace-time closures per node.

    The single-chip lowering below is the base; the mesh lowering
    (parallel/mesh_fuse.MeshLowering) subclasses the join/transform
    hooks to insert all_to_all repartitions and two-phase partial→final
    merges while inheriting the scan/concat/shared-subtree machinery —
    the seam that keeps single-chip the degenerate 1-device case
    instead of a third executor."""

    def __init__(self, sig: PlanSignature, db):
        self.sig = sig
        self.db = db
        self.site_by_node = {id(s.node): s for s in sig.sites}
        self.aux_np: dict = {}
        # grow-protocol capacity slots (FusedPlan.grow): parallel lists
        # of static capacity + slot kind ("expand" here; subclasses add
        # their own kinds, e.g. the mesh lowering's "shuffle")
        self.caps: list[int] = []
        self.cap_kinds: list[str] = []
        self._lowered: dict[int, tuple] = {}  # id -> (emit, schema, cap)
        self._n_nodes = 0

    def compiled(self, program, schema, dicts, dict_aliases=None,
                 partial_slots: bool = False):
        """Lower one fragment's program; its aux tables merge into the
        plan-wide dict under a per-fragment prefix. Returns (run, cp) —
        the prefixed runner plus the CompiledProgram (out_schema,
        group_layout) for callers that dispatch on layout."""
        cp = _compile_program(program, schema, dicts, self.db.key_spaces,
                              partial_slots=partial_slots,
                              dict_aliases=dict_aliases)
        pfx = f"n{self._n_nodes}."
        self._n_nodes += 1
        self.aux_np.update({pfx + k: v for k, v in cp.aux.items()})
        keys = tuple(cp.aux.keys())

        def run(block, aux):
            return cp.run(block, {k: aux[pfx + k] for k in keys})

        return run, cp

    def lower(self, node) -> tuple[Callable, dtypes.Schema, int]:
        hit = self._lowered.get(id(node))
        if hit is not None:
            return hit
        emit, sch, cap = self._lower(node)
        nid = id(node)

        # trace-time memo: a shared subtree (CTE referenced twice)
        # contributes its ops ONCE to the XLA graph, exactly like the
        # walk's _memo executes it once per statement
        def memo_emit(inputs, aux, memo, totals, _e=emit, _nid=nid):
            h = memo.get(_nid)
            if h is None:
                h = _e(inputs, aux, memo, totals)
                memo[_nid] = h
            return h

        out = (memo_emit, sch, cap)
        self._lowered[nid] = out
        return out

    def _lower(self, node):
        if isinstance(node, TableScan):
            return self.lower_scan(node)
        if isinstance(node, LookupJoin):
            return self.lower_lookup(node)
        if isinstance(node, ExpandJoin):
            return self.lower_expand(node)
        if isinstance(node, Transform):
            return self.lower_transform(node)
        if isinstance(node, Concat):
            return self.lower_concat(node)
        raise Unfusible(f"node does not lower: {node!r}")

    def lower_scan(self, node: TableScan):
        site = self.site_by_node[id(node)]
        src = self.db.sources[node.table]
        if node.program is None:
            sch = site.in_schema

            def emit(inputs, aux, memo, totals, _k=site.key,
                     _cols=site.read_cols):
                return inputs[_k].select(_cols)

            return emit, sch, site.capacity
        run, cp = self.compiled(
            node.program, site.in_schema,
            getattr(src, "dicts", None) or self.db.dicts)

        def emit(inputs, aux, memo, totals, _k=site.key,
                 _cols=site.read_cols, _run=run):
            return _run(inputs[_k].select(_cols), aux)

        return emit, cp.out_schema, site.capacity

    def lower_lookup(self, node: LookupJoin):
        p_emit, p_sch, p_cap = self.lower(node.probe)
        b_emit, b_sch, _ = self.lower(node.build)
        sch = lookup_schema(node, p_sch, b_sch)

        def emit(inputs, aux, memo, totals, _n=node, _pe=p_emit,
                 _be=b_emit):
            return join_kernels.run_equi_join(
                _pe(inputs, aux, memo, totals),
                _be(inputs, aux, memo, totals),
                _n.probe_keys, _n.build_keys, kind=_n.kind,
                suffix=_n.suffix, payload=_n.payload)

        return emit, sch, p_cap

    def expand_slot(self, probe_cap: int, fanout_hint: float) -> int:
        """Register one expand join's static output capacity; returns
        the slot index (totals[i] carries the traced match count)."""
        # probe_cap is an upper bound on the probe subtree's live rows
        # (group-bys only shrink), sized like run_equi_join's first
        # round; overflow grows it exactly (FusedPlan.grow)
        self.caps.append(max(
            int(probe_cap * fanout_hint), DEFAULT_CAPACITY_QUANTUM))
        self.cap_kinds.append("expand")
        return len(self.caps) - 1

    def expand_total(self, total):
        """Hook: how an expand join's traced match count reaches the
        host (the mesh lowering pmax-reduces it over the shard axis)."""
        return total

    def lower_expand(self, node: ExpandJoin):
        p_emit, p_sch, p_cap = self.lower(node.probe)
        b_emit, b_sch, _ = self.lower(node.build)
        sch = expand_schema(node, p_sch, b_sch)
        ei = self.expand_slot(p_cap, node.fanout_hint)
        caps = self.caps

        def emit(inputs, aux, memo, totals, _n=node, _pe=p_emit,
                 _be=b_emit, _ei=ei):
            out, total = join_kernels.expand_join(
                _pe(inputs, aux, memo, totals),
                _be(inputs, aux, memo, totals),
                list(_n.probe_keys), list(_n.build_keys),
                list(_n.probe_payload), list(_n.build_payload),
                out_capacity=caps[_ei],
                build_suffix=_n.build_suffix, kind=_n.kind)
            totals[_ei] = self.expand_total(total)
            return out

        # report the initial bound so parents (nested expands) can
        # size their own caps; if this cap later grows on overflow
        # the parent under-sizes at worst, and its own overflow
        # check grows it the same way
        return emit, sch, self.caps[ei]

    def lower_transform(self, node: Transform):
        i_emit, i_sch, i_cap = self.lower(node.input)
        run, cp = self.compiled(node.program, i_sch, self.db.dicts,
                                dict_aliases=dict(node.dict_aliases))

        def emit(inputs, aux, memo, totals, _ie=i_emit, _run=run):
            return _run(_ie(inputs, aux, memo, totals), aux)

        return emit, cp.out_schema, i_cap

    def lower_concat(self, node: Concat):
        parts = [self.lower(i) for i in node.inputs]
        sch = _union_nullability([p[1] for p in parts])
        caps = [p[2] for p in parts]
        cap = (sum(caps) if all(c is not None for c in caps)
               else None)

        def emit(inputs, aux, memo, totals, _parts=parts, _sch=sch):
            blocks = [
                # restamp to the union schema so the merged block
                # types like concat_blocks' output
                TableBlock(b.columns, b.length, _sch)
                for b in (p[0](inputs, aux, memo, totals)
                          for p in _parts)
            ]
            return merge_blocks_device(blocks)

        return emit, sch, cap


@host_ok("fused-plan compile: reached only on a compile-cache miss;"
         " the built FusedPlan is cached by plan fingerprint")
def _build(sig: PlanSignature, db) -> FusedPlan:
    lo = PlanLowering(sig, db)
    root, out_schema, _ = lo.lower(sig.plan)
    caps = lo.caps

    def run_all(inputs, aux):
        totals: list = [jnp.int64(0)] * len(caps)
        out = root(inputs, aux, {}, totals)
        return out, tuple(totals)

    return FusedPlan(sig.sites, out_schema, device_aux(lo.aux_np),
                     run_all, caps, sig.fused_stages, _DONATE)
