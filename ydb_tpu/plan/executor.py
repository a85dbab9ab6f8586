"""Plan executor: DQ stage graph for join-bearing plans, single-chip
walk for single-stage plans.

The host-side analog of the KQP executer (kqp_executer_impl.h:120):
every plan containing a join lowers to the DQ task graph — scan stages
feeding hash-partitioned channels into grace-bucket join stages and a
final aggregate — executed by credit-flow compute actors
(kqp/dq_lower.py + dq/compute.py), exactly as the reference routes every
query through executer → tasks → compute actors (kqp_tasks_graph.cpp:448).
Single-stage plans (scan → transform, no join) keep the direct
streaming walk below — the one-task collapse of the same graph: scans
stream blocks through compiled SSA (ydb_tpu.engine.scan), transforms
compile against the inferred intermediate schema. The recursive walk
also remains the fallback for plan shapes that do not lower (a
CTE-shared subtree feeding two consumers).
"""

from __future__ import annotations

import collections
import dataclasses
import os

import jax
import numpy as np

from ydb_tpu import chaos, dtypes
from ydb_tpu.analysis import host_ok
from ydb_tpu.analysis.verify import check_program
from ydb_tpu.chaos import deadline as statement_deadline
from ydb_tpu.blocks.block import TableBlock, concat_blocks, device_aux
from ydb_tpu.blocks.dictionary import DictionarySet
from ydb_tpu.engine.oracle import OracleTable
from ydb_tpu.engine.scan import (
    DEFAULT_BLOCK_ROWS,
    ColumnSource,
    ScanExecutor,
)
from ydb_tpu.obs import tracing
from ydb_tpu.obs.probes import probe as _probe
from ydb_tpu.ssa import join as join_kernels
from ydb_tpu.ssa import kernels, twophase
from ydb_tpu.ssa.compiler import LAYOUT_NAMES, compile_program
from ydb_tpu.ssa.program import (
    AssignStep,
    FilterStep,
    GroupByStep,
    Program,
    ProjectStep,
    RollupStep,
)
from ydb_tpu.plan.nodes import (
    Concat,
    ExpandJoin,
    LookupJoin,
    PlanNode,
    TableScan,
    Transform,
)

# the SQL scan path fires the SAME probe points the direct
# ColumnShard.scan fires (shard=-1 marks the statement-level aggregate
# over all shards), so EXPLAIN ANALYZE actuals and probe sessions see
# one consistent accounting
_P_SCAN_STAGES = _probe("columnshard.scan.stages")
_P_SCAN_PRUNING = _probe("columnshard.scan.pruning")


@dataclasses.dataclass
class Database:
    """Named host tables + shared dictionaries (one 'shard' worth).

    ``_compile_cache`` memoizes compiled Transform programs per
    (program, schema) — the XLA-era computation-pattern cache
    (mkql_computation_pattern_cache.h). Ingest that extends dictionaries
    must call ``invalidate_compile_cache()`` (plan-time dictionary tables
    bake into the cached aux)."""

    sources: dict[str, ColumnSource]
    dicts: DictionarySet | None = None
    key_spaces: dict[str, int] | None = None
    _compile_cache: dict = dataclasses.field(default_factory=dict)
    # when set (Cluster.enable_mesh), eligible plans execute SPMD over
    # the device mesh (parallel/mesh_exec.py) instead of DQ/recursive
    mesh_executor: object = None
    # cluster-owned DeviceBlockCache: table scans over portion-backed
    # sources reuse HBM-resident decoded blocks across statements (the
    # SQL path's share of the shared-page-cache analog). Databases are
    # per-statement; the cache outlives them.
    block_cache: object = None
    # aggregator table statistics (stats.cost.TableStats by table name):
    # feeds DQ join sizing estimates; advisory only
    table_stats: dict | None = None
    # rows per scan block, for every scan this Database's statements
    # run (the cluster hands down its shards' scan_block_rows): the
    # pushdown program's device temporaries scale with it (compiled
    # for a v5e, Q1's partial keeps 68 MB of them at 2^22 rows)
    scan_block_rows: int = DEFAULT_BLOCK_ROWS

    def invalidate_compile_cache(self):
        self._compile_cache.clear()


def _materialize(source: ColumnSource, columns) -> TableBlock:
    names = columns if columns is not None else source.schema.names
    blocks = list(source.blocks(block_rows=1 << 40, columns=names))
    return blocks[0] if len(blocks) == 1 else concat_blocks(blocks)


def _pruned_source(src, program, db: Database):
    """Predicate-pruned view of a scan source, when statistics are on
    and the source supports it (MultiShardStreamSource.with_predicates).
    Falls through to the original source otherwise — host-resident
    ColumnSources have no chunk plane to prune."""
    from ydb_tpu import stats as stats_mod

    with_preds = getattr(src, "with_predicates", None)
    if with_preds is None or not stats_mod.stats_enabled():
        return src
    from ydb_tpu.stats.zonemap import extract_predicates

    preds, _full = extract_predicates(program, src.schema, db.dicts)
    if not preds:
        return src
    return with_preds(preds)


# DQ is the default executor for join-bearing plans (VERDICT r4 item 2);
# YDB_TPU_DQ=0 restores the recursive walk for A/B debugging
_DQ_ON = os.environ.get("YDB_TPU_DQ", "1") not in ("0", "", "off")
_DQ_TASKS = int(os.environ.get("YDB_TPU_DQ_TASKS", "2"))
_DQ_BLOCK_ROWS = int(os.environ.get("YDB_TPU_DQ_BLOCK_ROWS",
                                    str(1 << 20)))


def _inputs(n: PlanNode) -> list:
    if isinstance(n, (LookupJoin, ExpandJoin)):
        return [n.probe, n.build]
    if isinstance(n, Transform):
        return [n.input]
    if isinstance(n, Concat):
        return list(n.inputs)
    return []


def _plan_nodes(plan: PlanNode):
    stack = [plan]
    while stack:
        n = stack.pop()
        yield n
        stack += _inputs(n)


class _Memo(dict):
    """One statement's walk: id(node) -> its result block, so a shared
    subtree (a CTE referenced from several places) executes once.
    ``shared`` holds the ids of the nodes that more than one consumer
    reads: their results must stay whole for the next reader."""

    def __init__(self, plan: PlanNode):
        super().__init__()
        consumers: collections.Counter = collections.Counter()
        seen: set[int] = set()
        stack = [plan]
        while stack:
            n = stack.pop()
            if id(n) in seen:
                continue
            seen.add(id(n))
            for child in _inputs(n):
                consumers[id(child)] += 1
                stack.append(child)
        self.shared = {i for i, c in consumers.items() if c > 1}
        #: id(TableScan) -> why the aggregate pushdown left the scan to
        #: run alone (``_scan_aggregated``): its ``scan`` span says so
        self.declined: dict[int, str] = {}


def _partition_for_dq(src) -> list:
    """A table's scan partitions for DQ task feeding: per-shard portion
    streams for sharded tables (their natural partitioning), round-robin
    row slices for host-resident sources."""
    subs = getattr(src, "subs", None)
    if subs:
        return list(subs)
    if isinstance(src, ColumnSource) and src.num_rows > 0:
        from ydb_tpu.kqp.dq_lower import partition_source

        return partition_source(src, _DQ_TASKS)
    return [src]


def _execute_plan_mesh(plan: PlanNode, db: Database):
    """SPMD mesh execution for eligible plans (scan+agg and join trees
    whose tables the mesh database carries). Returns the host-resident
    OracleTable (to_host passes it through — no device round-trip for a
    result already gathered), or None when the shape doesn't map
    (non-root aggregating Transform, missing table) so the caller falls
    through to DQ/recursive. Real execution defects (shape errors etc.)
    propagate — only the explicit doesn't-lower signal falls back."""
    mex = db.mesh_executor
    for node in _plan_nodes(plan):
        if isinstance(node, TableScan) and \
                node.table not in mex.db.sources:
            return None
    # sharded whole-plan fusion first (parallel/mesh_fuse): one jitted
    # donated-buffer dispatch over the mesh; the per-node walk remains
    # the fallback for shapes that don't mesh-fuse
    try:
        # the span is how a profile tells the mesh executors from the
        # single-chip ones: answered=1 with a "plan.fuse" child is the
        # mesh-fused dispatch, without one the mesh walk; a plan that
        # falls back leaves the span without the attr
        with tracing.span("mesh") as sp:
            sp.set(devices=mex.n)
            fused = getattr(mex, "execute_fused", None)
            out = fused(plan) if fused is not None else None
            if out is None:
                out = mex.execute(plan)
            sp.set(answered=1)
            return out
    except NotImplementedError:
        return None
    except chaos.DeviceLostError:
        # graceful degradation: a lost device fails THIS dispatch, not
        # the statement — single-chip fused execution (then the walk)
        # picks the plan up, bit-identical
        chaos.note_fallback("mesh.dispatch")
        tracing.annotate(mesh_fallback=1)
        return None


def source_counters(src) -> dict:
    """The cumulative chunk and resident counters of a scan source:
    shared unpruned sources accumulate them across statements, so a
    span reports a run's DELTA (pruned views are fresh per run)."""
    return {k: int(getattr(src, k, 0))
            for k in ("chunks_read", "chunks_skipped", "resident_hits",
                      "resident_rows", "resident_blocks_whole",
                      "resident_blocks_assembled")}


def pruning_since(src, before: dict) -> dict:
    """The pruning attrs of a scan span: the run's counter deltas,
    portions skipped by zone maps and portions in all."""
    pruning = {k: v - before[k] for k, v in source_counters(src).items()}
    # resident-hit attribution: EXPLAIN ANALYZE shows how much of the
    # scan the HBM tier served without touching host bytes
    pruning["resident_portions"] = pruning.pop("resident_hits")
    pruning["portions_skipped"] = int(getattr(src, "portions_skipped", 0))
    # a sharded table's source holds one portion stream a shard; the
    # mesh walk scans a shard's stream by itself
    pruning["portions_total"] = pruning["portions_skipped"] + sum(
        len(getattr(s, "metas", ())) for s in getattr(src, "subs", (src,)))
    return pruning


def _build_dq(plan: PlanNode, db: Database):
    """Partition the sources, lower to DQ stages and build the actor
    graph: ``(stages, parts, runtime, handle)``, or None when the plan
    does not lower."""
    from ydb_tpu.dq.compute import build_stage_graph
    from ydb_tpu.kqp.dq_lower import plan_to_stages
    from ydb_tpu.runtime.actors import ActorSystem

    seen: set[int] = set()
    parts: dict[str, list] = {}
    for node in _plan_nodes(plan):
        if id(node) in seen:
            # a shared subtree (CTE referenced twice) would re-lower —
            # and re-execute — once per consumer; the recursive walk's
            # _memo executes it once, so fall back
            return None
        seen.add(id(node))
        if isinstance(node, TableScan) and node.table not in parts:
            # dict.get never triggers lazy sys-view materialization
            src = db.sources.get(node.table)
            if src is None:
                return None
            parts[node.table] = _partition_for_dq(src)
    estimator = None
    if db.table_stats:
        from ydb_tpu import stats as stats_mod
        from ydb_tpu.stats import cost

        if stats_mod.stats_enabled():
            table_stats = db.table_stats
            # real schemas type predicate literals (decimal scaling)
            schemas = {
                name: db.sources[name].schema for name in parts
                if hasattr(db.sources.get(name), "schema")
            }

            def estimator(node):
                return cost.estimate_plan_rows(node, table_stats,
                                               schemas)
    rt = ActorSystem(node=1)
    try:
        stages = plan_to_stages(plan, n_tasks=_DQ_TASKS,
                                estimator=estimator)
        handle = build_stage_graph(
            stages, parts, rt, db.dicts, db.key_spaces,
            block_rows=_DQ_BLOCK_ROWS, compile_cache=db._compile_cache)
    except (ValueError, NotImplementedError):
        # plan shapes that do not lower (e.g. a join-rooted plan with no
        # result Transform) keep working through the recursive walk
        return None
    return stages, parts, rt, handle


def _execute_plan_dq(plan: PlanNode, db: Database) -> TableBlock | None:
    """Lower to DQ stages and run on an in-process actor system. Returns
    None when the plan does not lower (the caller falls back to the
    recursive walk)."""
    from ydb_tpu.obs.probes import StageTimer

    with tracing.span("dq.build") as bsp:
        built = _build_dq(plan, db)
    if built is None:
        return None
    stages, parts, rt, handle = built
    # the source scans charge the same stages and pruning counters as
    # the walk's (_scan_node), all of them to the one "dq" span; the
    # timer rides the shared base sources for this run only
    timed = [db.sources[t] for t in parts
             if bsp.recording and hasattr(db.sources[t], "attach_timer")]
    timer = StageTimer() if timed else None
    before = [source_counters(src) for src in timed]
    for src in timed:
        src.attach_timer(timer)
    try:
        with tracing.span("dq") as sp:
            sp.set(stages=len(stages), tasks=_DQ_TASKS)
            handle.start()
            with tracing.span("dq.pump"):
                rt.run()
            rows = handle.channel_rows()
            sp.set(device_channel_rows=rows["device"],
                   host_channel_rows=rows["host"])
            if timer is not None:
                pruning = collections.Counter()
                for src, b in zip(timed, before):
                    pruning.update(pruning_since(src, b))
                sp.set(**{f"stage_{k}": v
                          for k, v in timer.snapshot().items()},
                       **pruning)
        err = handle.collector.error
        if err is not None and "deadline" in err:
            # the graph aborted on statement-deadline expiry: surface
            # the typed cancellation, not a generic incompletion
            raise statement_deadline.StatementCancelled(err)
        if not handle.collector.done:
            raise RuntimeError("DQ stage graph did not complete")
        return handle.collector.result_block()
    finally:
        for src in timed:
            src.attach_timer(None)
        # a cancelled/aborted graph still holds spilled blobs for any
        # parked or accumulated block ids; drop them with the graph
        handle.close()


def execute_plan(plan: PlanNode, db: Database,
                 _memo: _Memo | None = None,
                 use_dq: bool | None = None) -> TableBlock:
    """Execute a logical plan: plans that join or hold a GROUP BY ROLLUP
    route through the DQ stage graph (the production executer path, and
    the one that sizes a rollup's levels by their rows); single-stage
    plans and non-lowerable shapes use the bottom-up walk. ``_memo`` dedupes
    shared subtrees (a CTE referenced from several places executes once
    per statement)."""
    if _memo is None:
        if db.mesh_executor is not None:
            out = _execute_plan_mesh(plan, db)
            if out is not None:
                return out
        if (use_dq if use_dq is not None else _DQ_ON) and any(
                isinstance(n, (LookupJoin, ExpandJoin))
                or (isinstance(n, Transform) and any(
                    isinstance(s, RollupStep) for s in n.program.steps))
                for n in _plan_nodes(plan)):
            out = _execute_plan_dq(plan, db)
            if out is not None:
                return out
        # whole-plan fusion (ssa.plan_fuse): replace the per-node memo
        # walk with ONE jitted dispatch when the whole tree is fusible.
        # A bare TableScan is already a single fragment — _scan_node's
        # streaming path stays.
        from ydb_tpu.ssa import plan_fuse

        if plan_fuse.fusion_enabled() and not isinstance(plan, TableScan):
            out = _execute_plan_fused(plan, db)
            if out is not None:
                return out
        _memo = _Memo(plan)
    hit = _memo.get(id(plan))
    if hit is not None:
        return hit
    out = _execute_node(plan, db, _memo)
    _memo[id(plan)] = out
    return out


def _scan_executor(plan: TableScan, db: Database,
                   dict_aliases: tuple = ()) -> tuple[ScanExecutor, bool]:
    """The scan's compiled executor out of ``db._compile_cache``, and
    whether this call built it. ``dict_aliases`` are those of the
    Transform whose program the scan's ends in (``_pushdown_scan``)."""
    key = (plan.table, plan.program, dict_aliases)
    ex = db._compile_cache.get(key)
    fresh = ex is None
    if fresh:
        ex = ScanExecutor(
            plan.program, db.sources[plan.table],
            block_rows=db.scan_block_rows, key_spaces=db.key_spaces,
            dict_aliases=dict(dict_aliases),
        ).detach()  # cache compiled state, not the source arrays
        db._compile_cache[key] = ex
    return ex, fresh


def _pushdown_scan(plan: Transform, shared: set) -> TableScan | None:
    """Aggregate pushdown into the scan (the reference's
    PushOlapAggregate, kqp_opt_phy_olap_agg.cpp): the ONE TableScan
    that answers an aggregating Transform over a scan, its program the
    scan's with the Transform's appended, or None where the plan keeps
    the two apart: the scan feeds another consumer too, something
    other than assign / filter / project comes before the group-by, or
    the two-phase split does not take one of the aggregates."""
    scan = plan.input
    if not isinstance(scan, TableScan) or scan.program is None \
            or id(scan) in shared:
        return None
    head = scan.program.steps
    steps = head + plan.program.steps
    for i, step in enumerate(steps):
        if isinstance(step, GroupByStep):
            if i < len(head):
                return None
            program = Program(steps)
            try:
                twophase.split(program)
            except NotImplementedError:
                return None
            return TableScan(scan.table, program)
        if not isinstance(step, (AssignStep, FilterStep, ProjectStep)):
            return None
    return None


def _scan_aggregated(plan: Transform, db: Database, shared: set,
                     declined: dict | None = None) -> TableBlock | None:
    """Run ``Transform(TableScan)`` as one scan that aggregates each
    block under its filter mask, folds the partial states on the device
    and finalizes in one dispatch: no block is compacted, nothing is
    fetched before the result, no program is shaped by the selected
    row count. None where the shape does not allow it (the caller runs
    the Transform over the scan's output): ``_pushdown_scan``'s
    conditions, or a group layout whose partials are not shape-stable
    (sort-derived: per-block sorts and a merge of N-group partials are
    another trade: ``declined``, the memo's, then says
    ``layout=sorted`` for the scan). The executor that says so stays
    cached, so the next run of the statement asks a dict."""
    pushed = _pushdown_scan(plan, shared)
    if pushed is None:
        return None
    ex, fresh = _scan_executor(pushed, db, plan.dict_aliases)
    if not ex.folds_partials:
        if declined is not None:
            declined[id(plan.input)] = "layout=" + LAYOUT_NAMES.get(
                ex.partial.group_layout[0], "none")
        return None
    with tracing.span("scan") as sp:
        sp.set(agg_pushdown=1)
        return _scan_node(pushed, db, sp, ex, fresh)


def _scan_node(plan: TableScan, db: Database, sp, ex: ScanExecutor,
               fresh: bool) -> TableBlock:
    from ydb_tpu.obs.probes import StageTimer
    from ydb_tpu.ssa import plan_fuse

    src = db.sources[plan.table]
    # stage accounting while a query trace records OR a probe session
    # listens (probe observability must not degrade when profiling is
    # off — the shard-level probes fire unconditionally too). The timer
    # itself is cheap, but attaching it threads per-chunk charging
    # through the whole staging pipeline; attached to the base source
    # for this run only — a Database reused across statements (bench)
    # shares its sources, and a stale timer would keep charging later
    # unprofiled scans — so it detaches after the stream drains.
    want_stats = (sp.recording or bool(_P_SCAN_STAGES)
                  or bool(_P_SCAN_PRUNING))
    timer = None
    base_src = src
    if want_stats:
        timer = StageTimer()
        if hasattr(base_src, "attach_timer"):
            base_src.attach_timer(timer)
    try:
        # zone-map scan pruning (stats.zonemap): the pushdown program's
        # conjunctive filters skip portions/chunks before any blob
        # read. The pruned view carries its predicate fingerprint into
        # the device cache key, so pruned streams never alias unpruned
        # ones.
        with tracing.span("scan.prune"):
            src = _pruned_source(src, plan.program, db)
        chunks0 = source_counters(src)
        raw_stream = src.blocks(db.scan_block_rows, ex.read_cols)
        stream = raw_stream
        bc = db.block_cache
        key_of = getattr(src, "device_cache_key", None)
        # the resident tier subsumes the whole-stream device cache (see
        # ColumnShard scan: double-caching holds the bytes twice)
        res_on = any(
            getattr(s.shard, "resident", None) is not None
            and s.shard.resident.enabled()
            for s in getattr(src, "subs", ()))
        if bc is not None and key_of is not None and bc.budget() > 0 \
                and not res_on:
            # bind the RAW source stream, not `stream` itself: the
            # single-flight cache calls make_blocks lazily (on first
            # next()), after `stream` has been rebound to the cache
            # generator — a late-bound `stream` would hand the
            # generator back to itself
            stream = bc.stream(
                key_of(ex.read_cols, db.scan_block_rows),
                lambda: raw_stream)
        # a scan with no final program ends in one host-concatenated
        # block: at a shape class of its rows, so that the Transform
        # over it compiles once a class and not once a selected count
        out = ex.run_stream(stream, timer=timer,
                            concat_capacity=plan_fuse.shape_class)
    finally:
        if timer is not None and hasattr(base_src, "attach_timer"):
            base_src.attach_timer(None)
    if want_stats:
        stages = timer.snapshot()
        pruning = pruning_since(src, chunks0)
        if sp.recording:
            sp.set(table=plan.table, rows=out.live_rows(),
                   compile_cache=("miss" if fresh else "hit"),
                   **{f"stage_{k}": v for k, v in stages.items()},
                   **pruning)
            if fresh and ex.first_trace_seconds:
                sp.set(first_trace_seconds=round(
                    ex.first_trace_seconds, 6))
        if _P_SCAN_STAGES:
            _P_SCAN_STAGES.fire(shard=-1, **stages)
        if _P_SCAN_PRUNING:
            _P_SCAN_PRUNING.fire(shard=-1, **pruning)
    return out


@host_ok("scan staging boundary: host source arrays cross to the"
         " device here by design (block cache / resident tier absorb"
         " repeat crossings; donate-safety copies are part of it)")
def _stage_fused_site(site, db: Database, timer, donate: bool):
    """Stage one fused scan site to its shape-class capacity.

    Mirrors _scan_node's staging side exactly — pruned view, chunk-delta
    pruning accounting, block cache / resident tier routing, StageTimer
    attachment — but ends at a single padded device block instead of a
    streamed program run (the program runs inside the fused trace).
    Returns (block, pruning dict). The staged block's buffers are always
    fresh (from_numpy copies / a jitted merge), so the fused dispatch
    may donate them."""
    import contextlib

    from ydb_tpu.ssa import plan_fuse

    src = db.sources[site.table]
    base_src = src
    if timer is not None and hasattr(base_src, "attach_timer"):
        base_src.attach_timer(timer)
    try:
        if site.node.program is not None:
            src = _pruned_source(src, site.node.program, db)
        chunks0 = source_counters(src)
        staging = (timer.stage("stage") if timer is not None
                   else contextlib.nullcontext())
        if isinstance(src, ColumnSource):
            n = src.num_rows
            arrays = {m: src.columns[m] for m in site.read_cols}
            validity = None
            if src.validity:
                validity = {m: src.validity[m]
                            for m in site.read_cols
                            if m in src.validity}
            if donate and site.capacity == n:
                # exact-fit capacity: from_numpy pads nothing, and
                # jnp.asarray may alias an aligned host array on CPU —
                # donating the alias would let XLA scribble over the
                # source table. Copy this (power-of-two row count) case;
                # every other path stages through fresh buffers already.
                arrays = {k: np.array(v) for k, v in arrays.items()}
                if validity:
                    validity = {k: np.array(v)
                                for k, v in validity.items()}
            with staging:
                blk = TableBlock.from_numpy(
                    arrays, site.in_schema, validity,
                    capacity=site.capacity)
        else:
            raw_stream = src.blocks(db.scan_block_rows, site.read_cols)
            stream = raw_stream
            bc = db.block_cache
            key_of = getattr(src, "device_cache_key", None)
            res_on = any(
                getattr(s.shard, "resident", None) is not None
                and s.shard.resident.enabled()
                for s in getattr(src, "subs", ()))
            if bc is not None and key_of is not None \
                    and bc.budget() > 0 and not res_on:
                stream = bc.stream(
                    key_of(site.read_cols, db.scan_block_rows),
                    lambda: raw_stream)
            with tracing.span("scan.pull"):
                blocks = tuple(stream)
            with staging:
                blk = plan_fuse.fit_blocks(blocks, site.capacity)
    finally:
        if timer is not None and hasattr(base_src, "attach_timer"):
            base_src.attach_timer(None)
    pruning = pruning_since(src, chunks0)
    return blk, pruning


def _run_fused(fused, db: Database, fsp) -> TableBlock:
    """Stage every scan site, dispatch the fused computation once, and
    handle expand-join overflow retries.

    Observability mirrors the walk: each staged table gets a "scan" span
    with stage/pruning attrs firing the shard=-1 probes; the PRIMARY
    (largest) table's span stays open around the fused dispatch so
    device time lands in its "compute" stage — EXPLAIN ANALYZE actuals
    and probe sessions stay consistent whichever executor ran."""
    import contextlib

    from ydb_tpu.obs.probes import StageTimer

    want_stats = (fsp.recording or bool(_P_SCAN_STAGES)
                  or bool(_P_SCAN_PRUNING))
    sites = fused.sites
    primary = max(range(len(sites)), key=lambda i: sites[i].capacity)
    inputs: dict = {}

    def emit_obs(sp, site, timer, rows, pruning):
        stages = timer.snapshot()
        if sp.recording:
            sp.set(table=site.table, rows=rows,
                   **{f"stage_{k}": v for k, v in stages.items()},
                   **pruning)
        if _P_SCAN_STAGES:
            _P_SCAN_STAGES.fire(shard=-1, **stages)
        if _P_SCAN_PRUNING:
            _P_SCAN_PRUNING.fire(shard=-1, **pruning)

    for i, other in enumerate(sites):
        if i == primary:
            continue
        with tracing.span("scan") as sp:
            timer = StageTimer() if want_stats else None
            blk, pruning = _stage_fused_site(other, db, timer,
                                             fused.donate)
            inputs[other.key] = blk
            if want_stats:
                emit_obs(sp, other, timer, blk.live_rows(), pruning)

    site = sites[primary]
    with tracing.span("scan") as sp:
        timer = StageTimer() if want_stats else None
        blk, pruning = _stage_fused_site(site, db, timer, fused.donate)
        inputs[site.key] = blk
        # rows read before the dispatch: donated inputs are dead after
        rows = blk.live_rows() if want_stats else 0
        while True:
            # cooperative cancellation between (uninterruptible) fused
            # dispatches: a statement past its deadline stops here
            statement_deadline.check_current("fused dispatch")
            computing = (timer.stage("compute") if timer is not None
                         else contextlib.nullcontext())
            with computing, tracing.span("dispatch",
                                         program="plan_fused"):
                out, totals = fused.run(inputs)
            over = fused.overflowed(totals)
            if not over:
                break
            # an expand join outgrew its static capacity: widen it (the
            # cached plan keeps the exact size for later statements),
            # re-stage — donation consumed the inputs — and re-dispatch
            for j in over:
                fused.grow(j, totals[j])
            inputs = {
                s.key: _stage_fused_site(s, db, None, fused.donate)[0]
                for s in sites
            }
        if want_stats:
            emit_obs(sp, site, timer, rows, pruning)
    return out


def _execute_plan_fused(plan: PlanNode, db: Database) -> TableBlock | None:
    """Whole-plan fused fast path (ssa.plan_fuse): one donated-buffer
    jitted dispatch per (plan fingerprint, shape class), cached in the
    cluster compile cache. Returns None when the plan is not fusible
    (the caller falls back to the per-node walk)."""
    from ydb_tpu.ssa import plan_fuse

    with tracing.span("plan.signature"):
        sig = plan_fuse.plan_signature_cached(plan, db)
    if sig is None or not sig.sites:
        return None
    if chaos.hit("fuse.trace") is not None:
        # injected trace failure: the fused path declines the plan and
        # the per-node walk answers, bit-identical
        chaos.note_fallback("fuse.trace")
        return None
    key = sig.cache_key(db)
    fused = db._compile_cache.get(key)
    fresh = fused is None
    with tracing.span("plan.fuse") as fsp:
        if fresh:
            try:
                fused = plan_fuse.build(sig, db)
            except plan_fuse.Unfusible:
                return None
            db._compile_cache[key] = fused
        ft0 = fused.first_trace_seconds or 0.0
        out = _run_fused(fused, db, fsp)
        if fsp.recording:
            fsp.set(fused_stages=fused.fused_stages,
                    fragments_elided=fused.fused_stages - 1,
                    compile_cache=("miss" if fresh else "hit"))
            # growth retraces on a cached plan count too: report THIS
            # run's trace time, not the lifetime accumulation
            ft = (fused.first_trace_seconds or 0.0) - ft0
            if ft:
                fsp.set(first_trace_seconds=round(ft, 6))
    return out


@host_ok("compile-cache miss path: compiles the Transform once; the"
         " (run, aux) pair is cached by (program, aliases, schema)")
def _compiled_transform(plan: Transform, schema, db: Database):
    """Compile a Transform program (jit + device aux); split out so the
    executor walk stays free of trace-time constructs."""
    cp = compile_program(
        plan.program, schema, db.dicts, db.key_spaces,
        dict_aliases=dict(plan.dict_aliases),
    )
    # ``notes``: the group-by's layout, and (filled by the first trace
    # of ``run``) its key words and reduce tier, for the span
    notes = cp.notes
    notes["group_layout"] = LAYOUT_NAMES.get(cp.group_layout[0], "none")
    return jax.jit(cp.run), device_aux(cp.aux), notes



def _transform_node(plan: Transform, block: TableBlock,
                    db: Database) -> TableBlock:
    """Run a Transform's program over its input's result block, in a
    program compiled at a shape class of that block's capacity
    (``plan_fuse.shape_class``, as ``ssa/join.py`` fits a join's sides):
    the rows past the live length are dead as they are in any block, and
    a new selected row count builds no program. The walk's scan already
    concatenates to a class (``_scan_node``); a join's output is fitted
    here."""
    from ydb_tpu.ssa import plan_fuse

    key = (plan.program, plan.dict_aliases, block.schema)
    hit = db._compile_cache.get(key)
    with tracing.span("transform") as sp:
        capacity = plan_fuse.shape_class(block.capacity)
        if capacity != block.capacity:
            with tracing.span("dispatch", program="transform_fit"):
                block = plan_fuse.fit_blocks((block,), capacity)
        if hit is None:
            sp.set(compile_cache="miss")
            # mandatory precondition (ydb_tpu.analysis): surface
            # step-indexed diagnostics for malformed programs
            # before any trace work; compile_program re-checks, but
            # this keeps the executor the choke point even if
            # lowering changes
            check_program(plan.program, block.schema)
            hit = _compiled_transform(plan, block.schema, db)
            db._compile_cache[key] = hit
        else:
            sp.set(compile_cache="hit")
        run, aux, notes = hit
        with tracing.span("dispatch", program="transform"):
            out = run(block, aux)
        if sp.recording:
            # the wait for the program belongs to this span (the
            # statement key ``transform``), as a scan's row count does
            # to its own: the session's fetch would wait a moment later
            sp.set(capacity=capacity, rows_in=block.live_rows(),
                   rows=out.live_rows(), **notes)
        return out


def _execute_node(plan: PlanNode, db: Database,
                  _memo: _Memo) -> TableBlock:
    if isinstance(plan, TableScan):
        src = db.sources[plan.table]
        if plan.program is None:
            return _materialize(src, plan.columns)
        with tracing.span("scan") as sp:
            declined = _memo.declined.get(id(plan))
            if declined is not None:
                sp.set(agg_pushdown=0, pushdown_declined=declined)
            return _scan_node(plan, db, sp, *_scan_executor(plan, db))
    if isinstance(plan, LookupJoin):
        probe = execute_plan(plan.probe, db, _memo)
        build = execute_plan(plan.build, db, _memo)
        return join_kernels.run_equi_join(
            probe, build, plan.probe_keys, plan.build_keys,
            kind=plan.kind, suffix=plan.suffix, payload=plan.payload,
        )
    if isinstance(plan, ExpandJoin):
        probe = execute_plan(plan.probe, db, _memo)
        build = execute_plan(plan.build, db, _memo)
        return join_kernels.run_equi_join(
            probe, build, plan.probe_keys, plan.build_keys,
            kind=plan.kind, suffix=plan.build_suffix, expand=True,
            probe_payload=plan.probe_payload,
            build_payload=plan.build_payload,
        )
    if isinstance(plan, Transform):
        out = _scan_aggregated(plan, db, _memo.shared, _memo.declined)
        if out is not None:
            return out
        return _transform_node(
            plan, execute_plan(plan.input, db, _memo), db)
    if isinstance(plan, Concat):
        # branches execute independently (planner guarantees identical
        # column names/types); live rows append in branch order
        return concat_blocks(
            [execute_plan(i, db, _memo) for i in plan.inputs])
    raise NotImplementedError(plan)


@host_ok("lazy result fetch: the ONE deliberate device->host boundary"
         " per statement (under the session's 'fetch' span)")
def to_host(block) -> OracleTable:
    if isinstance(block, OracleTable):  # mesh results are already host
        return block
    return OracleTable.from_block(block)
