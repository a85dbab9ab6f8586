"""Per-query profiles assembled from finished span trees.

The reference surfaces query runtime statistics three ways — the plan
annotated with actuals (``EXPLAIN ANALYZE`` / execution stats in the
query response), ``.sys/top_queries`` + ``.sys/query_metrics`` views
over an in-memory ring of the most expensive recent queries, and
per-pool latency histograms on the counters page (SURVEY.md §2.14,
§5.5). This module is that layer for the TPU build: the session runs
every statement under a traced root span (obs.tracing), the executor /
scan / DQ / conveyor layers attach children, and ``build_profile``
folds the finished tree into one ``QueryProfile`` — per-stage seconds,
the statement thread's self time by layer, rows, cache hits,
compile-vs-execute split — that
feeds ``session.last_profile``, the ``sys_top_queries`` /
``sys_query_log`` views, the ``/viewer/json/query_profile`` endpoint
and ``EXPLAIN ANALYZE`` rendering.
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
import time


#: span attrs summed into the per-query stage breakdown: what the
#: ``StageTimer`` of each scan charged, over all the threads of the
#: staging pipeline (so they may sum past the statement's seconds)
STAGE_KEYS = ("read", "merge", "stage", "compute")
#: span name -> statement stage: the self time of every span on the
#: statement's own thread is summed under its stage, and what no named
#: span covers is ``unattributed``. Together they sum to ``seconds``.
SPAN_STAGE = {
    "plan": "plan", "parse": "plan", "ssa.compile": "plan",
    "plan.signature": "plan", "dq.lower": "plan", "dq.build": "plan",
    "snapshot": "plan", "scan.prune": "plan",
    "scan.pull": "pull",
    "dispatch": "dispatch", "dq.pump": "dispatch",
    "dq.exchange": "dispatch", "host.concat": "dispatch",
    "device.wait": "device_wait", "device.get": "device_wait",
    "fetch": "fetch",
}
#: the write path's spans beneath ``write`` (``tx/sharded.py``
#: ``ShardedTable.insert``) -> the stage whose seconds their self time
#: counts into (``stage_seconds{stage=...}`` of the process's
#: ``component=write`` counters): a portion's own self time and its
#: promotion's enqueue count as the commit's
WRITE_SPAN_STAGE = {
    "write.encode": "encode", "write.route": "route",
    "write.buffer": "buffer", "write.commit": "commit",
    "write.portion": "commit", "write.promote.enqueue": "commit",
    "write.concat": "concat", "write.sort": "sort",
    "write.blob": "blob", "write.index": "index", "write.log": "log",
}
#: and one more statement key, named after the ``write`` span and only
#: of a statement that has such a span (an INSERT / UPSERT): the self
#: time of the ``write*`` spans on the statement's thread, so a
#: statement that writes is not all ``unattributed``
WRITE_KEY = "write"
SPAN_STAGE.update(dict.fromkeys(("write", *WRITE_SPAN_STAGE), WRITE_KEY))
STATEMENT_KEYS = ("plan", "pull", "dispatch", "device_wait", "fetch",
                  "unattributed")
#: a seventh statement key, named after the ``mesh`` span and only of
#: a statement that has one (a mesh executor ran): the self time of the
#: mesh's own ``dispatch`` / ``device.wait`` / ``device.get`` spans,
#: those beneath the ``mesh`` span and outside its per-shard ``scan``
#: spans (placing the shards' blocks on the mesh, the collective step,
#: the wait for it and the answer's copy out). It is taken out of
#: ``dispatch`` and ``device_wait``, which keep the shard scans' time,
#: so the seven keys sum to ``seconds`` as the six do.
MESH_KEY = "mesh"
#: two more, carved out of ``mesh`` by the same rule and only of a
#: statement that has such a span (a join ran over the mesh): the self
#: time of the ``dispatch`` / ``device.wait`` / ``device.get`` spans
#: whose nearest enclosing span of these kinds is a ``mesh.shuffle``
#: (one side's hash-repartition: each ``all_to_all`` exchange, the wait
#: for its worst bucket count, the wait that tightens the output) or a
#: ``mesh.join`` (one device-local join under ``shard_map``, the wait
#: for an expanding join's total). ``mesh`` keeps the placement, the
#: collective step and the answer's copy out; the keys still sum to
#: ``seconds``, and a statement without such a span has neither key.
MESH_SPAN_KEYS = {"mesh": MESH_KEY, "mesh.shuffle": "mesh_shuffle",
                  "mesh.join": "mesh_join"}
#: and two of the one-chip walk's path for an aggregate that does not
#: push down into its scan (a sort-derived group layout), by the same
#: rule and only of a statement that has such a span outside any mesh
#: span: ``concat``, the self time of the ``host.concat`` span that
#: turns the scan's block outputs into the one block a Transform reads
#: and of the ``device.get`` / ``dispatch`` / ``device.wait`` spans
#: beneath it; ``transform``, the same of the ``transform`` span (its
#: program enqueued, the wait for it). They are taken out of
#: ``dispatch``, ``device_wait`` and, the ``transform`` span's own time,
#: ``unattributed``; a mesh statement's ``host.concat`` spans stay
#: where they were (``mesh_join_scan_ms`` reads them there).
WALK_SPAN_KEYS = {"host.concat": "concat", "transform": "transform"}
#: and one key that is a view of ``seconds``, not a part of it, as the
#: four ``STAGE_KEYS`` are: the self time on the statement's thread of
#: each DQ join stage's span (``dispatch program=dq_stage`` with a
#: ``join`` attr: dq/compute.py ``_join_bucket``) and of every span
#: beneath it (the bucket's sides concatenated and staged, the join's
#: programs enqueued and waited for, the output copied out), which
#: ``dispatch`` and ``device_wait`` already count; only of a statement
#: with such a span
DQ_JOIN_KEY = "dq_join"
#: two more views by the same rule, of the DQ stage that rolls a
#: group-by up (the ``dispatch program=dq_stage`` span with a
#: ``rollup_levels`` attr: dq/compute.py ``_whole_input``: the levels'
#: rows read back, the levels, the program after them, the output's
#: routing) and of the stage that ranks (a ``window`` attr), each only of
#: a statement with such a span
DQ_ROLLUP_KEY = "dq_rollup"
DQ_WINDOW_KEY = "dq_window"
#: each DQ stage view: its key -> the attr its stages' spans carry
DQ_STAGE_VIEWS = {DQ_JOIN_KEY: "join", DQ_ROLLUP_KEY: "rollup_levels",
                  DQ_WINDOW_KEY: "window"}
#: span attrs summed into the per-query pruning/row accounting
PRUNING_KEYS = ("portions_total", "portions_skipped", "chunks_read",
                "chunks_skipped", "resident_portions", "resident_rows")
#: span names that carry scan-level stage/pruning/compile attrs
SCAN_SPANS = ("scan", "shard.scan")
#: span names that carry stage/pruning attrs (the DQ executor charges
#: all its source scans to its one span)
STAGE_SPANS = SCAN_SPANS + ("dq",)


@dataclasses.dataclass
class QueryProfile:
    """One query's assembled execution profile."""

    sql: str = ""
    kind: str = ""
    query_class: str = ""
    trace_id: int = 0
    seq: int = 0
    seconds: float = 0.0
    rows: int = 0
    plan_cache: str = ""      # hit | miss | "" (unknown/disabled)
    compile_cache: str = ""   # miss if ANY scan/transform compiled fresh
    compile_seconds: float = 0.0   # lowering + first-trace (XLA) time
    execute_seconds: float = 0.0   # seconds - compile_seconds
    fused_stages: int = 0     # plan nodes folded into one traced dispatch
    fragments_elided: int = 0  # dispatch boundaries removed by fusion
    #: scans that ran their consumer's aggregation themselves (the
    #: walk's aggregate pushdown, plan/executor.py): each block is
    #: aggregated under its filter mask and the scan ends on the
    #: device in a handful of rows
    agg_pushdown: int = 0
    #: cross-query batching (kqp/batch.py): group id + member count of
    #: the micro-batch that served this statement (0 = unbatched), how
    #: many of its scan sites were served by a staging shared with
    #: batchmates, and the wait-for-window vs shared-execute split
    batch_id: int = 0
    batch_size: int = 0
    shared_scan: int = 0
    batch_wait_seconds: float = 0.0
    batch_execute_seconds: float = 0.0
    stages: dict = dataclasses.field(default_factory=dict)
    pruning: dict = dataclasses.field(default_factory=dict)
    #: host-boundary counters from the sync sanitizer
    #: (analysis.syncsan, YDB_TPU_SYNCSAN=1): h2d/d2h transfers,
    #: blocking syncs and XLA compiles this statement crossed; {} when
    #: the sanitizer is off
    syncsan: dict = dataclasses.field(default_factory=dict)
    #: device-byte counters from the footprint sanitizer
    #: (analysis.memsan, YDB_TPU_MEMSAN=1): peak/live HBM bytes, charge
    #: count and unbudgeted allocations this statement made; {} when
    #: the sanitizer is off
    memsan: dict = dataclasses.field(default_factory=dict)
    #: per-stage busy fractions + overlap coefficients from the
    #: data-movement timeline (obs.timeline); {} when the ring is off
    stage_occupancy: dict = dataclasses.field(default_factory=dict)
    #: 1 when the statement failed mid-execution (the profile still
    #: lands in the ring so slow-then-failing statements stay visible)
    error: int = 0
    #: why it failed: "cancelled" (deadline), "overloaded" (admission
    #: shed), else the error type name; "" on success
    error_reason: str = ""
    #: workload pool the statement admitted under (serving/tenants.py);
    #: "" for sessions on clusters without a front door
    tenant: str = ""
    spans: list = dataclasses.field(default_factory=list)

    def to_dict(self, include_spans: bool = False) -> dict:
        """JSON-ready summary. Spans are excluded by default — every
        current consumer (bench extras, the viewer's top-N list) wants
        the summary, and span detail is served separately as a tree
        (``span_tree``) — only ``include_spans=True`` ships the raw
        list."""
        d = dataclasses.asdict(self)
        if not include_spans:
            del d["spans"]
            d["span_count"] = len(self.spans)
        d["seconds"] = round(self.seconds, 6)
        d["compile_seconds"] = round(self.compile_seconds, 6)
        d["execute_seconds"] = round(self.execute_seconds, 6)
        return d

    def span_tree(self) -> list[dict]:
        """Spans nested children-under-parents (forest of roots)."""
        by_id = {s["span_id"]: dict(s, children=[]) for s in self.spans}
        roots = []
        for s in by_id.values():
            parent = by_id.get(s["parent_id"])
            if parent is not None:
                parent["children"].append(s)
            else:
                roots.append(s)
        return roots


def _span_dict(s) -> dict:
    return {
        "name": s.name, "span_id": s.span_id,
        "parent_id": s.parent_id, "thread": s.thread,
        "seconds": round(s.seconds, 6), "attrs": dict(s.attrs),
    }


def self_seconds(spans) -> dict:
    """span id -> self time: a span's duration minus its children's on
    its own thread. Spans that are not lexical (``annotated`` false:
    ``dq.task``) overlap their siblings and count for nothing here."""
    by_id = {s.span_id: s for s in spans}
    out = {s.span_id: s.seconds for s in spans if s.annotated}
    for s in spans:
        parent = by_id.get(s.parent_id)
        if (s.annotated and parent is not None and parent.annotated
                and parent.thread == s.thread):
            out[parent.span_id] -= s.seconds
    return out


def statement_stages(spans, seconds: float) -> dict:
    """``SPAN_STAGE`` applied to the self times of the spans on the
    statement's thread (that of the span whose parent is not among
    them: the root), with what is left of ``seconds`` as
    ``unattributed``."""
    out = {k: 0.0 for k in STATEMENT_KEYS}
    if not spans:
        return out
    by_id = {s.span_id: s for s in spans}
    thread = next((s for s in spans if s.parent_id not in by_id),
                  spans[0]).thread
    names = {s.name for s in spans}
    mesh_keys = [k for n, k in MESH_SPAN_KEYS.items() if n in names]
    out.update(dict.fromkeys(mesh_keys, 0.0))
    # a statement with neither kind of span (Q1, Q6 pushed down; Q3 on
    # DQ) looks up no span's ancestors
    walk = not mesh_keys and not names.isdisjoint(WALK_SPAN_KEYS)
    selfs = self_seconds(spans)
    for s in spans:
        stage = SPAN_STAGE.get(s.name)
        if s.thread != thread or not s.annotated:
            continue
        carved = stage in ("dispatch", "device_wait")
        if mesh_keys:
            if carved:
                stage = _mesh_key(s, by_id) or stage
        elif walk and (carved or s.name in WALK_SPAN_KEYS):
            stage = _walk_key(s, by_id) or stage
        if stage is not None:
            out[stage] = out.get(stage, 0.0) + selfs[s.span_id]
    out["unattributed"] = max(0.0, seconds - sum(out.values()))
    return out


def dq_stage_seconds(spans, attr: str) -> float | None:
    """A ``DQ_STAGE_VIEWS`` key's seconds, by the attr its stages' spans
    carry, or None without such a stage."""
    stages = {s.span_id for s in spans
              if s.name == "dispatch" and attr in s.attrs}
    if not stages:
        return None
    by_id = {s.span_id: s for s in spans}
    thread = next(s for s in spans if s.parent_id not in by_id).thread
    selfs = self_seconds(spans)
    total = 0.0
    for s in spans:
        if s.thread != thread or not s.annotated:
            continue
        up = s
        while up is not None and up.span_id not in stages:
            up = by_id.get(up.parent_id)
        if up is not None:
            total += selfs[s.span_id]
    return total


def _walk_key(span, by_id: dict) -> str | None:
    """``concat`` or ``transform`` for a ``host.concat`` or
    ``transform`` span and what is beneath one, by the nearest above."""
    while span is not None:
        if span.name in WALK_SPAN_KEYS:
            return WALK_SPAN_KEYS[span.name]
        span = by_id.get(span.parent_id)
    return None


def _mesh_key(span, by_id: dict) -> str | None:
    """The mesh executor's statement key for ``span``, by the nearest
    ``mesh``, ``mesh.shuffle``, ``mesh.join`` or scan span above it:
    None beneath a scan span (a shard scan's work, not the mesh's) or
    outside the ``mesh`` span."""
    parent = by_id.get(span.parent_id)
    while parent is not None:
        if parent.name in SCAN_SPANS:
            return None
        if parent.name in MESH_SPAN_KEYS:
            return MESH_SPAN_KEYS[parent.name]
        parent = by_id.get(parent.parent_id)
    return None


def subtree(spans, root_span_id: int) -> list:
    """The spans descending from ``root_span_id`` (root excluded)."""
    children: dict = {}
    for s in spans:
        children.setdefault(s.parent_id, []).append(s)
    out, stack = [], [root_span_id]
    while stack:
        for s in children.get(stack.pop(), ()):
            out.append(s)
            stack.append(s.span_id)
    return out


def build_profile(spans, sql: str = "", kind: str = "",
                  query_class: str = "", seconds: float | None = None,
                  rows: int | None = None, seq: int = 0) -> QueryProfile:
    """Fold one trace's finished spans into a QueryProfile.

    ``spans`` is ``tracer.spans_for(trace_id)``; the root "query" span
    supplies totals when ``seconds``/``rows`` are not passed."""
    p = QueryProfile(sql=sql, kind=kind, query_class=query_class,
                     seq=seq)
    root = next((s for s in spans if s.parent_id is None), None)
    if root is not None:
        p.trace_id = root.trace_id
        p.kind = p.kind or str(root.attrs.get("kind", ""))
    elif spans:
        p.trace_id = spans[0].trace_id
    p.seconds = (seconds if seconds is not None
                 else (root.seconds if root is not None else 0.0))
    p.stages = {k: 0.0 for k in STAGE_KEYS}
    p.pruning = {k: 0 for k in PRUNING_KEYS}
    rows_out = 0
    for s in spans:
        a = s.attrs
        if a.get("plan_cache") and not p.plan_cache:
            p.plan_cache = str(a["plan_cache"])
        if "syncsan_compiles" in a and not p.syncsan:
            p.syncsan = {
                k[len("syncsan_"):]: int(v) for k, v in a.items()
                if k.startswith("syncsan_")}
        if "memsan_peak" in a and not p.memsan:
            p.memsan = {
                k[len("memsan_"):]: int(v) for k, v in a.items()
                if k.startswith("memsan_")}
        if s.name == "ssa.compile":
            p.compile_seconds += s.seconds
        if s.name == "plan.fuse":
            # whole-plan single-trace execution (ssa.plan_fuse): one
            # span per fused dispatch carrying the fusion accounting
            p.fused_stages = max(p.fused_stages,
                                 int(a.get("fused_stages", 0)))
            p.fragments_elided += int(a.get("fragments_elided", 0))
            if a.get("compile_cache") == "miss":
                p.compile_cache = "miss"
            elif (a.get("compile_cache") == "hit"
                  and not p.compile_cache):
                p.compile_cache = "hit"
            p.compile_seconds += float(
                a.get("first_trace_seconds", 0.0))
            continue
        if s.name == "dispatch.batch":
            # cross-query micro-batch seat (kqp/batch.py): one span per
            # member on its own session thread, so per-statement
            # profiles attribute window wait vs shared execute
            p.batch_id = int(a.get("batch_id", 0))
            p.batch_size = int(a.get("batch_size", 0))
            p.shared_scan = int(a.get("shared_scan", 0))
            p.batch_wait_seconds += float(a.get("wait_seconds", 0.0))
            p.batch_execute_seconds += float(
                a.get("execute_seconds", 0.0))
            continue
        if s.name == "dq.task":
            # DQ queries run their device dispatches inside compute
            # actors (no scan/transform spans on that path): the tasks'
            # accumulated compute seconds ARE the device time
            p.stages["compute"] += float(a.get("compute_seconds", 0.0))
            continue
        if s.name not in STAGE_SPANS and s.name != "transform":
            continue
        if a.get("compile_cache") == "miss":
            p.compile_cache = "miss"
        elif a.get("compile_cache") == "hit" and not p.compile_cache:
            p.compile_cache = "hit"
        p.compile_seconds += float(a.get("first_trace_seconds", 0.0))
        if s.name in SCAN_SPANS:
            rows_out += int(a.get("rows", 0))
            p.agg_pushdown += int(a.get("agg_pushdown", 0))
        if s.name in STAGE_SPANS:
            for k in STAGE_KEYS:
                p.stages[k] += float(a.get(f"stage_{k}", 0.0))
            for k in PRUNING_KEYS:
                p.pruning[k] += int(a.get(k, 0))
    p.stages.update(statement_stages(spans, p.seconds))
    for key, attr in DQ_STAGE_VIEWS.items():
        seconds = dq_stage_seconds(spans, attr)
        if seconds is not None:
            p.stages[key] = seconds
    p.stages = {k: round(v, 6) for k, v in p.stages.items()}
    p.rows = rows if rows is not None else rows_out
    p.execute_seconds = max(0.0, p.seconds - p.compile_seconds)
    p.spans = [_span_dict(s) for s in spans]
    from ydb_tpu.obs import timeline

    if timeline.timeline_enabled() and p.trace_id:
        p.stage_occupancy = timeline.query_occupancy(
            p.trace_id, wall=p.seconds or None)
    return p


def classify_plan(plan) -> str:
    """Query class for latency-histogram bucketing: joins dominate
    aggregates dominate plain scans."""
    from ydb_tpu.plan.nodes import Concat, ExpandJoin, LookupJoin, \
        Transform
    from ydb_tpu.ssa.program import GroupByStep

    has_join = False
    has_agg = False
    stack = [plan]
    while stack:
        n = stack.pop()
        if isinstance(n, (LookupJoin, ExpandJoin)):
            has_join = True
            stack += [n.probe, n.build]
        elif isinstance(n, Transform):
            if any(isinstance(st, GroupByStep)
                   for st in n.program.steps):
                has_agg = True
            stack.append(n.input)
        elif isinstance(n, Concat):
            stack += list(n.inputs)
        else:
            prog = getattr(n, "program", None)
            if prog is not None and any(
                    isinstance(st, GroupByStep) for st in prog.steps):
                has_agg = True
    if has_join:
        return "select_join"
    if has_agg:
        return "select_agg"
    return "select_scan"


class ProfileRing:
    """Bounded ring of recent QueryProfiles (the ``.sys/top_queries``
    backing store). Thread-safe: concurrent sessions append while sys
    views / the viewer snapshot."""

    def __init__(self, capacity: int = 128):
        self.capacity = max(1, int(capacity))
        self._items: list[QueryProfile] = []
        self._lock = threading.Lock()
        self._seq = 0

    def add(self, profile: QueryProfile) -> None:
        with self._lock:
            self._seq += 1
            profile.seq = self._seq
            self._items.append(profile)
            if len(self._items) > self.capacity:
                del self._items[: len(self._items) - self.capacity]

    def recent(self) -> list[QueryProfile]:
        """Arrival order, oldest first."""
        with self._lock:
            return list(self._items)

    def top(self, n: int = 16) -> list[QueryProfile]:
        """The n most expensive retained queries, slowest first."""
        with self._lock:
            items = list(self._items)
        items.sort(key=lambda p: p.seconds, reverse=True)
        return items[:n]

    def __len__(self) -> int:
        with self._lock:
            return len(self._items)


def format_plan_analyzed(plan, profile: QueryProfile) -> str:
    """EXPLAIN ANALYZE rendering: the physical plan plus measured
    actuals (per-stage seconds, pruning/row counts, compile-vs-execute
    split). Key=value lines so tests and tools parse them directly."""
    from ydb_tpu.plan.nodes import format_plan

    lines = [format_plan(plan), "-- actuals --"]
    lines.append(
        f"total: seconds={profile.seconds:.6f} rows={profile.rows}")
    lines.append(
        "compile: compile_cache=" + (profile.compile_cache or "none")
        + f" compile_seconds={profile.compile_seconds:.6f}"
        + f" execute_seconds={profile.execute_seconds:.6f}")
    if profile.syncsan:
        ss = profile.syncsan
        lines.append("syncsan: " + " ".join(
            f"{k}={ss.get(k, 0)}"
            for k in ("h2d", "d2h", "syncs", "compiles")))
    if profile.memsan:
        ms = profile.memsan
        lines.append("memsan: " + " ".join(
            f"{k}={ms.get(k, 0)}"
            for k in ("peak", "live", "charges", "unbudgeted")))
    if profile.fused_stages:
        lines.append(
            f"fusion: fused_stages={profile.fused_stages}"
            f" fragments_elided={profile.fragments_elided}")
    if profile.batch_size:
        lines.append(
            f"batching: batch_id={profile.batch_id}"
            f" batch_size={profile.batch_size}"
            f" shared_scan={profile.shared_scan}"
            f" wait_seconds={profile.batch_wait_seconds:.6f}"
            f" execute_seconds={profile.batch_execute_seconds:.6f}")
    st = profile.stages
    lines.append("stages: " + " ".join(
        f"{k}={st.get(k, 0.0):.6f}" for k in STAGE_KEYS))
    keys = STATEMENT_KEYS + tuple(
        k for k in (*MESH_SPAN_KEYS.values(), *WALK_SPAN_KEYS.values(),
                    WRITE_KEY)
        if k in st)
    lines.append("statement: " + " ".join(
        f"{k}={st.get(k, 0.0):.6f}" for k in keys))
    pr = profile.pruning
    lines.append("rows: " + " ".join(
        f"{k}={pr.get(k, 0)}" for k in PRUNING_KEYS))
    occ = profile.stage_occupancy
    if occ:
        frac = occ.get("fraction", {})
        bits = [f"{k}={frac.get(k, 0.0):.4f}" for k in STAGE_KEYS
                if k in frac]
        for pair, coeff in sorted(occ.get("overlap", {}).items()):
            bits.append(f"{pair}={coeff:.4f}")
        lines.append("occupancy: " + " ".join(bits))
    for s in profile.spans:
        if s["name"] not in SCAN_SPANS and s["name"] not in MESH_SPAN_KEYS:
            continue
        a = s["attrs"]
        bits = [f"seconds={s['seconds']:.6f}"]
        # an exchange or a local join of the mesh: all it carries
        # (bucket sizes, worst count, attempts, bytes; capacities)
        shown = a if s["name"] in MESH_SPAN_KEYS and s["name"] != MESH_KEY \
            else ("table", "shard", "device", "devices", "answered",
                  "rows", "compile_cache", "agg_pushdown",
                  "pushdown_declined")
        bits += [f"{k}={a[k]}" for k in shown if k in a]
        lines.append(f"  {s['name']}: " + " ".join(bits))
    return "\n".join(lines)


class _Holder:
    profile: QueryProfile | None = None


@contextlib.contextmanager
def profiled(sql: str = "", kind: str = "select",
             query_class: str = "", tracer=None):
    """Run a block under a fresh root span and hand back its profile
    (``holder.profile`` after exit) — the seam for profiling
    engine-tier scans that never pass through a session."""
    from ydb_tpu.obs.tracing import Tracer, activate

    tr = tracer if tracer is not None else Tracer()
    holder = _Holder()
    root = tr.trace("query")
    t0 = time.perf_counter()
    try:
        with activate(root):
            yield holder
    finally:
        root.finish()
        holder.profile = build_profile(
            tr.spans_for(root.trace_id), sql=sql, kind=kind,
            query_class=query_class,
            seconds=time.perf_counter() - t0)
