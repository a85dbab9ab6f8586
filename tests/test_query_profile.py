"""End-to-end query profiling: span-threaded execution, EXPLAIN
ANALYZE actuals vs probe values, trace-id propagation across DQ /
conveyor threads, sys_top_queries / sys_query_log, latency histograms
on /counters/prometheus, profile ring bounding, disabled path."""

import json
import threading

import numpy as np
import pytest

from ydb_tpu.kqp.session import Cluster
from ydb_tpu.obs import tracing
from ydb_tpu.obs.counters import Histogram
from ydb_tpu.obs.probes import TraceSession
from ydb_tpu.obs.profile import (STAGE_KEYS, STATEMENT_KEYS, ProfileRing,
                                 build_profile)
from ydb_tpu.obs.tracing import Tracer


MAIN_THREAD = threading.get_ident()


@pytest.fixture
def cluster():
    c = Cluster()
    s = c.session()
    s.execute("CREATE TABLE ev (id int64, ts int64, v int64, "
              "PRIMARY KEY (id)) WITH (shards = 2)")
    # several commits -> several portions per shard
    for base in (0, 100, 200):
        vals = ", ".join(f"({base + i}, {base + i}, {(base + i) * 3})"
                         for i in range(8))
        s.execute(f"INSERT INTO ev VALUES {vals}")
    return c


def tpch_cluster(sf, tables=("lineitem",)):
    """A Cluster holding the named TPC-H tables (several portions per
    shard each), and the generated data."""
    from ydb_tpu.scheme.model import type_to_str
    from ydb_tpu.workload import tpch

    data = tpch.TpchData(sf=sf, seed=7)
    c = Cluster()
    s = c.session()
    for name in tables:
        schema = getattr(tpch, name.upper() + "_SCHEMA")
        cols = ", ".join(f"{f.name} {type_to_str(f.type)}"
                         for f in schema.fields)
        s.execute(f"CREATE TABLE {name} ({cols}, "
                  f"PRIMARY KEY ({schema.fields[0].name})) "
                  "WITH (shards = 1)")
        rows = data.tables[name]
        n = len(rows[schema.fields[0].name])
        step = max(1, n // 3)
        for off in range(0, n, step):  # 3 commits -> 3 portions
            arrays = {}
            for f in schema.fields:
                v = rows[f.name][off:off + step]
                if f.type.is_string:
                    arrays[f.name] = [
                        bytes(x) for x in data.dicts[f.name].decode(
                            np.asarray(v, dtype=np.int32))]
                else:
                    arrays[f.name] = v
            c.tables[name].insert(arrays)
    c._invalidate_plans()
    return c, data


def lineitem_cluster(sf=0.002):
    """A Cluster holding TPC-H lineitem (several portions per shard)."""
    c, data = tpch_cluster(sf)
    return c, data.tables["lineitem"]


# ---------- span-threaded execution ----------

def test_span_tree_shape_single_stage(cluster):
    s = cluster.session()
    out = s.execute("SELECT ts, sum(v) AS sv FROM ev "
                    "GROUP BY ts ORDER BY ts LIMIT 5")
    assert out.num_rows == 5
    p = s.last_profile
    assert p is not None
    names = {sp["name"] for sp in p.spans}
    assert {"query", "plan", "parse", "execute", "scan",
            "fetch"} <= names
    by_id = {sp["span_id"]: sp for sp in p.spans}
    # every span belongs to one trace and parents resolve inside it
    root = next(sp for sp in p.spans if sp["parent_id"] is None)
    assert root["name"] == "query"
    for sp in p.spans:
        if sp["parent_id"] is not None:
            assert sp["parent_id"] in by_id
    # parse nests under plan nests under query
    parse = next(sp for sp in p.spans if sp["name"] == "parse")
    assert by_id[parse["parent_id"]]["name"] == "plan"
    assert by_id[by_id[parse["parent_id"]]["parent_id"]]["name"] == \
        "query"


def test_span_tree_shape_multi_stage_dq(cluster):
    s = cluster.session()
    s.execute("CREATE TABLE dim (ts int64, label int64, "
              "PRIMARY KEY (ts))")
    vals = ", ".join(f"({i}, {i % 4})" for i in range(0, 300))
    s.execute(f"INSERT INTO dim VALUES {vals}")
    out = s.execute(
        "SELECT d.label, count(*) AS n FROM ev e "
        "JOIN dim d ON e.ts = d.ts GROUP BY d.label ORDER BY d.label")
    assert out.num_rows > 0
    p = s.last_profile
    names = {sp["name"] for sp in p.spans}
    assert "dq" in names, names
    tasks = [sp for sp in p.spans if sp["name"] == "dq.task"]
    assert len(tasks) >= 3  # scan stages + join + final
    stages = {sp["attrs"]["stage"] for sp in tasks}
    assert len(stages) >= 3
    assert all("compute_seconds" in sp["attrs"] for sp in tasks)
    dq = next(sp for sp in p.spans if sp["name"] == "dq")
    assert dq["attrs"]["stages"] >= 4
    assert p.query_class == "select_join"
    # device time for a join query comes from the tasks' accumulated
    # compute seconds (there are no scan/transform spans on this path)
    task_compute = sum(sp["attrs"]["compute_seconds"] for sp in tasks)
    assert task_compute > 0
    assert p.stages["compute"] == pytest.approx(task_compute, abs=1e-6)
    # the tasks' dispatches and their waits on the device are spans of
    # the statement's thread, folded into its own stages
    assert p.stages["dispatch"] > 0 and p.stages["device_wait"] > 0


def test_trace_id_propagates_to_conveyor_producer(cluster):
    s = cluster.session()
    s.execute("SELECT sum(v) AS sv FROM ev")
    p = s.last_profile
    producers = [sp for sp in p.spans if sp["name"] == "scan.producer"]
    assert producers, "no prefetch producer span recorded"
    # the producer ran on a conveyor worker, not the session thread,
    # yet its span landed in the SAME trace
    assert any(sp["attrs"]["thread"] != MAIN_THREAD
               for sp in producers)
    assert all(
        sp["span_id"] in {q["span_id"] for q in p.spans}
        for sp in producers)


def test_compile_vs_execute_split_across_runs(cluster):
    sql = "SELECT ts, sum(v) AS sv FROM ev GROUP BY ts"
    s = cluster.session()
    s.execute(sql)
    first = s.last_profile
    assert first.plan_cache == "miss"
    assert first.compile_cache == "miss"
    assert first.compile_seconds > 0          # lowering + first trace
    assert first.execute_seconds >= 0
    names = {sp["name"] for sp in first.spans}
    assert "ssa.compile" in names
    s.execute(sql)
    second = s.last_profile
    assert second.plan_cache == "hit"
    assert second.compile_cache == "hit"       # warm: no retrace
    assert second.compile_seconds == 0.0
    assert second.seconds < first.seconds
    # compile-cache counters aggregate per cluster
    snap = cluster.counters.snapshot()
    assert snap.get("miss|component=kqp,kind=compile_cache", 0) >= 1
    assert snap.get("hit|component=kqp,kind=compile_cache", 0) >= 1


def test_scan_stage_seconds_and_pruning_attrs(cluster):
    s = cluster.session()
    s.execute("SELECT sum(v) AS sv FROM ev WHERE ts >= 200")
    p = s.last_profile
    assert p.pruning["portions_total"] > 0
    assert p.pruning["portions_skipped"] > 0   # zone maps pruned
    assert p.pruning["chunks_read"] > 0
    assert set(p.stages) == set(STAGE_KEYS) | set(STATEMENT_KEYS)
    assert p.stages["read"] > 0
    assert p.stages["compute"] > 0
    assert p.stages["dispatch"] > 0
    assert p.stages["device_wait"] > 0


# ---------- EXPLAIN ANALYZE ----------

def test_explain_analyze_actuals_match_probes(cluster):
    sql = ("EXPLAIN ANALYZE SELECT ts, sum(v) AS sv FROM ev "
           "WHERE ts >= 100 GROUP BY ts")
    s = cluster.session()
    with TraceSession("columnshard.scan.*") as ts:
        txt = s.execute(sql)
    assert "TableScan ev" in txt and "-- actuals --" in txt
    assert "compile_cache=miss" in txt
    prune = [p for n, p in ts.events
             if n == "columnshard.scan.pruning" and p["shard"] == -1]
    stages = [p for n, p in ts.events
              if n == "columnshard.scan.stages" and p["shard"] == -1]
    assert prune and stages
    pr, st = prune[-1], stages[-1]
    for k in ("portions_total", "portions_skipped", "chunks_read",
              "chunks_skipped"):
        assert f"{k}={pr[k]}" in txt
    for k in ("read", "merge", "stage", "compute"):
        assert f"{k}={st[k]:.6f}" in txt
    # second consecutive run: warm execute, no compile
    txt2 = s.execute(sql)
    assert "compile_cache=hit" in txt2
    assert "compile_seconds=0.000000" in txt2


def test_explain_analyze_tpch_q1():
    from ydb_tpu.workload.queries import TPCH

    c, li = lineitem_cluster()
    s = c.session()
    with TraceSession("columnshard.scan.*") as ts:
        txt = s.execute("EXPLAIN ANALYZE " + TPCH["q1"])
    assert "TableScan lineitem" in txt
    assert "compile_cache=miss" in txt
    pr = [p for n, p in ts.events
          if n == "columnshard.scan.pruning" and p["shard"] == -1][-1]
    assert f"chunks_read={pr['chunks_read']}" in txt
    assert pr["chunks_read"] > 0
    st = [p for n, p in ts.events
          if n == "columnshard.scan.stages" and p["shard"] == -1][-1]
    for k in ("read", "stage", "compute"):
        assert f"{k}={st[k]:.6f}" in txt
    # the measured total covers its parts
    total = float(txt.split("seconds=")[1].split()[0])
    assert total > 0
    txt2 = s.execute("EXPLAIN ANALYZE " + TPCH["q1"])
    assert "compile_cache=hit" in txt2
    assert "compile_seconds=0.000000" in txt2
    # the analyzed query really ran: row counts match a direct SELECT
    out = s.execute(TPCH["q1"])
    assert f"rows={out.num_rows}" in txt2


def test_plain_explain_unchanged(cluster):
    s = cluster.session()
    txt = s.execute("EXPLAIN SELECT sum(v) AS sv FROM ev")
    assert "TableScan ev" in txt
    assert "-- actuals --" not in txt


# ---------- sys views + viewer + counters ----------

def test_top_queries_and_query_log_sysviews(cluster):
    s = cluster.session()
    s.execute("SELECT ts, sum(v) AS sv FROM ev GROUP BY ts")
    out = s.execute(
        "SELECT rank, query_text, query_class, seconds, rows, "
        "compile_seconds, compile_cache FROM sys_top_queries "
        "ORDER BY rank")
    assert out.num_rows >= 3
    ranks = list(out.column("rank"))
    assert ranks == sorted(ranks)
    texts = [v.decode() for v in out.strings("query_text")]
    assert any("GROUP BY ts" in t for t in texts)
    classes = [v.decode() for v in out.strings("query_class")]
    assert "select_agg" in classes
    # seconds ordered most-expensive-first
    secs = list(out.column("seconds"))
    assert secs == sorted(secs, reverse=True)

    log = s.execute("SELECT seq, kind, spans FROM sys_query_log "
                    "ORDER BY seq")
    seqs = list(log.column("seq"))
    assert seqs == sorted(seqs) and len(seqs) >= 4
    assert all(n > 0 for n in log.column("spans"))


def test_viewer_query_profile_endpoint(cluster):
    from ydb_tpu.obs.viewer import Viewer

    s = cluster.session()
    s.execute("SELECT ts, sum(v) AS sv FROM ev GROUP BY ts")
    v = Viewer(cluster).start()
    try:
        import urllib.request

        with urllib.request.urlopen(
                f"http://127.0.0.1:{v.port}/viewer/json/query_profile",
                timeout=10) as r:
            assert r.status == 200
            payload = json.loads(r.read())
        assert payload["top"] and payload["last"]
        last = payload["last"]
        assert last["span_tree"], "span tree missing"
        assert last["stages"]["compute"] >= 0
        seq = payload["recent"][-1]["seq"]
        with urllib.request.urlopen(
                f"http://127.0.0.1:{v.port}"
                f"/viewer/json/query_profile?seq={seq}",
                timeout=10) as r:
            one = json.loads(r.read())
        assert one["seq"] == seq
        # the HTML page carries the profiles tab
        with urllib.request.urlopen(
                f"http://127.0.0.1:{v.port}/viewer", timeout=10) as r:
            assert b"profiles" in r.read()
    finally:
        v.stop()


def test_prometheus_latency_histograms(cluster):
    s = cluster.session()
    s.execute("SELECT ts, sum(v) AS sv FROM ev GROUP BY ts")
    s.execute("SELECT v FROM ev LIMIT 3")
    text = cluster.counters.encode_prometheus()
    assert 'query_latency_seconds_bucket' in text
    assert 'query_class="select_agg"' in text
    assert 'query_class="select_scan"' in text
    # p50/p99 gauges ride beside the raw histogram
    p50 = [ln for ln in text.splitlines()
           if ln.startswith("query_latency_p50")
           and 'query_class="select_agg"' in ln]
    assert p50 and float(p50[0].rsplit(" ", 1)[1]) > 0
    assert any(ln.startswith("query_latency_p99")
               for ln in text.splitlines())


# ---------- ring bounding + disabled path ----------

def test_profile_ring_bounded(cluster):
    cluster.profiles = ProfileRing(capacity=4)
    s = cluster.session()
    for i in range(9):
        s.execute(f"SELECT v FROM ev WHERE id = {i}")
    assert len(cluster.profiles) == 4
    recent = cluster.profiles.recent()
    # ring keeps the LAST 4, seq keeps counting
    assert [p.seq for p in recent] == sorted(p.seq for p in recent)
    assert recent[-1].seq == 9
    assert len(cluster.profiles.top(16)) == 4


def test_disabled_path():
    tracing.PROFILE_FORCE = False
    try:
        c = Cluster()
        s = c.session()
        s.execute("CREATE TABLE ev (id int64, v int64, "
                  "PRIMARY KEY (id))")
        s.execute("INSERT INTO ev VALUES (1, 2), (2, 4)")
        out = s.execute("SELECT sum(v) AS sv FROM ev")
        assert out.num_rows == 1
        assert s.last_profile is None
        assert len(c.profiles) == 0
        # root/plan/execute spans remain (the pre-profile surface),
        # nothing deeper
        q = [sp for sp in c.tracer.finished
             if sp.name == "query"][-1]
        names = {sp.name
                 for sp in c.tracer.spans_for(q.trace_id)}
        assert names == {"query", "plan", "execute"}
        # no per-class histogram was touched
        text = c.counters.encode_prometheus()
        assert "query_latency_seconds" not in text
        # EXPLAIN ANALYZE still runs and reports totals
        txt = s.execute("EXPLAIN ANALYZE SELECT sum(v) AS sv FROM ev")
        assert "-- actuals --" in txt and "total: seconds=" in txt
    finally:
        tracing.PROFILE_FORCE = None


# ---------- tracer thread-safety + index ----------

def test_tracer_concurrent_finish_and_index():
    tr = Tracer(max_spans=500)
    roots = [tr.trace(f"q{i}") for i in range(8)]
    errs = []

    def hammer(root):
        try:
            for _ in range(100):
                root.child("w").set(thread=threading.get_ident()) \
                    .finish()
        except Exception as e:  # pragma: no cover
            errs.append(e)

    threads = [threading.Thread(target=hammer, args=(r,))
               for r in roots]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    assert len(tr.finished) == 500  # bounded (8 * 100 > 500)
    # the index agrees with the ring after eviction
    total = sum(len(tr.spans_for(r.trace_id)) for r in roots)
    assert total == 500
    for r in roots:
        for sp in tr.spans_for(r.trace_id):
            assert sp.trace_id == r.trace_id


def test_tracer_index_lookup_matches_linear_scan():
    tr = Tracer()
    with tr.trace("a") as a:
        a.child("x").finish()
    with tr.trace("b") as b:
        b.child("y").finish()
        b.child("z").finish()
    assert {s.name for s in tr.spans_for(a.trace_id)} == {"a", "x"}
    assert {s.name for s in tr.spans_for(b.trace_id)} == {"b", "y", "z"}
    assert tr.spans_for(999999) == []


# ---------- histogram satellite ----------

def test_histogram_interpolates_within_bucket():
    h = Histogram(bounds=(1.0, 2.0, 4.0))
    h.observe(1.5)
    assert h.percentile(0.5) == pytest.approx(1.5)
    h2 = Histogram(bounds=(1.0, 2.0))
    for _ in range(4):
        h2.observe(1.1)  # all land in (1, 2]
    # quartiles spread linearly across the winning bucket
    assert 1.0 < h2.percentile(0.25) < h2.percentile(0.75) < 2.0


def test_histogram_submillisecond_p50_not_quantized():
    h = Histogram()  # default bounds now reach 1us
    for _ in range(50):
        h.observe(0.0004)  # 400us device op
    p50 = h.percentile(0.5)
    assert p50 < 0.001, "sub-ms p50 quantized to the old 1ms floor"
    assert p50 > 1e-5


def test_histogram_overflow_and_empty():
    h = Histogram(bounds=(1.0, 2.0))
    assert h.percentile(0.5) == 0.0
    h.observe(50.0)
    assert h.percentile(0.5) == 2.0  # finite (top bound), not inf


# ---------- profile assembly unit ----------

def test_build_profile_aggregates_scan_spans():
    tr = Tracer()
    root = tr.trace("query")
    sc1 = root.child("scan").set(
        table="a", rows=10, compile_cache="miss",
        first_trace_seconds=0.5, stage_read=0.1, stage_compute=0.2,
        portions_total=4, portions_skipped=1, chunks_read=3,
        chunks_skipped=2)
    sc1.finish()
    sc2 = root.child("shard.scan").set(
        shard=0, rows=5, compile_cache="hit", stage_read=0.3,
        stage_compute=0.1, portions_total=2, portions_skipped=0,
        chunks_read=1, chunks_skipped=0)
    sc2.finish()
    root.finish()
    p = build_profile(tr.spans_for(root.trace_id), sql="q",
                      kind="select", seconds=2.0)
    assert p.rows == 15
    assert p.compile_cache == "miss"
    assert p.compile_seconds == pytest.approx(0.5)
    assert p.execute_seconds == pytest.approx(1.5)
    assert p.stages["read"] == pytest.approx(0.4)
    assert p.stages["compute"] == pytest.approx(0.3)
    assert p.pruning == {"portions_total": 6, "portions_skipped": 1,
                         "chunks_read": 4, "chunks_skipped": 2,
                         "resident_portions": 0, "resident_rows": 0}
    # spans with no dispatch or wait beneath them leave the statement's
    # own stages at zero and everything unattributed
    assert p.stages["device_wait"] == p.stages["dispatch"] == 0.0
    assert p.stages["unattributed"] == pytest.approx(2.0)
    tree = p.span_tree()
    assert tree[0]["name"] == "query"
    assert {c["name"] for c in tree[0]["children"]} == \
        {"scan", "shard.scan"}


# ---------- one span tree over the whole statement ----------

@pytest.fixture(scope="module")
def tpch():
    return tpch_cluster(0.01, ("lineitem", "orders", "customer"))[0]


@pytest.fixture
def walk(monkeypatch):
    """At a test's size the fused executor would answer Q1 and Q6; the
    chip's cells answer them by the streaming walk."""
    from ydb_tpu.ssa import plan_fuse

    monkeypatch.setattr(plan_fuse, "FUSE_FORCE", False)


def statement_spans(cluster, profile):
    """The finished Span objects of one profiled statement."""
    return cluster.tracer.spans_for(profile.trace_id)


@pytest.mark.parametrize("qid,executor", [("q1", "scan"), ("q6", "scan"),
                                          ("q3", "dq")])
def test_both_executors_charge_every_layer(tpch, walk, qid, executor):
    from ydb_tpu.obs.profile import SPAN_STAGE, self_seconds
    from ydb_tpu.workload.queries import TPCH

    s = tpch.session()
    s.execute(TPCH[qid])           # compiles
    s.execute(TPCH[qid])
    p = s.last_profile
    spans = statement_spans(tpch, p)
    names = {sp.name for sp in spans}
    assert executor in names and "plan.fuse" not in names
    assert {"scan.pull", "dispatch", "device.wait", "fetch",
            "snapshot"} <= names
    assert set(STATEMENT_KEYS) <= set(p.stages)
    for k in ("plan", "pull", "dispatch", "device_wait", "fetch"):
        assert p.stages[k] > 0, k
    # self times on the statement's thread sum to its seconds: nothing
    # is counted twice and no interval is lost between parent and child
    root = next(sp for sp in spans if sp.parent_id is None)
    selfs = self_seconds(spans)
    on_thread = [sp for sp in spans
                 if sp.thread == root.thread and sp.annotated]
    total = sum(selfs[sp.span_id] for sp in on_thread)
    slack = max(0.01 * p.seconds, 2e-4)   # a 2 ms statement: 0.2 ms
    assert total == pytest.approx(p.seconds, abs=slack)
    assert all(selfs[sp.span_id] >= -1e-6 for sp in on_thread)
    assert sum(p.stages[k] for k in STATEMENT_KEYS) == pytest.approx(
        p.seconds, abs=slack)
    named = sum(selfs[sp.span_id] for sp in on_thread
                if sp.name in SPAN_STAGE)
    assert p.stages["unattributed"] == pytest.approx(
        p.seconds - named, abs=1e-5)
    # a statement of 2 ms (Q6 here) keeps 0.3-0.7 ms of bookkeeping
    # outside any named leaf; at the chip's sizes that is nothing. A
    # thread descheduled between two spans lands there too: this one
    # timing comparison gets three statements on a loaded host
    loose = [(p.stages["unattributed"], p.seconds)]
    while loose[-1][0] >= max(0.10 * loose[-1][1], 1.5e-3) \
            and len(loose) < 3:
        s.execute(TPCH[qid])
        again = s.last_profile
        loose.append((again.stages["unattributed"], again.seconds))
    assert loose[-1][0] < max(0.10 * loose[-1][1], 1.5e-3), loose
    # spans are per block, dispatch and message batch: never per row
    assert len(spans) <= 500
    if executor == "dq":
        # the DQ source scans charge the walk's stages and counters
        assert sum(p.stages[k] for k in ("read", "merge", "stage")) > 0
        assert p.pruning["portions_total"] > 0
        dispatched = {sp.attrs.get("program") for sp in spans
                      if sp.name == "dispatch"}
        assert {"dq_stage", "join_lookup", "join_expand"} <= dispatched
        # a task opens in one message and finishes in another: it is
        # in the profile, not in the self-time arithmetic
        assert not any(sp.annotated for sp in spans
                       if sp.name == "dq.task")
    else:
        assert {sp.attrs.get("program") for sp in spans
                if sp.name == "dispatch"} >= {"scan_partial"}


def test_explain_analyze_prints_the_statement_line(tpch, walk):
    from ydb_tpu.workload.queries import TPCH

    txt = tpch.session().execute("EXPLAIN ANALYZE " + TPCH["q6"])
    lines = txt.splitlines()
    at = next(i for i, ln in enumerate(lines)
              if ln.startswith("stages: "))
    assert lines[at].split()[1:] == [
        ln for ln in lines[at].split()[1:]
        if ln.split("=")[0] in STAGE_KEYS]
    assert lines[at + 1].startswith("statement: ")
    got = dict(kv.split("=") for kv in lines[at + 1].split()[1:])
    assert list(got) == list(STATEMENT_KEYS)
    assert float(got["device_wait"]) > 0 and float(got["pull"]) > 0


def test_profile_off_opens_no_child_span_and_no_annotation(monkeypatch):
    opened = []

    class Counting(tracing.TraceAnnotation):
        def __init__(self, name, **kw):
            opened.append(name)
            super().__init__(name, **kw)

    from ydb_tpu.obs import probes

    monkeypatch.setattr(tracing, "TraceAnnotation", Counting)
    monkeypatch.setattr(probes, "TraceAnnotation", Counting)
    monkeypatch.setattr(tracing, "PROFILE_FORCE", False)
    c = Cluster()
    s = c.session()
    s.execute("CREATE TABLE ev (id int64, v int64, PRIMARY KEY (id))")
    s.execute("INSERT INTO ev VALUES (1, 2), (2, 4)")
    opened.clear()
    s.execute("SELECT sum(v) AS sv FROM ev")
    q = [sp for sp in c.tracer.finished if sp.name == "query"][-1]
    spans = c.tracer.spans_for(q.trace_id)
    assert {sp.name for sp in spans} == {"query", "plan", "execute"}
    assert not any(sp.annotated for sp in spans)
    assert opened == []
    # the same statement with profiling on annotates every span
    monkeypatch.setattr(tracing, "PROFILE_FORCE", True)
    s.execute("SELECT sum(v) AS sv FROM ev")
    spans = statement_spans(c, s.last_profile)
    assert sorted(n for n in opened if ".stage." not in n) == sorted(
        "ydb." + sp.name for sp in spans)
    assert not any(n.startswith("bench.") for n in opened)


def test_the_span_that_dispatched_carries_the_compile(cluster):
    sql = "SELECT ts, max(v) AS mv FROM ev WHERE v > 7 GROUP BY ts"
    before = tracing.compile_counts()
    s = cluster.session()
    s.execute(sql)
    spans = statement_spans(cluster, s.last_profile)
    built = {sp.name: sp.attrs["compile_built"] for sp in spans
             if sp.attrs.get("compile_built")}
    assert built.get("dispatch", 0) >= 1, built
    first = next(sp for sp in spans if sp.name == "dispatch"
                 and sp.attrs.get("compile_built"))
    assert first.attrs["compile_seconds"] > 0
    assert "program" in first.attrs
    after = tracing.compile_counts()
    assert after["built"] - before["built"] == sum(built.values())
    assert after["fetched"] == before["fetched"]   # no persistent cache
    s.execute(sql)
    again = statement_spans(cluster, s.last_profile)
    assert not any(sp.attrs.get("compile_built")
                   or sp.attrs.get("compile_fetched") for sp in again)
    assert tracing.compile_counts()["built"] == after["built"]


def _traced_q3(cluster, session, trace_dir):
    """One Q3 under the JAX profiler: its annotated spans, and every
    host line's ``ydb.*`` events as (start_ns, duration_ns, name)."""
    import glob

    import jax
    from jax.profiler import ProfileData
    from ydb_tpu.workload.queries import TPCH

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        session.execute(TPCH["q3"])
        p = session.last_profile
    finally:
        jax.profiler.stop_trace()
    spans = [sp for sp in statement_spans(cluster, p) if sp.annotated]
    path, = glob.glob(str(trace_dir / "plugins/profile/*/*.xplane.pb"))
    planes = ProfileData.from_file(path).planes
    lines = []
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for ln in plane.lines:
            evs = sorted((e.start_ns, e.duration_ns, e.name)
                         for e in ln.events
                         if e.name.startswith("ydb.")
                         and not e.name.startswith("ydb.stage."))
            if evs:
                lines.append(evs)
    assert not any(e.name.startswith("bench.") for plane in planes
                   for ln in plane.lines for e in ln.events)
    return spans, lines


def test_spans_land_on_the_profiler_trace(tpch, walk, tmp_path):
    """Every finished span of a statement run under the JAX profiler is
    a ``ydb.<name>`` host event of the same thread's line, as long as
    the span within 1 ms and nested as the span tree is."""
    from ydb_tpu.workload.queries import TPCH

    s = tpch.session()
    s.execute(TPCH["q3"])
    # the span's clock and the profiler's bracket the same code, a
    # descheduled thread between the two reads stretches one of them:
    # the 1 ms comparison gets three traced statements on a loaded
    # host, the structure below holds in every one of them
    for attempt in range(3):
        spans, lines = _traced_q3(tpch, s, tmp_path / str(attempt))
        assert len(spans) > 20
        # a thread's spans in start order are one line's events in order
        event_of = {}
        for thread in {sp.thread for sp in spans}:
            mine = sorted((sp for sp in spans if sp.thread == thread),
                          key=lambda sp: sp.start)
            want = ["ydb." + sp.name for sp in mine]
            line = next((evs for evs in lines
                         if [n for _, _, n in evs] == want), None)
            assert line is not None, (want, [len(evs) for evs in lines])
            event_of.update(zip((sp.span_id for sp in mine), line))
        by_id = {sp.span_id: sp for sp in spans}
        nested = 0
        for sp in spans:
            parent = by_id.get(sp.parent_id)
            if parent is None or parent.thread != sp.thread:
                continue
            (c0, cd, _), (p0, pd, _) = event_of[sp.span_id], \
                event_of[parent.span_id]
            assert p0 <= c0 and c0 + cd <= p0 + pd, (sp.name, parent.name)
            nested += 1
        assert nested > 20
        gap, worst = max(
            (abs(event_of[sp.span_id][1] / 1e9 - sp.seconds), sp.name)
            for sp in spans)
        if gap < 1e-3:
            break
    assert gap < 1e-3, worst


def test_a_leaf_span_opens_no_span_beneath_it():
    from ydb_tpu.blocks.block import TableBlock, concat_blocks
    from ydb_tpu import dtypes

    sch = dtypes.schema(("a", dtypes.INT64), ("b", dtypes.INT64))
    blocks = [TableBlock.from_numpy(
        {"a": np.arange(4) + i, "b": np.arange(4) * i}, sch)
        for i in range(3)]
    tr = Tracer()
    with tr.trace("query") as root, tracing.activate(root):
        out = concat_blocks(blocks)
        with tracing.span("after") as sp:
            assert tracing.current_span() is sp
    assert list(out.to_numpy()["a"]) == [0, 1, 2, 3, 1, 2, 3, 4,
                                         2, 3, 4, 5]
    spans = tr.spans_for(root.trace_id)
    concat = next(s for s in spans if s.name == "host.concat")
    gets = [s for s in spans if s.name == "device.get"]
    # two per column (data, validity), whatever the number of blocks,
    # and none beneath
    assert len(gets) == 4
    assert all(s.parent_id == concat.span_id for s in gets)
    assert not any(s.parent_id in {g.span_id for g in gets}
                   for s in spans)
    assert next(s for s in spans if s.name == "after").parent_id == \
        root.span_id
