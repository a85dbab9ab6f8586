"""Data-movement timeline: ring bounding under concurrent writers,
Chrome-trace JSON schema round-trip, occupancy math on hand-built
fixtures, warm-query busy sums vs EXPLAIN ANALYZE stage seconds,
movement byte counters, conveyor queue telemetry, sys_active_queries
live introspection, the slow-query watchdog and error=1 profiles."""

import json
import threading

import pytest

from ydb_tpu.kqp.session import Cluster
from ydb_tpu.obs import timeline
from ydb_tpu.obs.probes import TraceSession
from ydb_tpu.obs.profile import STAGE_KEYS
from ydb_tpu.obs.timeline import (
    Event,
    TimelineRing,
    export_chrome_trace,
    intersect_seconds,
    merge_intervals,
    occupancy_from_events,
    union_seconds,
)


@pytest.fixture
def forced_timeline():
    """Timeline ON for the test, restored after (ring cleared both
    sides so other tests see a quiet ring)."""
    prev = timeline.TIMELINE_FORCE
    timeline.TIMELINE_FORCE = True
    timeline.RING.clear()
    yield timeline.RING
    timeline.TIMELINE_FORCE = prev
    timeline.RING.clear()


@pytest.fixture
def cluster():
    c = Cluster()
    s = c.session()
    s.execute("CREATE TABLE ev (id int64, v int64, "
              "PRIMARY KEY (id)) WITH (shards = 2)")
    for base in (0, 100, 200):
        vals = ", ".join(f"({base + i}, {(base + i) * 3})"
                         for i in range(8))
        s.execute(f"INSERT INTO ev VALUES {vals}")
    return c


# ---------- ring bounding ----------

def test_ring_bounds_and_order():
    r = TimelineRing(capacity=8, name="t_bounds")
    for i in range(20):
        r.record(f"e{i}", "read", float(i), float(i) + 0.5)
    assert len(r) == 8
    assert r.recorded == 20
    assert r.dropped == 12
    evs = r.events()
    # oldest-first: the retained window is the last 8 records
    assert [e.name for e in evs] == [f"e{i}" for i in range(12, 20)]


def test_ring_concurrent_writers_stay_bounded():
    """Many threads hammering one small ring: the bound holds, every
    retained slot is a complete Event, and the total count equals the
    sum of writes (the ring lock is sanitizer-tracked, so the
    concurrency analyzer sees this interleaving too)."""
    r = TimelineRing(capacity=64, name="t_conc")
    per_thread = 500
    n_threads = 8
    start = threading.Barrier(n_threads)

    def writer(k):
        start.wait()
        for i in range(per_thread):
            r.record(f"w{k}.{i}", "read", float(i), float(i) + 1.0,
                     trace_id=k, args={"i": i})

    threads = [threading.Thread(target=writer, args=(k,))
               for k in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert r.recorded == per_thread * n_threads
    assert r.dropped == per_thread * n_threads - 64
    evs = r.events()
    assert len(evs) == 64
    for e in evs:
        assert isinstance(e, Event)
        assert e.end > e.start
        assert e.args["i"] >= 0


def test_ring_clear():
    r = TimelineRing(capacity=4, name="t_clear")
    r.record("a", "read", 0.0, 1.0)
    r.clear()
    assert len(r) == 0 and r.recorded == 0 and r.events() == []


# ---------- gating ----------

def test_disabled_ring_records_nothing(monkeypatch):
    monkeypatch.delenv("YDB_TPU_TIMELINE", raising=False)
    prev = timeline.TIMELINE_FORCE
    timeline.TIMELINE_FORCE = None
    try:
        assert not timeline.timeline_enabled()
        before = timeline.RING.recorded
        timeline.record("x", "read", 0.0, 1.0)
        with timeline.event("y", "decode"):
            pass
        assert timeline.RING.recorded == before
        timeline.TIMELINE_FORCE = False
        monkeypatch.setenv("YDB_TPU_TIMELINE", "1")
        assert not timeline.timeline_enabled()  # FORCE wins over env
    finally:
        timeline.TIMELINE_FORCE = prev


def test_profiled_statement_records_nothing_while_disabled(cluster):
    """A whole profiled statement crosses every record site (blob read,
    decode, the stage timers): with the timeline off the ring must not
    move, and the same statement with it on does record, so the sites
    are on its path."""
    s = cluster.session()
    q = "SELECT id, sum(v) AS sv FROM ev GROUP BY id ORDER BY id"
    prev = timeline.TIMELINE_FORCE
    timeline.TIMELINE_FORCE = False
    try:
        s.execute(q)  # warm: compile + cache fill
        before = timeline.RING.recorded
        s.execute(q)
        assert s.last_profile is not None  # it was profiled
        assert timeline.RING.recorded == before
        timeline.TIMELINE_FORCE = True
        s.execute(q)
        assert timeline.RING.recorded > before
    finally:
        timeline.TIMELINE_FORCE = prev
        timeline.RING.clear()


def test_env_enables(monkeypatch):
    prev = timeline.TIMELINE_FORCE
    timeline.TIMELINE_FORCE = None
    try:
        monkeypatch.setenv("YDB_TPU_TIMELINE", "1")
        assert timeline.timeline_enabled()
        monkeypatch.setenv("YDB_TPU_TIMELINE", "off")
        assert not timeline.timeline_enabled()
    finally:
        timeline.TIMELINE_FORCE = prev


# ---------- interval math ----------

def test_interval_math():
    assert merge_intervals([(0, 1), (2, 3), (0.5, 2.5)]) == [(0, 3)]
    assert union_seconds([(0, 1), (2, 3)]) == 2
    assert intersect_seconds([(0, 2)], [(1, 3)]) == 1
    assert intersect_seconds([(0, 1)], [(2, 3)]) == 0


def test_occupancy_serial_two_stage():
    """read [0,1) then compute [1,2): fractions 0.5 each, zero
    overlap (a fully serialized pipeline)."""
    evs = [Event("r", "read", 0.0, 1.0, 1, 1, {}),
           Event("c", "compute", 1.0, 2.0, 1, 1, {})]
    occ = occupancy_from_events(evs)
    assert occ["wall_seconds"] == pytest.approx(2.0)
    assert occ["busy"]["read"] == pytest.approx(1.0)
    assert occ["busy"]["compute"] == pytest.approx(1.0)
    assert occ["fraction"]["read"] == pytest.approx(0.5)
    assert occ["overlap"]["compute|read"] == 0.0
    assert occ["overlap"]["movement|compute"] == 0.0


def test_occupancy_overlapping_two_stage():
    """read [0,2), compute [1,3): 1s of overlap over min(2,2) = 0.5;
    two overlapping read intervals union (no double count)."""
    evs = [Event("r1", "read", 0.0, 1.5, 1, 1, {}),
           Event("r2", "read", 1.0, 2.0, 2, 1, {}),
           Event("c", "compute", 1.0, 3.0, 3, 1, {})]
    occ = occupancy_from_events(evs)
    assert occ["busy"]["read"] == pytest.approx(2.0)
    assert occ["overlap"]["compute|read"] == pytest.approx(0.5)
    assert occ["overlap"]["movement|compute"] == pytest.approx(0.5)
    # explicit wall overrides the observed extent
    occ = occupancy_from_events(evs, wall=4.0)
    assert occ["fraction"]["read"] == pytest.approx(0.5)


def test_occupancy_ignores_span_category():
    evs = [Event("query", "span", 0.0, 10.0, 1, 1, {}),
           Event("r", "read", 0.0, 1.0, 1, 1, {})]
    occ = occupancy_from_events(evs)
    assert "span" not in occ["busy"]
    assert occ["wall_seconds"] == pytest.approx(1.0)


# ---------- Chrome trace export ----------

def test_chrome_trace_schema_round_trip():
    r = TimelineRing(capacity=16, name="t_chrome")
    r.record("stage.read", "read", 1.0, 2.0, trace_id=7,
             args={"bytes": 10})
    r.record("plan.dispatch", "dispatch", 2.0, 2.5)
    trace = json.loads(json.dumps(export_chrome_trace(ring=r)))
    assert trace["displayTimeUnit"] == "ms"
    evs = trace["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    xs = [e for e in evs if e["ph"] == "X"]
    assert len(xs) == 2
    assert meta and all(e["name"] == "thread_name" and
                        "name" in e["args"] for e in meta)
    for e in xs:
        # the trace_event contract Perfetto/chrome://tracing require
        assert set(e) >= {"name", "cat", "ph", "ts", "dur", "pid",
                          "tid"}
        assert isinstance(e["ts"], (int, float))
        assert e["dur"] >= 0
    read = next(e for e in xs if e["name"] == "stage.read")
    assert read["args"]["trace_id"] == 7
    assert read["args"]["bytes"] == 10
    assert read["dur"] == pytest.approx(1e6)  # 1s in µs


# ---------- end-to-end: warm query ----------

def test_warm_query_busy_matches_stage_seconds(forced_timeline,
                                               cluster):
    s = cluster.session()
    q = "SELECT id, sum(v) AS sv FROM ev GROUP BY id ORDER BY id"
    s.execute(q)  # warm: compile + cache fill
    forced_timeline.clear()
    s.execute(q)
    p = s.last_profile
    assert p is not None and p.stage_occupancy
    # every stage charge funnels through StageTimer.add, which records
    # the identical interval — so the per-stage event SUMS equal the
    # EXPLAIN ANALYZE stage seconds (within 10%, per acceptance)
    evs = [e for e in forced_timeline.events()
           if e.trace_id == p.trace_id]
    assert evs, "no ring events attributed to the query"
    for stage in STAGE_KEYS:   # what StageTimer charged, not the fold
        total = p.stages[stage]
        if total <= 0:
            continue
        ev_sum = sum(e.end - e.start for e in evs if e.cat == stage)
        assert ev_sum == pytest.approx(total, rel=0.1), stage
    occ = p.stage_occupancy
    assert 0 < occ["wall_seconds"] <= (p.seconds or 1.0) * 1.1
    # the staged scan path must report the movement-vs-compute
    # overlap coefficient (the serialized-pipeline detector)
    assert "movement|compute" in occ["overlap"]
    for v in occ["overlap"].values():
        assert 0.0 <= v <= 1.0
    # blob read + decode byte movement was accounted
    mv = timeline.movement_snapshot()
    assert mv.get("blob_read_bytes", 0) > 0
    assert mv.get("decoded_bytes", 0) > 0


def test_explain_analyze_prints_occupancy(forced_timeline, cluster):
    s = cluster.session()
    text = s.execute("EXPLAIN ANALYZE SELECT sum(v) AS sv FROM ev")
    assert "occupancy:" in str(text)


def test_viewer_timeline_endpoint(forced_timeline, cluster):
    from ydb_tpu.obs.viewer import Viewer

    s = cluster.session()
    s.execute("SELECT sum(v) AS sv FROM ev")
    v = Viewer(cluster).start()
    try:
        body, ctype = v.render("/viewer/json/timeline", {})
        out = json.loads(body)
        assert out["enabled"] is True
        assert out["events"] > 0
        assert "categories" in out and "movement_bytes" in out
        assert "active_queries" in out
        body, _ = v.render("/viewer/json/timeline", {"trace": ["1"]})
        trace = json.loads(body)
        assert trace["traceEvents"]
        assert any(e["ph"] == "X" for e in trace["traceEvents"])
    finally:
        v.stop()


# ---------- conveyor queue telemetry ----------

def test_conveyor_queue_stats():
    from ydb_tpu.runtime.conveyor import Conveyor

    cv = Conveyor(workers=2)
    try:
        hs = [cv.submit("scan", lambda: 1) for _ in range(6)]
        for h in hs:
            assert h.wait(5) == 1
        st = cv.queue_stats()
        assert st["submitted"] == 6
        assert st["completed"] == 6
        assert st["rejected"] == 0
        assert st["depth"] == 0
        assert st["workers"] == 2
        waits = st["waits"].get("scan", [])
        assert waits and all(w >= 0 for w in waits)
        # wait samples + high-water mark drain with the snapshot
        st2 = cv.queue_stats()
        assert st2["waits"] == {}
        assert st2["max_depth"] == 0
    finally:
        cv.shutdown()


def test_run_background_exports_conveyor_and_movement(cluster):
    c = cluster
    s = c.session()
    s.execute("SELECT sum(v) AS sv FROM ev")
    c.run_background()
    snap = c.counters.snapshot()
    conveyor_keys = [k for k in snap if "component=conveyor" in k]
    assert any(k.startswith("submitted") for k in conveyor_keys)
    assert any(k.startswith("completed") for k in conveyor_keys)
    movement_keys = [k for k in snap if "component=movement" in k]
    assert any(k.startswith("blob_read_bytes") for k in movement_keys)
    prom = c.counters.encode_prometheus()
    assert 'component="movement"' in prom
    assert 'component="conveyor"' in prom


# ---------- live query introspection ----------

def test_sys_active_queries_shows_then_clears(cluster):
    s = cluster.session()
    # a statement reading sys_active_queries observes ITSELF in
    # flight (registered before planning, still running while the
    # view materializes)
    out = s.execute("SELECT query_text, stage, elapsed_seconds "
                    "FROM sys_active_queries")
    assert out.num_rows == 1
    # ...and the registry clears once execution finishes
    assert cluster.active_query_snapshot() == []
    out = s.execute("SELECT query_text FROM sys_active_queries")
    assert out.num_rows == 1  # only itself again, not a leak


def test_active_registry_clears_on_failure(cluster):
    s = cluster.session()
    with pytest.raises(Exception):
        s.execute("SELECT * FROM no_such_table")
    assert cluster.active_query_snapshot() == []


def test_slow_query_watchdog_fires(cluster, monkeypatch):
    import time

    monkeypatch.setenv("YDB_TPU_SLOW_QUERY_SECONDS", "0.5")
    ts = TraceSession(pattern="query.slow").attach()
    try:
        tok = cluster._register_active("SELECT slow",
                                       time.monotonic() - 2.0)
        try:
            assert cluster.check_slow_queries() == 1
            # latched: the same statement does not re-fire
            assert cluster.check_slow_queries() == 0
        finally:
            cluster._unregister_active(tok)
        assert ts.counts["query.slow"] == 1
        name, params = ts.events[0]
        assert params["elapsed"] >= 0.5
        assert params["sql"] == "SELECT slow"
    finally:
        ts.detach()


def test_fast_query_does_not_fire_watchdog(cluster, monkeypatch):
    monkeypatch.setenv("YDB_TPU_SLOW_QUERY_SECONDS", "30")
    s = cluster.session()
    ts = TraceSession(pattern="query.slow").attach()
    try:
        s.execute("SELECT sum(v) AS sv FROM ev")
        assert cluster.check_slow_queries() == 0
        assert ts.counts["query.slow"] == 0
    finally:
        ts.detach()


# ---------- failed statements land in the profile ring ----------

def test_failed_query_recorded_with_error_flag(cluster):
    s = cluster.session()
    n_before = len(cluster.profiles.recent())
    with pytest.raises(Exception):
        s.execute("SELECT * FROM no_such_table")
    recent = cluster.profiles.recent()
    assert len(recent) == n_before + 1
    p = recent[-1]
    assert p.error == 1
    assert "no_such_table" in p.sql
    # ...and the sys view exposes the flag
    out = s.execute("SELECT query_text, error FROM sys_top_queries "
                    "WHERE error = 1")
    assert out.num_rows >= 1


def test_ok_query_has_error_zero(cluster):
    s = cluster.session()
    s.execute("SELECT sum(v) AS sv FROM ev")
    assert s.last_profile.error == 0
