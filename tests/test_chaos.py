"""Chaos fault-injection end-to-end (ydb_tpu/chaos): gates and seeded
replay, blob faults healed by RetryPolicy, conveyor delay/drop/worker
death with pool respawn, typed ConveyorTimeout surfaces, bit-identical
fallback chains (fused -> walk, resident -> staged host, mesh ->
single chip), statement deadlines -> StatementCancelled with resource
release, load shedding -> OverloadedError, and the ISSUE acceptance
scenario over TPC-H Q1/Q3/Q6."""

import threading
import time

import numpy as np
import pytest

from ydb_tpu import chaos
from ydb_tpu.chaos.deadline import Deadline, StatementCancelled
from ydb_tpu.chaos.retry import RetryPolicy
from ydb_tpu.kqp.rm import OverloadedError
from ydb_tpu.kqp.session import Cluster
from ydb_tpu.runtime.conveyor import (Conveyor, ConveyorTimeout,
                                      ResourceBroker, shared_conveyor)


@pytest.fixture(autouse=True)
def _chaos_off_after():
    """Every test leaves the subsystem disarmed and gate-closed."""
    yield
    chaos.clear()
    chaos.CHAOS_FORCE = None


def _armed(scenario):
    chaos.CHAOS_FORCE = True
    chaos.install(scenario)


def _same_result(a, b):
    assert a.schema.names == b.schema.names
    assert a.num_rows == b.num_rows
    for name in a.schema.names:
        av, aok = a.cols[name]
        bv, bok = b.cols[name]
        np.testing.assert_array_equal(np.asarray(av), np.asarray(bv),
                                      err_msg=name)
        np.testing.assert_array_equal(np.asarray(aok), np.asarray(bok),
                                      err_msg=f"{name} validity")


def _kv_cluster(n=300):
    c = Cluster()
    s = c.session()
    s.execute("CREATE TABLE kv (k Int64 NOT NULL, v Int64, "
              "PRIMARY KEY (k)) WITH (shards = 2)")
    t = c.tables["kv"]
    for off in range(0, n, n // 3):  # several portions per shard
        ks = list(range(off, min(n, off + n // 3)))
        t.insert({"k": ks, "v": [k * 7 for k in ks]})
    c._invalidate_plans()
    return c, s


AGG_SQL = ("SELECT k % 5 AS g, SUM(v) AS sv, COUNT(*) AS n FROM kv "
           "GROUP BY k % 5 ORDER BY g")


# ---------- gates, determinism, scenario DSL ----------

def test_gate_closed_by_default(monkeypatch):
    monkeypatch.delenv("YDB_TPU_CHAOS", raising=False)
    assert chaos.CHAOS_FORCE is None
    assert not chaos.chaos_enabled()
    with pytest.raises(RuntimeError):
        chaos.install(chaos.Scenario(seed=1, sites={
            "blob.get": {"kind": "io_error"}}))
    assert not chaos.armed()
    assert chaos.hit("blob.get") is None
    assert chaos.counters_snapshot() == {}


def test_force_overrides_env(monkeypatch):
    monkeypatch.setenv("YDB_TPU_CHAOS", "1")
    assert chaos.chaos_enabled()
    chaos.CHAOS_FORCE = False  # in-process pin beats the env
    assert not chaos.chaos_enabled()
    chaos.CHAOS_FORCE = True
    assert chaos.chaos_enabled()


def test_disarmed_statement_counts_no_site_hits():
    """The production state: a statement crosses the blob, conveyor and
    fuse sites with nothing installed, and no site may count a hit. A
    dormant scenario (p = 0 on the same sites) then counts hits, fires
    nothing and answers the same."""
    c, s = _kv_cluster()
    chaos.clear()
    want = s.execute(AGG_SQL)
    c.scan_block_cache.clear()
    _same_result(s.execute(AGG_SQL), want)  # re-read from the blobs
    assert not chaos.counters_snapshot().get("sites")
    _armed(chaos.Scenario(seed=7, sites={
        "blob.get": {"kind": "io_error", "p": 0.0},
        "blob.get_range": {"kind": "io_error", "p": 0.0},
        "conveyor.task": {"kind": "delay", "p": 0.0},
    }))
    c.scan_block_cache.clear()
    _same_result(s.execute(AGG_SQL), want)
    sites = chaos.counters_snapshot()["sites"]
    assert sum(v["hits"] for v in sites.values()) > 0
    assert sum(v["fired"] for v in sites.values()) == 0


def test_seeded_replay_is_deterministic():
    def fire_seq(seed):
        p = chaos.FaultPoint("blob.get", "io_error", p=0.5, seed=seed)
        return [p.roll() is not None for _ in range(20)]

    assert fire_seq(42) == fire_seq(42)
    assert fire_seq(42) != fire_seq(43)  # the seed IS the schedule


def test_sites_draw_independent_streams():
    # two sites under one scenario seed: removing one never shifts the
    # other's fire/skip sequence (per-site rng = seed ^ crc32(name))
    sc_both = chaos.Scenario(seed=9, sites={
        "blob.get": {"kind": "io_error", "p": 0.5},
        "conveyor.task": {"kind": "drop", "p": 0.5}})
    sc_one = chaos.Scenario(seed=9, sites={
        "blob.get": {"kind": "io_error", "p": 0.5}})

    def seq(sc):
        pt = sc.build_points()["blob.get"]
        return [pt.roll() is not None for _ in range(20)]

    assert seq(sc_both) == seq(sc_one)


def test_scenario_json_roundtrip(tmp_path):
    sc = chaos.Scenario(seed=7, sites={
        "blob.get_range": {"kind": "io_error", "p": 0.05},
        "mesh.dispatch": {"kind": "device_lost", "budget": 1},
        "conveyor.task": {"kind": "delay", "p": 0.1,
                          "latency": 0.001}})
    sc2 = chaos.Scenario.from_json(sc.to_json())
    assert sc2.seed == sc.seed and sc2.spec == sc.spec
    f = tmp_path / "scenario.json"
    f.write_text(sc.to_json())
    sc3 = chaos.Scenario.from_file(str(f))
    assert sc3.spec == sc.spec


def test_budget_caps_fires():
    p = chaos.FaultPoint("blob.get", "io_error", p=1.0, budget=3)
    fired = sum(p.roll() is not None for _ in range(10))
    assert fired == 3 and p.stats()["fired"] == 3
    assert p.stats()["hits"] == 10


# ---------- blob faults healed by RetryPolicy ----------

def test_blob_io_error_healed_by_retry():
    c, s = _kv_cluster()
    want = s.execute(AGG_SQL)
    _armed(chaos.Scenario(seed=21, sites={
        "blob.get_range": {"kind": "io_error", "p": 0.6, "budget": 6},
    }))
    got = s.execute(AGG_SQL)
    snap = chaos.counters_snapshot()
    assert snap["sites"]["blob.get_range"]["fired"] > 0  # faults DID fire
    assert sum(snap["retries"].values()) > 0  # ...and retries healed them
    _same_result(got, want)


def test_blob_torn_read_healed_by_refetch():
    # a torn read truncates the chunk: the decode fails, and ONLY a
    # re-fetch (fetch+decode retried as one unit) can heal it
    c, s = _kv_cluster()
    want = s.execute(AGG_SQL)
    _armed(chaos.Scenario(seed=5, sites={
        "blob.get_range": {"kind": "torn", "p": 1.0, "budget": 2},
    }))
    got = s.execute(AGG_SQL)
    assert chaos.counters_snapshot()["sites"]["blob.get_range"][
        "fired"] == 2
    _same_result(got, want)


def test_retry_policy_backoff_and_deadline():
    pol = RetryPolicy(max_attempts=3, base_delay=0.001, jitter=0.0)
    assert pol.delay(0) == pytest.approx(0.001)
    assert pol.delay(1) == pytest.approx(0.002)
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise OSError("transient")
        return "ok"

    assert pol.call(flaky, site="t.flaky") == "ok"
    assert len(calls) == 3
    # a spent deadline stops the retry loop with the LAST error
    calls.clear()
    with pytest.raises(OSError):
        pol.call(flaky, site="t.flaky", deadline=Deadline(0.0))
    assert len(calls) == 1


# ---------- conveyor faults + typed timeout surfaces ----------

def test_conveyor_task_drop_surfaces_error():
    conv = Conveyor(workers=1)
    try:
        _armed(chaos.Scenario(seed=3, sites={
            "conveyor.task": {"kind": "drop", "p": 1.0, "budget": 1}}))
        h = conv.submit("bg", lambda: 42)
        with pytest.raises(chaos.ChaosError):
            h.wait(timeout=5.0)
        chaos.clear()
        assert conv.submit("bg", lambda: 42).wait(timeout=5.0) == 42
    finally:
        conv.shutdown()


def test_conveyor_worker_death_respawns_pool():
    conv = Conveyor(workers=2)
    try:
        _armed(chaos.Scenario(seed=3, sites={
            "conveyor.task": {"kind": "worker_death", "p": 1.0,
                              "budget": 1}}))
        h = conv.submit("bg", lambda: 1)
        with pytest.raises(chaos.ChaosError):
            h.wait(timeout=5.0)
        chaos.clear()
        # the pool self-healed: full worker count, later tasks run
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            if sum(t.is_alive() for t in conv._threads) == 2:
                break
            time.sleep(0.01)
        assert sum(t.is_alive() for t in conv._threads) == 2
        hs = [conv.submit("bg", lambda i=i: i * i) for i in range(4)]
        assert [h.wait(timeout=5.0) for h in hs] == [0, 1, 4, 9]
    finally:
        conv.shutdown()


def test_conveyor_delay_fault_just_slows():
    conv = Conveyor(workers=1)
    try:
        _armed(chaos.Scenario(seed=3, sites={
            "conveyor.task": {"kind": "delay", "p": 1.0, "budget": 1,
                              "latency": 0.02}}))
        t0 = time.perf_counter()
        assert conv.submit("bg", lambda: 7).wait(timeout=5.0) == 7
        assert time.perf_counter() - t0 >= 0.02
    finally:
        conv.shutdown()


def test_task_handle_wait_timeout_typed():
    conv = Conveyor(workers=1)
    ev = threading.Event()
    try:
        h = conv.submit("slowq", ev.wait, 5.0)
        with pytest.raises(ConveyorTimeout, match="slowq"):
            h.wait(timeout=0.01)
    finally:
        ev.set()
        conv.shutdown()


def test_wait_idle_names_busy_queues():
    conv = Conveyor(workers=1)
    ev = threading.Event()
    try:
        conv.submit("resident_promote", ev.wait, 5.0)
        with pytest.raises(ConveyorTimeout, match="resident_promote"):
            conv.wait_idle(timeout=0.05)
    finally:
        ev.set()
        conv.shutdown()


def test_broker_acquire_deadline_rejection():
    conv = Conveyor(workers=1, broker=ResourceBroker(quotas={"q": 1}))
    b = conv.broker
    b.acquire("q")  # holds the only slot
    try:
        with pytest.raises(ConveyorTimeout):
            b.acquire("q", deadline=Deadline(0.0))
        assert conv.queue_stats()["rejected_deadline"] == 1
    finally:
        b.release("q")
        conv.shutdown()


# ---------- bit-identical fallback chains ----------

def test_fused_to_walk_fallback_identical():
    c, s = _kv_cluster()
    want = s.execute(AGG_SQL)
    _armed(chaos.Scenario(seed=11, sites={
        "fuse.trace": {"kind": "io_error", "p": 1.0}}))
    got = s.execute(AGG_SQL)
    snap = chaos.counters_snapshot()
    assert snap["fallbacks"].get("fuse.trace", 0) >= 1
    _same_result(got, want)


def test_resident_to_host_fallback_identical():
    from ydb_tpu import dtypes
    from ydb_tpu.engine import resident as resident_mod
    from ydb_tpu.engine.blobs import MemBlobStore
    from ydb_tpu.engine.shard import ColumnShard
    from ydb_tpu.ssa import Agg, AggSpec, GroupByStep
    from ydb_tpu.ssa.program import Program

    schema = dtypes.schema(("id", dtypes.INT64, False),
                           ("val", dtypes.INT64))
    prev = resident_mod.RESIDENT_FORCE
    resident_mod.RESIDENT_FORCE = True
    try:
        shard = ColumnShard("chres", schema, MemBlobStore(),
                            pk_column="id")
        shard.commit([shard.write({
            "id": np.arange(200, dtype=np.int64),
            "val": np.arange(200, dtype=np.int64) * 3})])
        shard.resident.drain()
        assert shard.resident.snapshot()["portions"] == 1
        prog = Program((GroupByStep(keys=(), aggs=(
            AggSpec(Agg.SUM, "val", "s"),
            AggSpec(Agg.COUNT_ALL, None, "n"))),))
        want = shard.scan(prog)
        hits0 = shard.resident.hits
        shard.scan(prog)
        assert shard.resident.hits > hits0  # baseline IS resident-served
        # injected decode error mid-stream: the scan degrades to the
        # staged-host path for that portion, bit-identical
        _armed(chaos.Scenario(seed=2, sites={
            "resident.lookup": {"kind": "io_error", "p": 1.0}}))
        misses0 = shard.resident.misses
        got = shard.scan(prog)
        assert shard.resident.misses > misses0
        assert chaos.counters_snapshot()["fallbacks"][
            "resident.lookup"] >= 1
        _same_result(got, want)
    finally:
        resident_mod.RESIDENT_FORCE = prev


def test_mesh_device_loss_falls_back_identical():
    from ydb_tpu.plan import executor as ex

    c, s = _kv_cluster()
    want = s.execute(AGG_SQL)
    c.enable_mesh()
    mesh_returns = []
    orig = ex._execute_plan_mesh

    def spy(p, d):
        r = orig(p, d)
        mesh_returns.append(r)
        return r

    _armed(chaos.Scenario(seed=4, sites={
        "mesh.dispatch": {"kind": "device_lost", "budget": 1}}))
    ex._execute_plan_mesh = spy
    try:
        got = s.execute(AGG_SQL)
    finally:
        ex._execute_plan_mesh = orig
    # the mesh WAS tried, lost a device, and the single-chip fallback
    # produced the same rows
    assert mesh_returns and mesh_returns[0] is None
    snap = chaos.counters_snapshot()
    assert snap["sites"]["mesh.dispatch"]["fired"] == 1
    assert snap["fallbacks"].get("mesh.dispatch", 0) >= 1
    _same_result(got, want)
    chaos.clear()
    got2 = s.execute(AGG_SQL)  # budget spent: mesh serves again
    _same_result(got2, want)


# ---------- statement deadlines + load shedding ----------

def test_statement_timeout_cancels_with_typed_reason():
    c, s = _kv_cluster()
    with pytest.raises(StatementCancelled):
        s.execute(AGG_SQL, timeout=0.0)
    p = s.last_profile
    assert p.error == 1 and p.error_reason == "cancelled"
    out = s.execute("SELECT query_text, error, error_reason "
                    "FROM sys_top_queries WHERE error = 1")
    assert out.num_rows >= 1
    reasons = [v.decode() for v in out.strings("error_reason")]
    assert "cancelled" in reasons
    # cancellation released its conveyor work: the pool drains idle
    shared_conveyor().wait_idle(timeout=10.0)
    qs = shared_conveyor().queue_stats()
    assert qs["depth"] == 0 and qs["active"] == 0
    # and the engine still serves (no wedged slot/quota)
    assert s.execute(AGG_SQL, timeout=30.0).num_rows == 5


def test_overload_shedding_typed_error():
    c, s = _kv_cluster()
    c.max_inflight_statements = 1
    tok = c._register_active("sleeper", time.monotonic())
    try:
        with pytest.raises(OverloadedError):
            s.execute(AGG_SQL)
    finally:
        c._unregister_active(tok)
        c.max_inflight_statements = 0
    assert s.last_profile.error == 1
    assert s.last_profile.error_reason == "overloaded"
    out = s.execute("SELECT error_reason FROM sys_top_queries "
                    "WHERE error = 1")
    assert "overloaded" in [v.decode()
                            for v in out.strings("error_reason")]


def test_chaos_admission_overload_site():
    c, s = _kv_cluster()
    _armed(chaos.Scenario(seed=8, sites={
        "session.admit": {"kind": "overload", "p": 1.0, "budget": 1}}))
    with pytest.raises(OverloadedError):
        s.execute(AGG_SQL)
    # budget spent: the next statement is admitted
    assert s.execute(AGG_SQL).num_rows == 5


def test_chaos_counters_exported_by_run_background():
    c, s = _kv_cluster()
    _armed(chaos.Scenario(seed=13, sites={
        "blob.get_range": {"kind": "io_error", "p": 0.5, "budget": 2}}))
    s.execute(AGG_SQL)
    c.run_background()
    snap = c.counters.snapshot()
    fired = [v for k, v in snap.items()
             if k.startswith("fired|") and "component=chaos" in k]
    assert fired and max(fired) > 0


# ---------- the ISSUE acceptance scenario ----------

def _tpch_cluster(sf=0.002):
    """Cluster holding TPC-H lineitem/orders/customer, several
    portions per table (the test_query_profile loader generalized)."""
    from ydb_tpu.scheme.model import type_to_str
    from ydb_tpu.workload import tpch

    data = tpch.TpchData(sf=sf, seed=7)
    c = Cluster()
    s = c.session()
    pks = {"lineitem": "l_orderkey", "orders": "o_orderkey",
           "customer": "c_custkey"}
    for tname, pk in pks.items():
        schema = data.schema(tname)
        cols = ", ".join(f"{f.name} {type_to_str(f.type)}"
                         for f in schema.fields)
        s.execute(f"CREATE TABLE {tname} ({cols}, "
                  f"PRIMARY KEY ({pk})) WITH (shards = 1)")
        src = data.tables[tname]
        t = c.tables[tname]
        n = len(src[pk])
        step = max(1, n // 3)
        for off in range(0, n, step):  # 3 commits -> 3 portions
            arrays = {}
            for f in schema.fields:
                v = src[f.name][off:off + step]
                if f.type.is_string:
                    arrays[f.name] = [
                        bytes(x) for x in data.dicts[f.name].decode(
                            np.asarray(v, dtype=np.int32))]
                else:
                    arrays[f.name] = v
            t.insert(arrays)
    c._invalidate_plans()
    return c, s


def test_acceptance_scenario_q1_q3_q6():
    """The ISSUE's seeded scenario: blob-read faults at p=0.05, one
    injected mesh device loss, and a fifth of statements pushed past
    their deadline — TPC-H Q1/Q3/Q6 complete, surviving queries
    bit-identical to fault-free, every cancelled statement surfacing a
    typed error in sys_top_queries, and no leaked conveyor tasks or
    resident-promotion flights afterwards. The whole scenario runs
    under the leak sanitizer: every seeded fault + cancellation must
    ALSO drain every tracked handle kind to zero (PR 13's invariant)."""
    from test_sql import Q1_SQL, Q3_SQL, Q6_SQL

    from ydb_tpu.analysis import leaksan
    from ydb_tpu.engine import resident as resident_mod

    with leaksan.activate():
        _acceptance_scenario(Q1_SQL, Q3_SQL, Q6_SQL, resident_mod,
                             leaksan)


def _acceptance_scenario(Q1_SQL, Q3_SQL, Q6_SQL, resident_mod,
                         leaksan):
    c, s = _tpch_cluster()
    queries = {"q1": Q1_SQL, "q3": Q3_SQL, "q6": Q6_SQL}
    want = {name: s.execute(sql) for name, sql in queries.items()}
    c.enable_mesh()

    _armed(chaos.Scenario(seed=42, sites={
        "blob.get_range": {"kind": "io_error", "p": 0.05},
        "mesh.dispatch": {"kind": "device_lost", "budget": 1},
    }))
    cancelled = 0
    stmt = 0
    for _round in range(2):
        for name, sql in queries.items():
            stmt += 1
            # cold block cache: chunk reads actually cross the faulted
            # blob surface instead of being served warm
            c.scan_block_cache.clear()
            if stmt % 5 == 0:  # 20% of statements past deadline
                with pytest.raises(StatementCancelled):
                    s.execute(sql, timeout=0.0)
                cancelled += 1
                assert s.last_profile.error_reason == "cancelled"
            else:
                got = s.execute(sql, timeout=60.0)
                _same_result(got, want[name])
    assert cancelled >= 1
    snap = chaos.counters_snapshot()
    assert snap["sites"]["blob.get_range"]["hits"] > 0
    # every cancelled statement surfaces typed in sys_top_queries
    out = s.execute("SELECT error_reason FROM sys_top_queries "
                    "WHERE error = 1")
    reasons = [v.decode() for v in out.strings("error_reason")]
    assert reasons.count("cancelled") >= cancelled
    chaos.clear()
    # nothing leaked: the conveyor drains to zero...
    shared_conveyor().wait_idle(timeout=30.0)
    qs = shared_conveyor().queue_stats()
    assert qs["depth"] == 0 and qs["active"] == 0
    # ...and resident-promotion flights opened after the scenario
    # (heat-driven async promotions on the conveyor) all land or
    # discard — no stranded _inflight entries
    prev_res = resident_mod.RESIDENT_FORCE
    resident_mod.RESIDENT_FORCE = True
    try:
        for _ in range(2):  # cross PROMOTE_HEAT on every portion
            for sql in queries.values():
                s.execute(sql)
        promoted = 0
        for t in c.tables.values():
            for sh in t.shards:
                store = getattr(sh, "resident", None)
                if store is None:
                    continue
                store.drain()
                psnap = store.snapshot()
                promoted += psnap["promotions"]
                assert psnap["inflight"] == 0
        assert promoted > 0
    finally:
        resident_mod.RESIDENT_FORCE = prev_res
    # the closing invariant: after faults, cancellations, device loss
    # and async promotions, EVERY tracked resource kind has drained —
    # conveyor tasks, broker slots, resident/blockcache flights,
    # session registry rows, rm grants, spilled blobs
    shared_conveyor().wait_idle(timeout=30.0)
    assert leaksan.counts() == {}, leaksan.counts()
    leaksan.assert_drained(where="chaos acceptance scenario")
