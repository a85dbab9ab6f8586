"""Cross-query batched serving tier: the kqp/batch.py dispatcher
(window gating, dedup vs stacked dispatch, deadline isolation inside a
batch), the engine/scanshare single-flight staging share, and the
observability surface (profile batching line, sys view columns,
batching counters). Every batched result must be bit-identical to the
serial path, and window=0 must leave the serial path untouched."""

import contextlib
import threading
import time

import numpy as np
import pytest

from ydb_tpu.analysis import leaksan
from ydb_tpu.chaos.deadline import StatementCancelled
from ydb_tpu.engine.scanshare import ScanShare
from ydb_tpu.kqp.batch import BatchDispatcher
from ydb_tpu.kqp.session import Cluster

from test_sql import Q1_SQL, Q6_SQL


# ---------------- fixtures ----------------

def _lineitem_cluster(sf=0.002):
    """Cluster holding TPC-H lineitem, three portions (the test_chaos
    loader trimmed to the one table the batched queries need)."""
    from ydb_tpu.scheme.model import type_to_str
    from ydb_tpu.workload import tpch

    data = tpch.TpchData(sf=sf, seed=7)
    c = Cluster()
    s = c.session()
    schema = data.schema("lineitem")
    cols = ", ".join(f"{f.name} {type_to_str(f.type)}"
                     for f in schema.fields)
    s.execute(f"CREATE TABLE lineitem ({cols}, "
              f"PRIMARY KEY (l_orderkey)) WITH (shards = 1)")
    src = data.tables["lineitem"]
    t = c.tables["lineitem"]
    n = len(src["l_orderkey"])
    step = max(1, n // 3)
    for off in range(0, n, step):
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
    return c


@pytest.fixture(scope="module")
def cluster():
    c = _lineitem_cluster()
    yield c
    c.stop()


@contextlib.contextmanager
def _armed(c, window_ms, max_batch=None):
    bt = c.batcher
    w0, m0 = bt.window_ms, bt.max_batch
    bt.window_ms = float(window_ms)
    if max_batch is not None:
        bt.max_batch = max_batch
    try:
        yield bt
    finally:
        bt.window_ms, bt.max_batch = w0, m0


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


# ---------------- scan share (single-flight staging) ----------------

def test_scanshare_single_flight():
    share = ScanShare()
    staging = threading.Event()   # filler is inside stage_fn
    release = threading.Event()   # attacher is waiting on the flight
    calls = []

    def stage():
        calls.append(threading.get_ident())
        staging.set()
        assert release.wait(5.0)
        return {"block": 42}

    out = [None, None]
    t0 = threading.Thread(
        target=lambda: out.__setitem__(0, share.get_or_stage("k", stage)))
    t0.start()
    assert staging.wait(5.0)
    t1 = threading.Thread(
        target=lambda: out.__setitem__(1, share.get_or_stage("k", stage)))
    t1.start()
    while share.attached == 0:   # t1 registered as an attacher
        time.sleep(0.001)
    release.set()
    t0.join(5.0)
    t1.join(5.0)
    assert len(calls) == 1       # staged exactly once
    assert out[0] is out[1]      # the attacher shares the SAME block
    assert share.snapshot() == {"staged": 1, "attached": 1,
                                "inflight": 0}


def test_scanshare_error_propagates_then_clears():
    share = ScanShare()
    staging = threading.Event()
    release = threading.Event()

    def boom():
        staging.set()
        assert release.wait(5.0)
        raise ValueError("staging fault")

    errs = [None, None]

    def fill():
        try:
            share.get_or_stage("k", boom)
        except ValueError as e:
            errs[0] = e

    def attach():
        try:
            share.get_or_stage("k", boom)
        except ValueError as e:
            errs[1] = e

    t0 = threading.Thread(target=fill)
    t0.start()
    assert staging.wait(5.0)
    t1 = threading.Thread(target=attach)
    t1.start()
    while share.attached == 0:
        time.sleep(0.001)
    release.set()
    t0.join(5.0)
    t1.join(5.0)
    assert errs[0] is not None and errs[1] is errs[0]
    # the failed flight cleared immediately: a retry restages fresh
    assert share.get_or_stage("k", lambda: "ok") == "ok"
    assert share.staged == 1


def test_scanshare_key_none_stages_privately():
    share = ScanShare()
    calls = []
    for _ in range(2):
        share.get_or_stage(None, lambda: calls.append(1))
    assert len(calls) == 2
    assert share.snapshot() == {"staged": 0, "attached": 0,
                                "inflight": 0}


# ---------------- stacked / shared dispatch bit-identity ----------------

def test_run_stacked_slices_match_run_shared():
    """Two members with DIFFERENT staged inputs stack into one vmapped
    dispatch; each slice must be bit-identical to that member's own
    non-donating serial dispatch (and the two members' answers really
    differ, so slicing is observable)."""
    from ydb_tpu.engine.scan import ColumnSource
    from ydb_tpu.plan.executor import Database, _stage_fused_site
    from ydb_tpu.plan.nodes import TableScan
    from ydb_tpu.ssa import plan_fuse
    from ydb_tpu.workload import tpch

    data = tpch.TpchData(sf=0.002, seed=11)
    schema = data.schema("lineitem")
    cols_a = data.tables["lineitem"]
    cols_b = dict(cols_a)
    cols_b["l_quantity"] = np.asarray(cols_a["l_quantity"]) * 2
    db_a = Database(
        sources={"lineitem": ColumnSource(cols_a, schema, data.dicts)},
        dicts=data.dicts)
    db_b = Database(
        sources={"lineitem": ColumnSource(cols_b, schema, data.dicts)},
        dicts=data.dicts)

    plan = TableScan("lineitem", program=tpch.q6_program())
    sig = plan_fuse.plan_signature(plan, db_a)
    assert sig is not None and sig.sites
    # distinct host sources -> distinct member identities (the
    # dispatcher's stacked-routing input), stable per member
    ida = BatchDispatcher._identity_vector(sig, db_a)
    assert ida == BatchDispatcher._identity_vector(sig, db_a)
    assert ida != BatchDispatcher._identity_vector(sig, db_b)

    fused = plan_fuse.build(sig, db_a)
    ia = {s.key: _stage_fused_site(s, db_a, None, donate=False)[0]
          for s in sig.sites}
    ib = {s.key: _stage_fused_site(s, db_b, None, donate=False)[0]
          for s in sig.sites}
    ra, ta = fused.run_shared(ia)
    assert not fused.overflowed(ta)
    rb, tb = fused.run_shared(ib)
    assert not fused.overflowed(tb)
    out, tt = fused.run_stacked([ia, ib])
    assert not fused.overflowed(tt)

    def same(x, y):
        xv, xok = x.to_numpy(), x.validity_numpy()
        yv, yok = y.to_numpy(), y.validity_numpy()
        for name in x.schema.names:
            np.testing.assert_array_equal(xok[name], yok[name])
            np.testing.assert_array_equal(
                np.where(xok[name], xv[name], 0),
                np.where(yok[name], yv[name], 0), err_msg=name)

    same(plan_fuse.slice_member(out, 0), ra)
    same(plan_fuse.slice_member(out, 1), rb)
    # doubled quantities flip Q6's l_quantity filter: the two members'
    # revenues differ, so the slices are genuinely per-member
    assert (ra.to_numpy()["revenue"][0]
            != rb.to_numpy()["revenue"][0])


def test_run_stacked_q3_members_match_serial():
    """The join shape: TPC-H Q3 (semi + inner join, grouped top-10)
    fuses, and three members staged from the same tables stacked into
    ONE vmapped dispatch each equal the serial non-donating dispatch,
    as does a second shared dispatch (the dedup path's result)."""
    from ydb_tpu.engine.scan import ColumnSource
    from ydb_tpu.plan.executor import Database, _stage_fused_site
    from ydb_tpu.ssa import plan_fuse
    from ydb_tpu.workload import tpch

    data = tpch.TpchData(sf=0.002, seed=5)
    db = Database(
        sources={t: ColumnSource(cols, data.schema(t), data.dicts)
                 for t, cols in data.tables.items()},
        dicts=data.dicts)
    sig = plan_fuse.plan_signature(tpch.q3_plan(), db)
    assert sig is not None and len(sig.sites) >= 3
    fused = plan_fuse.build(sig, db)
    inputs = {s.key: _stage_fused_site(s, db, None, donate=False)[0]
              for s in sig.sites}
    serial, totals = fused.run_shared(inputs)
    assert not fused.overflowed(totals)
    assert int(serial.length) > 0
    stacked, totals = fused.run_stacked([inputs] * 3)
    assert not fused.overflowed(totals)
    again, totals = fused.run_shared(inputs)
    assert not fused.overflowed(totals)

    sv, sok = serial.to_numpy(), serial.validity_numpy()
    members = [plan_fuse.slice_member(stacked, i) for i in range(3)]
    for blk in members + [again]:
        bv, bok = blk.to_numpy(), blk.validity_numpy()
        for name in serial.schema.names:
            np.testing.assert_array_equal(sok[name], bok[name])
            np.testing.assert_array_equal(
                np.where(sok[name], sv[name], 0),
                np.where(bok[name], bv[name], 0), err_msg=name)


# ---------------- window gating ----------------

def test_window_zero_is_serial(cluster):
    s = cluster.session()
    assert not cluster.batcher.armed()
    s.execute(Q1_SQL)
    snap = cluster.batcher.snapshot()
    assert snap["batches"] == 0 and snap["solo"] == 0
    assert snap["scan_staged"] == 0
    assert s.last_profile.batch_size == 0
    assert s.last_profile.batch_id == 0


def test_solo_group_returns_to_serial_path(cluster):
    """One statement inside the window is NOT a batch: the caller runs
    the unchanged serial path, with the window wait attributed on the
    dispatch.batch span (visible as batch_size=1 in the profile)."""
    s = cluster.session()
    want = s.execute(Q1_SQL)
    with _armed(cluster, window_ms=30):
        got = s.execute(Q1_SQL)
    _same_result(got, want)
    snap = cluster.batcher.snapshot()
    assert snap["solo"] >= 1 and snap["batched_statements"] == 0
    assert s.last_profile.batch_size == 1
    assert s.last_profile.batch_wait_seconds >= 0.0


# ---------------- batched end-to-end ----------------

def test_batched_results_bit_identical(cluster):
    n = 4
    s0 = cluster.session()
    want = s0.execute(Q1_SQL)
    bt0 = cluster.batcher.snapshot()
    results = [None] * n
    errors = [None] * n
    profiles = [None] * n
    barrier = threading.Barrier(n)

    def work(i):
        s = cluster.session()
        barrier.wait()
        try:
            results[i] = s.execute(Q1_SQL)
            profiles[i] = s.last_profile
        except Exception as e:  # noqa: BLE001 - surfaced below
            errors[i] = e

    with _armed(cluster, window_ms=500, max_batch=n):
        threads = [threading.Thread(target=work, args=(i,))
                   for i in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30.0)
    assert errors == [None] * n
    for r in results:
        _same_result(r, want)
    snap = cluster.batcher.snapshot()
    assert snap["batches"] >= bt0["batches"] + 1
    assert snap["batched_statements"] >= bt0["batched_statements"] + 2
    # same snapshot, same plan -> ONE deduped dispatch, scans staged
    # once and shared by every member
    assert snap["dedup_dispatches"] >= bt0["dedup_dispatches"] + 1
    assert snap["scan_staged"] >= bt0["scan_staged"] + 1
    batched = [p for p in profiles if p is not None and p.batch_size >= 2]
    assert batched, "no member profile recorded a batch seat"
    for p in batched:
        assert p.batch_id > 0
        assert p.shared_scan >= 1
        assert p.batch_execute_seconds >= 0.0

    # counters surface through run_background into the batching group
    cluster.run_background()
    g = cluster.counters.group(component="batching")
    assert g.counter("batches").value == snap["batches"]
    assert g.counter("batched_statements").value \
        == snap["batched_statements"]


def test_distinct_plans_never_share_a_batch(cluster):
    """Q1 and Q6 arrivals in the same window form separate groups (the
    cache key is the plan fingerprint) — both bit-identical to serial."""
    s0 = cluster.session()
    want = {Q1_SQL: s0.execute(Q1_SQL), Q6_SQL: s0.execute(Q6_SQL)}
    sqls = [Q1_SQL, Q6_SQL, Q1_SQL, Q6_SQL]
    results = [None] * len(sqls)
    errors = [None] * len(sqls)
    barrier = threading.Barrier(len(sqls))

    def work(i):
        s = cluster.session()
        barrier.wait()
        try:
            results[i] = s.execute(sqls[i])
        except Exception as e:  # noqa: BLE001 - surfaced below
            errors[i] = e

    with _armed(cluster, window_ms=400):
        threads = [threading.Thread(target=work, args=(i,))
                   for i in range(len(sqls))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30.0)
    assert errors == [None] * len(sqls)
    for i, sql in enumerate(sqls):
        _same_result(results[i], want[sql])


# ---------------- deadline isolation inside a batch ----------------

def test_deadline_cancel_leaves_batchmates_intact(cluster):
    """The chaos scenario: one member's statement deadline fires while
    it waits in the batch. That member alone raises StatementCancelled;
    its batchmates complete with bit-identical results (the leader
    serves the abandoned seat harmlessly)."""
    s0 = cluster.session()
    want = s0.execute(Q1_SQL)
    results = [None] * 3
    errors = [None] * 3
    started = threading.Event()

    def leader():
        s = cluster.session()
        started.set()
        try:
            results[0] = s.execute(Q1_SQL)
        except Exception as e:  # noqa: BLE001 - surfaced below
            errors[0] = e

    def doomed():
        s = cluster.session()
        try:
            results[1] = s.execute(Q1_SQL, timeout=0.12)
        except Exception as e:  # noqa: BLE001 - surfaced below
            errors[1] = e

    def survivor():
        s = cluster.session()
        try:
            results[2] = s.execute(Q1_SQL)
        except Exception as e:  # noqa: BLE001 - surfaced below
            errors[2] = e

    with _armed(cluster, window_ms=500, max_batch=8):
        t0 = threading.Thread(target=leader)
        t0.start()
        assert started.wait(5.0)
        time.sleep(0.05)  # enqueue the doomed member INSIDE the window
        t1 = threading.Thread(target=doomed)
        t1.start()
        t2 = threading.Thread(target=survivor)
        t2.start()
        for t in (t0, t1, t2):
            t.join(30.0)
    assert errors[0] is None and errors[2] is None
    assert isinstance(errors[1], StatementCancelled)
    _same_result(results[0], want)
    _same_result(results[2], want)


# ---------------- leak sanitizer drain ----------------

def test_batched_path_drains_under_leaksan(cluster):
    """Batch seats and staging flights all close — including the seat
    abandoned by a deadline-cancelled member."""
    with leaksan.activate():
        n = 3
        errors = [None] * n
        cancelled = [None] * n
        barrier = threading.Barrier(n)

        def work(i):
            s = cluster.session()
            barrier.wait()
            try:
                s.execute(Q1_SQL,
                          timeout=(0.1 if i == n - 1 else None))
            except StatementCancelled as e:
                cancelled[i] = e  # expected for the doomed member
            except Exception as e:  # noqa: BLE001 - surfaced below
                errors[i] = e

        with _armed(cluster, window_ms=400, max_batch=n):
            threads = [threading.Thread(target=work, args=(i,))
                       for i in range(n)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(30.0)
        assert errors == [None] * n
        counts = leaksan.counts()
        assert counts.get("batch.member", 0) == 0
        assert counts.get("scanshare.flight", 0) == 0
        leaksan.assert_drained(
            kinds=("batch.member", "scanshare.flight"),
            where="after batched burst")


# ---------------- observability surface ----------------

def test_sys_views_expose_batch_columns(cluster):
    s = cluster.session()
    top = s.execute("SELECT batch_id, batch_size, shared_scan "
                    "FROM sys_top_queries")
    assert tuple(top.schema.names) == ("batch_id", "batch_size",
                                       "shared_scan")
    sizes = np.asarray(top.cols["batch_size"][0])
    # earlier tests in this module ran real batches; they show here
    assert top.num_rows > 0 and int(sizes.max()) >= 2
    act = s.execute("SELECT query_text, batch_id, batch_size, "
                    "shared_scan FROM sys_active_queries")
    # the introspection statement itself is live and unbatched
    assert act.num_rows >= 1
    ids = np.asarray(act.cols["batch_id"][0])
    assert int(ids.min()) >= 0


def test_explain_analyze_prints_batching_line(cluster):
    s = cluster.session()
    with _armed(cluster, window_ms=30):
        txt = s.execute("EXPLAIN ANALYZE " + Q1_SQL)
    assert "batching: batch_id=" in txt
    assert "batch_size=1" in txt          # solo group: wait attribution
    assert "wait_seconds=" in txt and "execute_seconds=" in txt
    with _armed(cluster, window_ms=0):
        txt0 = s.execute("EXPLAIN ANALYZE " + Q1_SQL)
    assert "batching:" not in txt0        # disarmed: line absent
