"""Resource-lifecycle analyzer (R001-R008) + leak sanitizer: firing
fixtures per rule, drain tests per tracked handle kind, regression
tests for the true findings the pass surfaced, and the
100-concurrent-session deadline soak where every gauge drains to 0."""

import ast
import textwrap
import threading
import time
from pathlib import Path

import numpy as np
import pytest

from ydb_tpu.analysis import leaksan, lifecycle


def _codes(src, filename="fix.py"):
    return [f.code for f in
            lifecycle.check_source(textwrap.dedent(src), filename)]


@pytest.fixture(autouse=True)
def _leaksan_off_after():
    """Every test leaves the sanitizer unpinned and empty."""
    yield
    leaksan.set_force(None)
    leaksan.reset()


# ---------- static rules: one firing fixture per R-rule ----------

def test_r000_syntax_error():
    assert _codes("def f(:\n") == ["R000"]


def test_r001_release_never_in_finally():
    src = """
    class C:
        def f(self):
            self.lock.acquire()
            self.work()
            self.lock.release()
    """
    assert "R001" in _codes(src)


def test_r001_clean_with_finally():
    src = """
    class C:
        def f(self):
            self.lock.acquire()
            try:
                self.work()
            finally:
                self.lock.release()
    """
    assert _codes(src) == []


def test_r001_skips_cross_function_protocol():
    # acquire with NO release anywhere in the function is a protocol
    # handing ownership elsewhere (leaksan's beat), not a finding
    src = """
    class C:
        def f(self):
            self.lock.acquire()
            return self.handle()
    """
    assert _codes(src) == []


def test_r002_generator_flight_without_finally():
    src = """
    class C:
        def gen(self, key, ev):
            self._flights[key] = ev
            yield key
    """
    assert "R002" in _codes(src)


def test_r002_clean_flight_popped_in_finally():
    src = """
    class C:
        def gen(self, key, ev):
            self._flights[key] = ev
            try:
                yield key
            finally:
                self._flights.pop(key, None)
    """
    assert _codes(src) == []


def test_r002_generator_owned_acquire_across_yield():
    src = """
    class C:
        def gen(self):
            self.lock.acquire()
            yield 1
            self.lock.release()
    """
    assert "R002" in _codes(src)


def test_r003_gauge_decrement_not_in_finally():
    src = """
    class C:
        def f(self):
            self.inflight += 1
            self.work()
            self.inflight -= 1
    """
    assert "R003" in _codes(src)


def test_r003_clean_decrement_in_finally():
    src = """
    class C:
        def f(self):
            self.inflight += 1
            try:
                self.work()
            finally:
                self.inflight -= 1
    """
    assert _codes(src) == []


def test_r003_skips_non_unit_accounting():
    # += nbytes / -= nbytes is byte accounting (blockcache tee), not a
    # paired gauge — constant-1 pairs only
    src = """
    class C:
        def f(self, nbytes):
            self.total += nbytes
            self.work()
            self.total -= nbytes
    """
    assert _codes(src) == []


def test_r004_swallowed_cancellation():
    src = """
    class C:
        def f(self):
            try:
                self.run()
            except StatementCancelled:
                pass
    """
    assert "R004" in _codes(src)


def test_r004_clean_reraise_or_record():
    reraise = """
    class C:
        def f(self):
            try:
                self.run()
            except StatementCancelled:
                self.cleanup()
                raise
    """
    record = """
    class C:
        def f(self):
            try:
                self.run()
            except ConveyorTimeout as e:
                self.result.error = e
    """
    assert _codes(reraise) == []
    assert _codes(record) == []


def test_r005_stoppable_member_unreachable():
    src = """
    import threading

    class Worker:
        def __init__(self):
            self.t = threading.Thread(target=self.run)
        def run(self):
            pass
        def stop(self):
            self.t.join()

    class Holder:
        def __init__(self):
            self.w = Worker()
    """
    assert "R005" in _codes(src)


def test_r005_clean_stop_path_reaches_member():
    src = """
    import threading

    class Worker:
        def __init__(self):
            self.t = threading.Thread(target=self.run)
        def run(self):
            pass
        def stop(self):
            self.t.join()

    class Holder:
        def __init__(self):
            self.w = Worker()
        def stop(self):
            self.w.stop()
    """
    assert _codes(src) == []


def test_r006_broker_acquire_without_deadline():
    src = """
    class C:
        def f(self):
            self.broker.acquire("scan")
            try:
                self.work()
            finally:
                self.broker.release("scan")
    """
    assert "R006" in _codes(src)


def test_r006_clean_with_deadline():
    src = """
    class C:
        def f(self, dl):
            self.broker.acquire("scan", deadline=dl)
            try:
                self.work()
            finally:
                self.broker.release("scan")
    """
    assert _codes(src) == []


def test_r007_grow_only_container():
    src = """
    class C:
        def __init__(self):
            self._cache = {}
        def put(self, k, v):
            self._cache[k] = v
    """
    assert "R007" in _codes(src)


def test_r007_clean_with_removal_or_bound():
    removal = """
    class C:
        def __init__(self):
            self._cache = {}
        def put(self, k, v):
            self._cache[k] = v
        def drop(self, k):
            self._cache.pop(k, None)
    """
    bound = """
    class C:
        def __init__(self):
            self._cache = {}
            self.cap = 8
        def put(self, k, v):
            self._cache[k] = v
            if len(self._cache) > self.cap:
                self.evict()
        def evict(self):
            pass
    """
    assert _codes(removal) == []
    assert _codes(bound) == []


def test_r007_membership_test_is_not_a_bound():
    # dedup against a grow-only set IS the leak shape, not its bound
    src = """
    class C:
        def __init__(self):
            self._seen = set()
        def note(self, k):
            if k in self._seen:
                return
            self._seen.add(k)
    """
    assert "R007" in _codes(src)


def test_r008_flight_crosses_submit_unowned():
    src = """
    class C:
        def f(self, pid):
            self._inflight.add(pid)
            self.conveyor.submit("promote", self.task)
    """
    assert "R008" in _codes(src)


def test_r008_clean_closure_owns_release():
    # the closure IS the ownership continuation across threads: its
    # finally-discard counts as the parent's release
    src = """
    class C:
        def f(self, pid):
            self._inflight.add(pid)

            def task():
                try:
                    self.load(pid)
                finally:
                    self._inflight.discard(pid)

            self.conveyor.submit("promote", task)
    """
    assert _codes(src) == []


def test_pragma_suppression():
    src = """
    class C:
        def __init__(self):
            self._cache = {}
        def put(self, k, v):
            self._cache[k] = v  # ydb-lint: disable=R007
    """
    assert _codes(src) == []


# ---------- leak sanitizer: gate, handles, drain checks ----------

def test_leaksan_disabled_is_free(monkeypatch):
    monkeypatch.delenv("YDB_TPU_LEAKSAN", raising=False)
    leaksan.refresh()
    assert leaksan.track("conveyor.task", "q") is None
    leaksan.close(None)  # None-safe
    assert leaksan.counts() == {}
    leaksan.assert_drained()  # no-op when off


def test_leaksan_scan_tracks_nothing_off_and_drains_armed():
    """A scan crosses the conveyor, stream-morsel and blockcache track
    sites. Off (the production state) it may track no handle; armed,
    handles open during the scan and every one closes once the scan's
    conveyor work completes."""
    from ydb_tpu import dtypes
    from ydb_tpu.engine.blobs import MemBlobStore
    from ydb_tpu.engine.reader import PortionStreamSource
    from ydb_tpu.engine.shard import ColumnShard, ShardConfig
    from ydb_tpu.runtime.conveyor import shared_conveyor, stream_conveyor
    from ydb_tpu.ssa import Agg, AggSpec, GroupByStep
    from ydb_tpu.ssa.program import Program

    schema = dtypes.schema(("id", dtypes.INT64, False),
                           ("v", dtypes.INT64))
    shard = ColumnShard(
        "s1", schema, MemBlobStore(), pk_column="id", upsert=False,
        config=ShardConfig(compact_portion_threshold=10**6,
                           scan_block_rows=256))
    for off in range(4):
        ids = np.arange(off * 300, off * 300 + 300, dtype=np.int64)
        shard.commit([shard.write({"id": ids, "v": ids % 7})])
    prog = Program((GroupByStep(("v",), (
        AggSpec(Agg.COUNT_ALL, None, "n"),
        AggSpec(Agg.SUM, "id", "s"))),))

    def drained():
        stream_conveyor().wait_idle(timeout=10.0)
        shared_conveyor().wait_idle(timeout=10.0)
        deadline = time.monotonic() + 5.0
        while leaksan.counts() and time.monotonic() < deadline:
            time.sleep(0.005)  # a worker may close its handle post-idle
        return leaksan.counts()

    leaksan.reset()
    assert not leaksan.enabled()
    off_answer = shard.scan(prog)
    assert drained() == {}
    seen = []
    with leaksan.activate():
        src = PortionStreamSource(shard, shard.visible_portions(None))
        it = src.blocks(256)
        next(it)
        seen = leaksan.live()  # flights admitted ahead of the consumer
        for _ in it:
            pass
        armed_answer = shard.scan(prog)
        assert drained() == {}
    assert seen, "the armed scan tracked no handle: dead track sites"
    for name in off_answer.cols:
        for a, b in zip(off_answer.cols[name], armed_answer.cols[name]):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_leaksan_track_close_and_stacks():
    with leaksan.activate():
        h = leaksan.track("broker.slot", "scan", owner="q1")
        assert leaksan.counts() == {"broker.slot": 1}
        assert "broker.slot[scan]" in h.describe()
        assert "test_lifecycle" in h.describe()  # creation site kept
        h.close()
        h.close()  # idempotent
        assert leaksan.counts() == {}


def test_leaksan_assert_drained_names_leaks():
    with leaksan.activate():
        leaksan.track("conveyor.task", "compaction")
        with pytest.raises(leaksan.LeakError) as ei:
            leaksan.assert_drained(where="test")
        assert "conveyor.task[compaction]" in str(ei.value)
        leaksan.reset()


def test_leaksan_owner_scoped_drain():
    with leaksan.activate():
        a = leaksan.track("session.active", "SELECT 1", owner=7)
        leaksan.track("session.active", "SELECT 2", owner=8)
        leaksan.close(a)
        leaksan.assert_drained(owner=7)  # 7 drained; 8 still open
        with pytest.raises(leaksan.LeakError):
            leaksan.assert_drained(owner=8)
        leaksan.reset()


# ---------- one drain test per tracked kind ----------

def test_kind_conveyor_task():
    from ydb_tpu.runtime.conveyor import Conveyor

    with leaksan.activate():
        cv = Conveyor(workers=1)
        try:
            gate = threading.Event()
            h = cv.submit("bg", gate.wait, 5.0)
            deadline = time.monotonic() + 5.0
            while not leaksan.live("conveyor.task") and \
                    time.monotonic() < deadline:
                time.sleep(0.005)
            assert leaksan.counts() == {"conveyor.task": 1}
            gate.set()
            h.wait(5.0)
            cv.wait_idle(timeout=5.0)
            assert leaksan.counts() == {}
        finally:
            cv.shutdown()


def test_kind_broker_slot():
    from ydb_tpu.runtime.conveyor import ResourceBroker

    with leaksan.activate():
        br = ResourceBroker(quotas={"scan": 2})
        br.acquire("scan")
        br.acquire("scan")
        assert leaksan.counts() == {"broker.slot": 2}
        br.release("scan")
        assert leaksan.counts() == {"broker.slot": 1}
        br.release("scan")
        assert leaksan.counts() == {}


def test_kind_rm_slot():
    from ydb_tpu.kqp.rm import ResourceManager

    with leaksan.activate():
        rm = ResourceManager()
        rm.acquire("q1", slots=1)
        rm.acquire("q1", slots=2)  # regrant: still one handle
        assert leaksan.counts() == {"rm.slot": 1}
        rm.release("q1")
        assert leaksan.counts() == {}


def test_kind_resident_flight():
    from ydb_tpu.engine import resident as resident_mod
    from ydb_tpu.runtime.conveyor import shared_conveyor

    prev = resident_mod.RESIDENT_FORCE
    resident_mod.RESIDENT_FORCE = True
    try:
        with leaksan.activate():
            store = resident_mod.ResidentStore("t", budget=1 << 20)
            gate = threading.Event()

            def loader():
                gate.wait(5.0)
                raise RuntimeError("load failed on purpose")

            assert store.promote_async(1, rows=10, loader=loader)
            deadline = time.monotonic() + 5.0
            while not leaksan.live("resident.flight") and \
                    time.monotonic() < deadline:
                time.sleep(0.005)
            assert leaksan.counts().get("resident.flight") == 1
            gate.set()
            store.drain(timeout=10.0)
            shared_conveyor().wait_idle(timeout=10.0)
            # the failing loader still drains: discard + close live in
            # the task's finally
            assert store.snapshot()["inflight"] == 0
            assert leaksan.counts() == {}
    finally:
        resident_mod.RESIDENT_FORCE = prev


def test_kind_stream_morsel():
    """stream.morsel handles open at flight admission and close at
    retire: live while prefetched flights wait behind the consumer,
    zero once the scan drains."""
    from ydb_tpu import dtypes
    from ydb_tpu.engine.blobs import MemBlobStore
    from ydb_tpu.engine.reader import PortionStreamSource
    from ydb_tpu.engine.shard import ColumnShard, ShardConfig
    from ydb_tpu.runtime.conveyor import stream_conveyor

    schema = dtypes.schema(("id", dtypes.INT64, False),
                           ("v", dtypes.INT64))
    with leaksan.activate():
        shard = ColumnShard(
            "s1", schema, MemBlobStore(), pk_column="id",
            upsert=False,
            config=ShardConfig(compact_portion_threshold=10**6))
        for off in range(6):
            base = off * 200
            wid = shard.write({
                "id": np.arange(base, base + 200, dtype=np.int64),
                "v": np.arange(base, base + 200, dtype=np.int64)})
            shard.commit([wid])
        src = PortionStreamSource(shard,
                                  shard.visible_portions(None))
        it = src.blocks(64)
        next(it)  # later morsels are admitted ahead, uncollected
        assert leaksan.live("stream.morsel")
        for _ in it:
            pass
        deadline = time.monotonic() + 5.0
        while leaksan.live("stream.morsel") and \
                time.monotonic() < deadline:
            time.sleep(0.005)
        assert leaksan.live("stream.morsel") == []
        stream_conveyor().wait_idle(timeout=10.0)
        while leaksan.counts() and time.monotonic() < deadline:
            time.sleep(0.005)
        assert leaksan.counts() == {}


class _FakeCol:
    def __init__(self):
        self.data = np.zeros(4, dtype=np.int64)
        self.validity = np.ones(4, dtype=bool)


class _FakeBlock:
    def __init__(self):
        self.columns = {"c": _FakeCol()}


def test_kind_blockcache_flight():
    from ydb_tpu.engine.blockcache import DeviceBlockCache

    with leaksan.activate():
        cache = DeviceBlockCache(budget=1 << 20)
        blocks = [_FakeBlock(), _FakeBlock()]
        g = cache.stream("k1", lambda: iter(blocks))
        next(g)  # first next registers the fill flight
        assert leaksan.counts() == {"blockcache.flight": 1}
        g.close()  # abandoned stream: the finally closes the flight
        assert leaksan.counts() == {}


def test_kind_session_active():
    from ydb_tpu.kqp.session import Cluster

    with leaksan.activate():
        c = Cluster()
        tok = c._register_active("SELECT 1", time.monotonic())
        assert leaksan.counts() == {"session.active": 1}
        with pytest.raises(leaksan.LeakError):
            leaksan.assert_drained(owner=tok)
        c._unregister_active(tok)
        assert leaksan.counts() == {}
        c.stop()


def test_kind_dq_spill():
    from ydb_tpu.dq.spilling import Spiller

    with leaksan.activate():
        sp = Spiller(mem_quota_bytes=0, prefix="spill/t9")
        a = sp.put({"x": np.arange(8)})
        sp.put({"x": np.arange(8)})
        assert leaksan.counts() == {"dq.spill": 2}
        sp.get(a)  # consumed: blob deleted, handle closed
        assert leaksan.counts() == {"dq.spill": 1}
        sp.close()  # aborted query: leftover blobs dropped
        assert leaksan.counts() == {}
        assert sp.store.list("spill/t9") == []
        sp.close()  # idempotent


# ---------- regression tests for the true findings fixed ----------

SESSION_PY = Path(lifecycle.__file__).parents[1] / "kqp" / "session.py"
STATS_PY = Path(lifecycle.__file__).parents[1] / "stats" / \
    "aggregator.py"


def _strip_method(src: str, cls_name: str, meth: str) -> str:
    """Remove one method body from a class, textually by AST lines."""
    tree = ast.parse(src)
    for st in tree.body:
        if isinstance(st, ast.ClassDef) and st.name == cls_name:
            for sub in st.body:
                if isinstance(sub, (ast.FunctionDef,
                                    ast.AsyncFunctionDef)) and \
                        sub.name == meth:
                    lines = src.splitlines(keepends=True)
                    start = sub.lineno - 1
                    if sub.decorator_list:
                        start = sub.decorator_list[0].lineno - 1
                    del lines[start:sub.end_lineno]
                    return "".join(lines)
    raise AssertionError(f"{cls_name}.{meth} not found")


def test_r005_regression_cluster_without_stop():
    """Pre-fix shape: Cluster held the thread-owning
    StatisticsAggregator with NO stop path at all — R005 must fire on
    the real sources once Cluster.stop is stripped back out, and stay
    quiet with it present."""
    session_src = SESSION_PY.read_text(encoding="utf-8")
    stats_src = STATS_PY.read_text(encoding="utf-8")

    def run(src):
        return [f.code for f in lifecycle.check_sources([
            (src, "session.py", "session"),
            (stats_src, "aggregator.py", "aggregator"),
        ])]

    assert "R005" not in run(session_src)  # fixed tree is clean
    stripped = _strip_method(session_src, "Cluster", "stop")
    assert "R005" in run(stripped)


def test_cluster_stop_drains_and_checks():
    from ydb_tpu.kqp.session import Cluster

    with leaksan.activate():
        c = Cluster()
        s = c.session()
        s.execute("CREATE TABLE kv (k Int64 NOT NULL, v Int64, "
                  "PRIMARY KEY (k))")
        c.tables["kv"].insert({"k": [1, 2], "v": [7, 14]})
        out = s.execute("SELECT SUM(v) AS sv FROM kv")
        assert int(np.asarray(out.cols["sv"][0])[0]) == 21
        c.stop()  # stats thread stopped + global drain check passes
        assert c.stats._thread is None  # stop() joined + cleared it
        assert leaksan.counts() == {}


def test_execute_admission_released_on_unexpected_error():
    """Regression: an exception between workload admission and the
    compute-slot grant used to strand qid in the pool's running set
    forever. Any failure there must release the pool entry."""
    from ydb_tpu.kqp.rm import ResourceManager, WorkloadService
    from ydb_tpu.kqp.session import Cluster

    c = Cluster()
    s = c.session()
    s.execute("CREATE TABLE kv (k Int64 NOT NULL, "
              "PRIMARY KEY (k))")
    c.workload = WorkloadService()
    c.rm = ResourceManager()

    class _Boom(Exception):
        pass

    def boom(*a, **k):
        raise _Boom("rm exploded")

    c.rm.acquire = boom
    with pytest.raises(_Boom):
        s.execute("SELECT k FROM kv")
    assert c.workload.stats()["running"] == 0
    assert c.workload.stats()["queued"] == 0
    c.workload = None
    c.rm = None
    c.stop()


def test_console_on_change_unsubscribe():
    """Regression (R007): ConfigsDispatcher callbacks were append-only
    — a component torn down before its node leaked its callback for
    the dispatcher's lifetime. on_change now returns an unsubscribe."""
    from ydb_tpu.runtime.console import ConfigsDispatcher

    d = ConfigsDispatcher()
    seen = []
    off = d.on_change(seen.append)
    assert len(d._callbacks) == 1
    off()
    assert d._callbacks == []
    off()  # idempotent


def test_interconnect_remove_peer():
    """Regression (R007): the peer map only ever grew — nodes coming
    and going could not be forgotten."""
    from ydb_tpu.runtime.actors import ActorSystem
    from ydb_tpu.runtime.interconnect import Interconnect

    ic = Interconnect(ActorSystem(node=1), listen_port=None)
    ic.add_peer(2, "127.0.0.1", 19999)
    assert 2 in ic.peers
    ic.remove_peer(2)
    assert ic.peers == {}
    ic.remove_peer(2)  # absent: no-op


def test_spiller_close_drops_aborted_blobs():
    """Regression: Spiller had no teardown — a query aborted with
    parked/accumulated sids left spill blobs in the store forever
    (only get() deleted them). GraphHandle.close / ReleaseQuery now
    close every task's spiller."""
    from ydb_tpu.dq.spilling import Spiller
    from ydb_tpu.engine.blobs import MemBlobStore

    store = MemBlobStore()
    sp = Spiller(store=store, mem_quota_bytes=0, prefix="spill/q7")
    sids = [sp.put({"x": np.arange(16)}) for _ in range(3)]
    assert len(store.list("spill/q7")) == 3
    sp.get(sids[0])
    assert len(store.list("spill/q7")) == 2
    sp.close()  # abort path: leftover blobs deleted
    assert store.list("spill/q7") == []


# ---------- the 100-concurrent-session deadline soak ----------

def test_soak_100_sessions_every_3rd_deadline():
    """100 concurrent sessions, every 3rd statement forced past its
    deadline, pool admission + compute-slot planes armed: afterwards
    EVERY tracked gauge drains to zero — registry rows, pool running
    set, rm grants, conveyor tasks, broker slots, leaksan counts."""
    from ydb_tpu.chaos.deadline import StatementCancelled
    from ydb_tpu.kqp.rm import (PoolOverloaded, ResourceManager,
                                WorkloadService)
    from ydb_tpu.kqp.session import Cluster
    from ydb_tpu.runtime.conveyor import shared_conveyor

    with leaksan.activate():
        c = Cluster()
        setup = c.session()
        setup.execute("CREATE TABLE kv (k Int64 NOT NULL, v Int64, "
                      "PRIMARY KEY (k)) WITH (shards = 2)")
        ks = list(range(600))
        c.tables["kv"].insert({"k": ks, "v": [k * 3 for k in ks]})
        c._invalidate_plans()
        setup.execute("SELECT SUM(v) AS sv FROM kv")  # warm plans
        c.workload = WorkloadService()
        c.workload.configure("default", concurrent_limit=16,
                             queue_size=256)
        c.rm = ResourceManager(compute_slots=32)

        ok = [0]
        cancelled = [0]
        failures = []
        lock = threading.Lock()

        def worker(i):
            try:
                s = c.session()
                for j in range(3):
                    stmt = i * 3 + j
                    if stmt % 3 == 2:  # every 3rd past its deadline
                        try:
                            s.execute("SELECT SUM(v) AS sv FROM kv",
                                      timeout=0.0)
                        except (StatementCancelled, PoolOverloaded):
                            with lock:
                                cancelled[0] += 1
                    else:
                        s.execute("SELECT COUNT(*) AS n FROM kv "
                                  "WHERE k < 100")
                        with lock:
                            ok[0] += 1
            except Exception as e:  # noqa: BLE001 - soak must report
                with lock:
                    failures.append(f"session {i}: {e!r}")

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(100)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120.0)
        assert not any(t.is_alive() for t in threads), "soak wedged"
        assert failures == [], failures[:5]
        assert ok[0] == 200 and cancelled[0] == 100

        # every gauge drains to zero
        shared_conveyor().wait_idle(timeout=30.0)
        assert c.active_queries == {}
        assert c.workload.stats()["running"] == 0
        assert c.workload.stats()["queued"] == 0
        assert c.rm.used() == (0, 0)
        qs = shared_conveyor().queue_stats()
        assert qs["depth"] == 0 and qs["active"] == 0
        c.workload = None
        c.rm = None
        c.stop()  # global leaksan drain check runs here
        assert leaksan.counts() == {}, leaksan.counts()
