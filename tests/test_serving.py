"""Multi-tenant front door end-to-end (ydb_tpu/serving): tenant
resolution and weighted shares, per-pool admission seats with typed
shedding and deadline-ordered queues, the tenant column / pool view /
per-tenant SLO gauges on the observability surface, cross-CONNECTION
pgwire batching (two sockets, one device dispatch group), two-tenant
noisy-neighbor isolation under the seeded chaos scenario, and the
1k-connection churn soak draining every serving.* leak handle."""

import pathlib
import threading
import time

import pytest

from test_batching import _armed, _lineitem_cluster, _same_result
from test_pgwire import MiniPgClient
from test_sql import Q1_SQL, Q6_SQL

from ydb_tpu import chaos, serving
from ydb_tpu.analysis import leaksan
from ydb_tpu.api.pgwire import PgWireServer
from ydb_tpu.chaos.deadline import StatementCancelled
from ydb_tpu.kqp.rm import OverloadedError
from ydb_tpu.kqp.session import Cluster
from ydb_tpu.runtime.conveyor import shared_conveyor


@pytest.fixture(autouse=True)
def _chaos_off_after():
    yield
    chaos.clear()
    chaos.CHAOS_FORCE = None


@pytest.fixture(scope="module")
def front():
    """One lineitem cluster behind a front door with every tenant the
    module's tests use, plus a live pgwire listener."""
    c = _lineitem_cluster()
    # the per-tenant caps are the shed boundary under test; park the
    # legacy global valve far out of the way
    c.max_inflight_statements = max(c.max_inflight_statements, 1024)
    reg = serving.TenantRegistry()
    reg.register("gold", weight=3.0, max_inflight=32)
    reg.register("bronze", weight=1.0, max_inflight=16)
    reg.register("noisy", weight=1.0, max_inflight=2, queue_size=2)
    reg.register("victim", weight=2.0, max_inflight=8)
    reg.register("small", weight=0.5, max_inflight=1, queue_size=0)
    reg.bind_principal("gold-token", "gold")
    serving.install(c, reg)
    s = c.session()
    for sql in (Q1_SQL, Q6_SQL):  # warm plan + compile caches
        s.execute(sql)
    srv = PgWireServer(c).start()
    yield c, srv
    srv.stop()
    c.stop()


# ---------------- registry + statement classification ----------------

def test_registry_resolution_order():
    reg = serving.TenantRegistry()
    reg.register("gold", weight=3.0)
    reg.bind_principal("alice", "gold")
    # explicit registered tenant wins
    assert reg.resolve(tenant="gold", principal="bob") == "gold"
    # then the principal binding
    assert reg.resolve(principal="alice") == "gold"
    # unknown names and untagged clients land in the default pool
    assert reg.resolve(tenant="typo") == serving.DEFAULT_TENANT
    assert reg.resolve() == serving.DEFAULT_TENANT
    # an unknown tenant keeps the default pool's entitlements
    assert reg.get("typo").name == serving.DEFAULT_TENANT


def test_weighted_shares_floor():
    reg = serving.TenantRegistry()
    reg.register("big", weight=30.0)
    reg.register("tiny", weight=0.01)
    shares = reg.shares(16)
    assert shares["big"] > shares["tiny"]
    # a tiny weight degrades to trickle, never to zero
    assert shares["tiny"] == 1
    assert shares[serving.DEFAULT_TENANT] >= 1


def test_is_read_statement():
    assert serving.is_read_statement("SELECT 1 FROM t")
    assert serving.is_read_statement("  explain select k from t")
    assert serving.is_read_statement("-- note\nSELECT k FROM t")
    assert serving.is_read_statement("/* hint */ SELECT k FROM t")
    assert not serving.is_read_statement("INSERT INTO t VALUES (1)")
    assert not serving.is_read_statement("CREATE TABLE t (k int64)")
    assert not serving.is_read_statement("BEGIN")
    assert not serving.is_read_statement("-- dangling comment")


# ---------------- the admission plane itself ----------------

def test_front_door_shed_names_pool():
    c = Cluster()
    try:
        reg = serving.TenantRegistry()
        reg.register("small", max_inflight=1, queue_size=0)
        fd = serving.install(c, reg)
        seat = fd.admit("small")
        with pytest.raises(OverloadedError, match="small"):
            fd.admit("small")
        snap = fd.snapshot()["small"]
        assert snap["inflight"] == 1 and snap["shed"] == 1
        # ...while another tenant admits freely: per-pool isolation
        fd.admit("other").release()
        seat.release()
        fd.admit("small").release()
        snap = fd.snapshot()["small"]
        assert snap["inflight"] == 0 and snap["admitted"] == 2
        # the shed/admitted telemetry rides the cluster counters
        keys = [k for k in c.counters.snapshot()
                if "component=serving" in k and "tenant=small" in k]
        assert any(k.startswith("admitted") for k in keys)
        assert any(k.startswith("shed") for k in keys)
    finally:
        c.stop()


def test_edf_orders_queued_admissions():
    c = Cluster()
    try:
        reg = serving.TenantRegistry()
        reg.register("edf", max_inflight=1, queue_size=8)
        fd = serving.install(c, reg)
        seat = fd.admit("edf")
        order = []
        rec = threading.Lock()
        now = time.monotonic()

        def waiter(tag, dl):
            s = fd.admit("edf", deadline_at=dl, timeout=10.0)
            with rec:
                order.append(tag)
            s.release()

        # FIFO arrival far-then-near; EDF grant must invert it
        far = threading.Thread(target=waiter, args=("far", now + 60))
        far.start()
        while fd.snapshot()["edf"]["queued"] < 1:
            time.sleep(0.001)
        near = threading.Thread(target=waiter, args=("near", now + 30))
        near.start()
        while fd.snapshot()["edf"]["queued"] < 2:
            time.sleep(0.001)
        seat.release()
        far.join(10.0)
        near.join(10.0)
        assert order == ["near", "far"]
        # a queued admission whose deadline already passed is shed
        # instead of consuming a grant
        seat = fd.admit("edf")
        with pytest.raises(OverloadedError):
            fd.admit("edf", deadline_at=time.monotonic() - 1.0)
        seat.release()
    finally:
        c.stop()


def test_no_front_door_by_default_installed_counts_default_pool():
    """A cluster serves without a front door (one ``front_door is
    None`` test a statement) until one is installed. Installed with no
    registry, a plain session is the default pool's: the statement is
    admitted, not shed, answers the same, and its seat is released."""
    c = _lineitem_cluster()
    try:
        assert c.front_door is None
        s = c.session()
        want = s.execute(Q6_SQL)
        fd = serving.install(c)
        assert c.front_door is fd
        assert not fd.snapshot().get(
            serving.DEFAULT_TENANT, {}).get("admitted")
        _same_result(s.execute(Q6_SQL), want)
        pool = fd.snapshot()[serving.DEFAULT_TENANT]
        assert pool["admitted"] == 1
        assert pool["shed"] == 0 and pool["inflight"] == 0
    finally:
        c.stop()


def test_session_overload_is_typed_and_named(front):
    c, _ = front
    fd = c.front_door
    blocker = fd.admit("small")  # cap 1, queue 0: next admit sheds
    try:
        s = c.session()
        s.tenant = "small"
        with pytest.raises(OverloadedError, match="small"):
            s.execute(Q6_SQL)
        assert getattr(s.last_profile, "error_reason", None) \
            == "overloaded"
    finally:
        blocker.release()
    # seat released on the error path: the pool recovers
    s2 = c.session()
    s2.tenant = "small"
    assert s2.execute(Q6_SQL).num_rows > 0
    assert fd.snapshot()["small"]["inflight"] == 0


# ---------------- observability surface ----------------

def test_tenant_rides_profile_views_and_gauges(front):
    c, _ = front
    s = c.session()
    s.tenant = "gold"
    out = s.execute(Q1_SQL)
    assert out.num_rows > 0
    assert s.last_profile.tenant == "gold"
    view = s.execute("SELECT tenant FROM sys_top_queries")
    assert "gold" in {v.decode() for v in view.strings("tenant")}
    # a statement reading sys_active_queries observes ITSELF labeled
    live = s.execute("SELECT tenant FROM sys_active_queries")
    assert "gold" in {v.decode() for v in live.strings("tenant")}
    pools = s.execute(
        "SELECT tenant, weight, max_inflight, admitted, shed, "
        "pool_limit, conveyor_workers FROM sys_tenant_pools")
    names = {v.decode() for v in pools.strings("tenant")}
    assert {"default", "gold", "bronze", "noisy", "victim",
            "small"} <= names
    # per-tenant SLO gauges on the prometheus surface
    c.run_background()
    prom = c.counters.encode_prometheus()
    assert 'tenant="gold"' in prom
    assert "query_latency_p99" in prom


# ---------------- protocol fronts ----------------

def test_pgwire_tenant_startup_param(front):
    c, srv = front
    fd = c.front_door
    base = fd.snapshot()["bronze"]["admitted"]
    cl = MiniPgClient(srv.port, startup={"tenant": "bronze"})
    rows, _, tags, errors = cl.query(Q6_SQL)
    cl.close()
    assert not errors and rows
    assert fd.snapshot()["bronze"]["admitted"] > base


def test_pgwire_unknown_tenant_lands_in_default(front):
    c, srv = front
    base = c.front_door.snapshot()["default"]["admitted"]
    cl = MiniPgClient(srv.port, startup={"tenant": "no-such-pool"})
    _, _, _, errors = cl.query(Q6_SQL)
    cl.close()
    assert not errors
    assert c.front_door.snapshot()["default"]["admitted"] > base


def test_cross_connection_pgwire_batching(front):
    """The acceptance bar: the same warm SELECT from two DIFFERENT
    network connections joins ONE batch group (group size >= 2) — the
    window sees the cross-client queue because pgwire reads run
    outside the server's connection-serial lock."""
    c, srv = front
    bt0 = c.batcher.snapshot()
    clients = [MiniPgClient(srv.port) for _ in range(2)]
    results = [None, None]
    errors = [None, None]
    barrier = threading.Barrier(2)

    def work(i):
        try:
            barrier.wait()
            results[i] = clients[i].query(Q1_SQL)
        except Exception as e:  # noqa: BLE001 - surfaced below
            errors[i] = e

    with _armed(c, window_ms=500, max_batch=2):
        threads = [threading.Thread(target=work, args=(i,))
                   for i in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30.0)
    for cl in clients:
        cl.close()
    assert errors == [None, None]
    rows0, _, tags0, errs0 = results[0]
    rows1, _, tags1, errs1 = results[1]
    assert not errs0 and not errs1
    assert rows0 and rows0 == rows1  # same statement, same answer
    snap = c.batcher.snapshot()
    assert snap["batches"] >= bt0["batches"] + 1
    assert snap["batched_statements"] >= bt0["batched_statements"] + 2
    assert snap["max_batch_size"] >= 2


# ---------------- SLO isolation under the chaos scenario ----------------

def test_two_tenant_isolation_noisy_neighbor(front):
    """Tenant 'noisy' deadline-storms and cancel-floods its pool (cap
    2, queue 2) with the seeded noisy_neighbor chaos scenario armed on
    top; tenant 'victim' runs warm Q1 the whole time. The victim's
    answers stay bit-identical, its pool never sheds, its worst-case
    latency stays bounded, the noisy pool DID shed, the faults DID
    fire, and every leak-sanitizer handle drains to zero."""
    c, _ = front
    fd = c.front_door
    scen = chaos.Scenario.from_file(
        str(pathlib.Path(chaos.__file__).parent
            / "noisy_neighbor.json"))

    with leaksan.activate():
        vs = c.session()
        vs.tenant = "victim"
        want = vs.execute(Q1_SQL)

        chaos.CHAOS_FORCE = True
        chaos.install(scen)
        stop = threading.Event()
        rec = threading.Lock()
        stats = {"cancelled": 0, "shed": 0, "other": []}

        def noisy_worker():
            s = c.session()
            s.tenant = "noisy"
            while not stop.is_set():
                try:
                    # the storm: every statement already past deadline
                    s.execute(Q6_SQL, timeout=0.0)
                except StatementCancelled:
                    with rec:
                        stats["cancelled"] += 1
                except OverloadedError:
                    with rec:
                        stats["shed"] += 1
                except Exception as e:  # noqa: BLE001 - surfaced below
                    with rec:
                        stats["other"].append(repr(e)[-200:])
                    return

        storms = [threading.Thread(target=noisy_worker)
                  for _ in range(4)]
        for t in storms:
            t.start()
        lat = []
        try:
            for _ in range(20):
                t0 = time.perf_counter()
                got = vs.execute(Q1_SQL, timeout=30.0)
                lat.append(time.perf_counter() - t0)
                _same_result(got, want)
        finally:
            stop.set()
            for t in storms:
                t.join(20.0)
        snap = chaos.counters_snapshot()
        assert snap["sites"]["serving.admit"]["fired"] > 0
        chaos.clear()
        assert stats["other"] == []
        assert stats["cancelled"] > 0  # the storm really ran
        assert stats["shed"] > 0       # ...and overflowed its own pool
        door = fd.snapshot()
        assert door["noisy"]["shed"] > 0
        assert door["victim"]["shed"] == 0  # isolation by construction
        # worst-case victim latency stays inside a generous SLO while
        # 4 threads hammer the neighbor pool (warm Q1 is ~10ms here;
        # the bound only has to exclude starvation, not jitter)
        assert max(lat) < 5.0
        # the whole storm drains: seats, conns, tasks, flights
        shared_conveyor().wait_idle(timeout=30.0)
        assert not leaksan.counts()


# ---------------- connection-churn leak soak ----------------

def test_connection_churn_soak_drains(front):
    """1k pgwire connects/disconnects (the acceptance soak): every
    serving.conn handle must drain once the sockets close."""
    c, srv = front
    with leaksan.activate():
        held = MiniPgClient(srv.port, startup={"tenant": "gold"})
        # a query roundtrip proves the session loop (and its conn
        # handle) is live — the handshake alone races the handler
        held.query(Q6_SQL)
        assert leaksan.counts().get("serving.conn", 0) >= 1
        churned = [0]
        rec = threading.Lock()

        def churn(n):
            for _ in range(n):
                MiniPgClient(srv.port).close()
                with rec:
                    churned[0] += 1

        threads = [threading.Thread(target=churn, args=(125,))
                   for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120.0)
        assert churned[0] == 1000
        held.close()
        deadline = time.monotonic() + 15.0
        while time.monotonic() < deadline:
            counts = leaksan.counts()
            if not counts.get("serving.conn") \
                    and not counts.get("serving.seat"):
                break
            time.sleep(0.05)
        counts = leaksan.counts()
        assert not counts.get("serving.conn"), counts
        assert not counts.get("serving.seat"), counts


# ---------------- gRPC-style front (skipped without protoc) ----------------

def test_request_proxy_close_drains_sessions():
    """RequestProxy sessions are serving.conn handles; close() must
    drop every server-side session (and join operation threads) so
    Cluster.stop's drain assertion passes."""
    try:
        from ydb_tpu.api import server as api_server
    except Exception as e:  # noqa: BLE001 - protoc-less containers
        pytest.skip(f"api.server unavailable: {e!r}")

    class Ctx:
        def invocation_metadata(self):
            return []

        def abort(self, code, msg):
            raise RuntimeError(msg)

    with leaksan.activate():
        c = Cluster()
        serving.install(c)
        proxy = api_server.RequestProxy(c)
        for _ in range(5):
            proxy.create_session(
                api_server.pb.CreateSessionRequest(), Ctx())
        assert leaksan.counts().get("serving.conn") == 5
        proxy.close()
        assert not leaksan.counts().get("serving.conn")
        c.stop()
