"""HBM-resident column tier tests (engine/resident.py): promotion and
eviction lifecycle, invalidation across compaction/TTL rewrites,
mid-stream resident/host fallback equality, the YDB_TPU_RESIDENT=0 A/B
switch, and the single-flight DeviceBlockCache fill."""

import threading

import numpy as np
import pytest

from ydb_tpu import dtypes
from ydb_tpu.analysis import sanitizer
from ydb_tpu.engine import resident as resident_mod
from ydb_tpu.engine.blobs import MemBlobStore
from ydb_tpu.engine.resident import ResidentStore
from ydb_tpu.engine.shard import ColumnShard, ShardConfig
from ydb_tpu.ssa import Agg, AggSpec, Call, Col, FilterStep, GroupByStep, Op
from ydb_tpu.ssa.program import Program, lit

SCHEMA = dtypes.schema(
    ("id", dtypes.INT64, False),
    ("ts", dtypes.DATE, False),
    ("tag", dtypes.STRING),
    ("val", dtypes.INT64),
)


@pytest.fixture(autouse=True)
def _restore_force():
    yield
    resident_mod.RESIDENT_FORCE = None


def _shard(upsert=False, **cfg):
    return ColumnShard(
        "rshard", SCHEMA, MemBlobStore(),
        pk_column="id", ttl_column="ts", upsert=upsert,
        config=ShardConfig(**cfg) if cfg else None,
    )


def _write(shard, ids, ts=None, vals=None):
    n = len(ids)
    cols = shard.encode_strings({
        "id": np.asarray(ids, dtype=np.int64),
        "ts": np.asarray(ts if ts is not None else [100] * n,
                         dtype=np.int32),
        "tag": [b"x"] * n,
        "val": np.asarray(vals if vals is not None else ids,
                          dtype=np.int64),
    })
    return shard.write(cols)


def _agg_prog():
    return Program((
        GroupByStep(keys=(), aggs=(
            AggSpec(Agg.SUM, "val", "s"),
            AggSpec(Agg.COUNT_ALL, None, "n"),
        )),
    ))


def _sum_n(shard, snap=None):
    out = shard.scan(_agg_prog(), snap)
    return int(out.cols["s"][0][0]), int(out.cols["n"][0][0])


def test_eager_promotion_at_commit():
    resident_mod.RESIDENT_FORCE = True
    shard = _shard()
    shard.commit([_write(shard, list(range(100)))])
    shard.resident.drain()
    snap = shard.resident.snapshot()
    assert snap["portions"] == 1 and snap["promotions"] == 1
    assert snap["bytes"] > 0
    # the FIRST scan is already served from the resident tier
    assert _sum_n(shard) == (sum(range(100)), 100)
    assert shard.resident.hits >= 1 and shard.resident.misses == 0


def test_heat_driven_promotion():
    # commit while the tier is off: nothing promoted eagerly
    resident_mod.RESIDENT_FORCE = False
    shard = _shard()
    shard.commit([_write(shard, list(range(50)))])
    resident_mod.RESIDENT_FORCE = True
    assert shard.resident.snapshot()["portions"] == 0
    # first host-path scan: heat 1, below threshold
    assert _sum_n(shard) == (sum(range(50)), 50)
    shard.resident.drain()
    assert shard.resident.snapshot()["portions"] == 0
    # second scan crosses PROMOTE_HEAT: async promotion via blob loader
    _sum_n(shard)
    shard.resident.drain()
    snap = shard.resident.snapshot()
    assert snap["portions"] == 1 and snap["promotions"] == 1
    hits0 = shard.resident.hits
    assert _sum_n(shard) == (sum(range(50)), 50)
    assert shard.resident.hits > hits0


def test_eviction_order_zskips_then_cold(monkeypatch):
    """Victims: zone-pruned-away portions first, then coldest by
    (heat, LRU tick) — and the budget bounds resident bytes."""
    store = ResidentStore("evict-test", budget=10 ** 9)
    a = np.arange(1000, dtype=np.int64)
    v = np.ones(1000, dtype=bool)
    for pid in (1, 2, 3):
        assert store.promote(pid, 1000, {"c": a}, {"c": v})
    per = store.snapshot()["bytes"] // 3
    # portion 2: zone maps keep pruning it away -> zero resident value
    store.note_pruned(2)
    # portion 1: hottest by access
    store.lookup(1, ("c",))
    store.lookup(1, ("c",))
    store.lookup(3, ("c",))
    # shrink the budget to fit two portions: 2 must go first
    store._budget = per * 2 + 1
    assert store.promote(9, 1000, {"c": a}, {"c": v}) or True
    with store._lock:
        assert 2 not in store._info
    # shrink to one portion: of (1, 3, 9), the coldest goes; 1 stays
    store._budget = per + 1
    store.lookup(1, ("c",))  # force an over-budget evict pass
    with store._lock:
        store._evict_to_budget_locked(store._budget)
        assert 1 in store._info
        assert store._nbytes <= per + 1
    assert store.snapshot()["evictions"] >= 2
    # a portion larger than the whole valve spills, never pins
    store._budget = 10
    assert not store.promote(7, 1000, {"c": a}, {"c": v})
    assert store.snapshot()["spills"] == 1


def test_budget_env_valve(monkeypatch):
    resident_mod.RESIDENT_FORCE = True
    shard = _shard()
    monkeypatch.setenv("YDB_TPU_RESIDENT_BYTES", "0")
    assert not shard.resident.enabled()
    monkeypatch.setenv("YDB_TPU_RESIDENT_BYTES", "1048576")
    assert shard.resident.enabled()
    assert shard.resident.budget() == 1048576
    monkeypatch.setenv("YDB_TPU_RESIDENT_BYTES", "junk")
    assert not shard.resident.enabled()


def test_invalidation_across_compaction_and_gc():
    resident_mod.RESIDENT_FORCE = True
    shard = _shard(compact_portion_threshold=10 ** 9)
    shard.commit([_write(shard, [1, 2, 3], vals=[10, 20, 30])])
    shard.commit([_write(shard, [4], vals=[40])])
    shard.resident.drain()
    assert shard.resident.snapshot()["portions"] == 2
    old_pids = {m.portion_id for m in shard.visible_portions()}
    shard.compact()
    shard.resident.drain()  # compaction output promotes eagerly
    # old portions still resident: old-snapshot readers keep hitting
    # them until GC proves no snapshot can name them
    assert shard.resident.snapshot()["portions"] == 3
    shard.gc_blobs(keep_snap=shard.snap)
    with shard.resident._lock:
        assert not (old_pids & set(shard.resident._info))
    assert shard.resident.snapshot()["invalidations"] >= 2
    # post-GC scans serve the new portion, correct rows
    assert _sum_n(shard) == (100, 4)


def test_no_stale_reads_after_ttl():
    resident_mod.RESIDENT_FORCE = True
    shard = _shard(compact_portion_threshold=10 ** 9)
    shard.commit([_write(shard, [1, 2], ts=[10, 10], vals=[5, 5])])
    shard.commit([_write(shard, [3, 4], ts=[999, 999], vals=[7, 7])])
    shard.resident.drain()
    assert _sum_n(shard) == (24, 4)
    shard.evict_ttl(cutoff=100)
    # resident arrays of the expired portion must not leak into reads
    assert _sum_n(shard) == (14, 2)
    shard.gc_blobs(keep_snap=shard.snap)
    assert _sum_n(shard) == (14, 2)


def test_mid_scan_resident_host_fallback_equality():
    """Some portions resident, some not: the mixed stream must produce
    exactly the all-host results (row order included)."""
    resident_mod.RESIDENT_FORCE = True
    shard = _shard()
    shard.commit([_write(shard, list(range(0, 300)))])      # promoted
    shard.resident.drain()
    resident_mod.RESIDENT_FORCE = False
    shard.commit([_write(shard, list(range(300, 500)))])    # host-only
    shard.commit([_write(shard, list(range(500, 900)))])    # host-only
    resident_mod.RESIDENT_FORCE = True
    shard.commit([_write(shard, list(range(900, 1000)))])   # promoted
    shard.resident.drain()
    assert shard.resident.snapshot()["portions"] == 2
    prog = Program((
        FilterStep(Call(Op.GE, Col("val"), lit(100))),
        GroupByStep(keys=(), aggs=(
            AggSpec(Agg.SUM, "val", "s"),
            AggSpec(Agg.COUNT_ALL, None, "n"),
            AggSpec(Agg.MIN, "id", "lo"),
            AggSpec(Agg.MAX, "id", "hi"),
        )),
    ))
    hits0 = shard.resident.hits
    on = shard.scan(prog)
    assert shard.resident.hits > hits0
    resident_mod.RESIDENT_FORCE = False
    off = shard.scan(prog)
    for name in on.cols:
        a, aok = (np.asarray(x) for x in on.cols[name])
        b, bok = (np.asarray(x) for x in off.cols[name])
        assert np.array_equal(aok, bok)
        assert np.array_equal(np.where(aok, a, 0), np.where(bok, b, 0))


def test_resident_off_bit_identity(monkeypatch):
    """YDB_TPU_RESIDENT=0 restores the pre-tier scan path exactly."""
    outs = {}
    for label, env in (("on", "1"), ("off", "0")):
        monkeypatch.setenv("YDB_TPU_RESIDENT", env)
        shard = _shard()
        shard.commit([_write(shard, list(range(500)))])
        shard.commit([_write(shard, list(range(500, 800)))])
        shard.resident.drain()
        assert shard.resident.enabled() == (env == "1")
        outs[label] = shard.scan(_agg_prog())
    for name in outs["on"].cols:
        a, aok = (np.asarray(x) for x in outs["on"].cols[name])
        b, bok = (np.asarray(x) for x in outs["off"].cols[name])
        assert np.array_equal(aok, bok)
        assert np.array_equal(np.where(aok, a, 0), np.where(bok, b, 0))


def test_upsert_merged_clusters_stay_on_host_path():
    """K-way dedup merges rewrite rows: those clusters must bypass the
    resident tier, and results must match the tier-off scan."""
    resident_mod.RESIDENT_FORCE = True
    shard = _shard(upsert=True)
    shard.commit([_write(shard, [1, 2, 3], vals=[10, 20, 30])])
    shard.commit([_write(shard, [2, 3, 4], vals=[21, 31, 41])])
    shard.resident.drain()
    on = _sum_n(shard)
    resident_mod.RESIDENT_FORCE = False
    assert _sum_n(shard) == on == (10 + 21 + 31 + 41, 4)


def test_resident_span_attribution():
    from ydb_tpu.obs import tracing
    from ydb_tpu.obs.tracing import Tracer

    resident_mod.RESIDENT_FORCE = True
    shard = _shard()
    shard.commit([_write(shard, list(range(100)))])
    shard.resident.drain()
    tr = Tracer()
    root = tr.trace("q")
    with tracing.activate(root):
        shard.scan(_agg_prog())
    root.finish()
    spans = [s for s in tr.spans_for(root.trace_id)
             if s.name == "shard.scan"]
    assert spans and spans[0].attrs["resident_portions"] == 1
    assert spans[0].attrs["resident_rows"] == 100


def test_sysview_and_viewer_surface():
    resident_mod.RESIDENT_FORCE = True
    from ydb_tpu.kqp.session import Cluster

    c = Cluster(n_shards=2)
    s = c.session()
    s.execute("create table t (k bigint not null, v bigint, "
              "primary key (k))")
    s.execute("insert into t values (1, 10)")
    s.execute("insert into t values (2, 20)")
    for sh in c.tables["t"].shards:
        sh.resident.drain()
    r = s.execute("select shard, portions, bytes, promotions "
                  "from sys_resident_store order by shard")
    total = int(np.asarray(r.cols["portions"][0]).sum())
    assert total >= 1
    # aggregate counters ride the maintenance cadence
    c.run_background()
    enc = c.counters.encode_prometheus()
    assert "resident" in enc
    # viewer endpoint renders per-shard rows + totals
    import json as _json

    from ydb_tpu.obs.viewer import Viewer

    v = Viewer(c).start()
    try:
        body, ctype = v.render("/viewer/json/resident", {})
        payload = _json.loads(body)
        assert payload["total"]["portions"] >= 1
        assert ctype.startswith("application/json")
    finally:
        v.stop()


def test_concurrent_scans_during_promotion_tsan():
    """Scans racing heat-driven promotions and commits under the
    sanitizer: no lockset violations, every result exact."""
    with sanitizer.activate():
        resident_mod.RESIDENT_FORCE = True
        shard = _shard()
        shard.commit([_write(shard, list(range(200)))])
        want = (sum(range(200)), 200)
        errs: list = []
        stop = threading.Event()

        def scanner():
            try:
                while not stop.is_set():
                    if _sum_n(shard) != want:
                        errs.append("mismatch")
                        return
            except Exception as e:  # noqa: BLE001
                errs.append(repr(e))

        threads = [threading.Thread(target=scanner) for _ in range(3)]
        for t in threads:
            t.start()
        try:
            # churn: repeated invalidate + re-promotion under scans
            for _ in range(5):
                shard.resident.clear()
                _sum_n(shard)
                _sum_n(shard)
                shard.resident.drain()
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=30)
        assert not errs
        assert shard.resident.snapshot()["portions"] >= 0


def test_blockcache_single_flight():
    """Two concurrent misses on one key: exactly one fill runs; the
    other serves the cached entry after waiting."""
    from ydb_tpu.engine.blockcache import DeviceBlockCache

    class _Col:
        data = np.zeros(64, dtype=np.int64)
        validity = np.ones(64, dtype=bool)

    class _Blk:
        columns = {"c": _Col()}

    cache = DeviceBlockCache(budget=1 << 20)
    fills = []
    gate = threading.Event()
    done: list = []

    def make_blocks():
        fills.append(1)
        gate.wait(10)
        return iter([_Blk()])

    def run():
        done.append(len(list(cache.stream(("k",), make_blocks))))

    threads = [threading.Thread(target=run) for _ in range(4)]
    for t in threads:
        t.start()
    # let every thread reach the flight gate, then release the filler
    import time as _time

    _time.sleep(0.1)
    gate.set()
    for t in threads:
        t.join(timeout=30)
    assert done == [1, 1, 1, 1]
    assert len(fills) == 1  # single flight: one decode for 4 scans
    assert cache.flight_waits >= 1
    assert cache.hits >= 3


def test_blockcache_flight_released_on_abandoned_stream():
    """A filler whose consumer abandons the stream mid-way must still
    release the flight so later scans are not wedged."""
    from ydb_tpu.engine.blockcache import DeviceBlockCache

    class _Col:
        data = np.zeros(8, dtype=np.int64)
        validity = np.ones(8, dtype=bool)

    class _Blk:
        columns = {"c": _Col()}

    cache = DeviceBlockCache(budget=1 << 20)
    g = cache.stream(("k",), lambda: iter([_Blk(), _Blk()]))
    next(g)
    g.close()  # abandon mid-stream
    with cache._lock:
        assert ("k",) not in cache._flights
    # the next scan fills normally (no 30s wait)
    assert len(list(cache.stream(("k",), lambda: iter([_Blk()])))) == 1


def test_bounded_under_sustained_ingest_and_scan(monkeypatch):
    """Sustained ingest+scan stress: resident bytes never exceed the
    valve; spills/evictions absorb the pressure."""
    resident_mod.RESIDENT_FORCE = True
    monkeypatch.setenv("YDB_TPU_RESIDENT_BYTES", str(64 << 10))
    shard = _shard()
    total = 0
    for i in range(12):
        ids = list(range(i * 500, (i + 1) * 500))
        shard.commit([_write(shard, ids)])
        total += len(ids)
        _sum_n(shard)
        shard.resident.drain()
        assert shard.resident.nbytes <= 64 << 10
    snap = shard.resident.snapshot()
    assert snap["evictions"] + snap["spills"] > 0
    assert _sum_n(shard)[1] == total


def test_auto_budget_is_device_wide_not_per_store(monkeypatch):
    """A table has a store per shard and a cluster many tables, all in
    one HBM: an auto-budget store may only grow into what the other
    auto stores on its device leave of the device's share. Explicit
    budgets stay per store."""
    import weakref

    resident_mod.RESIDENT_FORCE = True
    monkeypatch.setattr(resident_mod, "FORCED_BYTES", 1300)
    # a ledger of its own: stores of earlier tests may still be alive
    monkeypatch.setattr(resident_mod, "_STORES", weakref.WeakSet())
    a, b = ResidentStore("dw_a"), ResidentStore("dw_b")
    fixed = ResidentStore("dw_fixed", budget=600)
    assert a.budget() == b.budget() == 1300
    # 50 rows are held at 64 (resident_rows), and the ledger counts the
    # padded bytes: 512 B + 64 B validity
    cols = {"v": np.arange(50, dtype=np.int64)}
    assert a.promote(1, 50, cols, None)
    assert a.nbytes == 576
    assert a.budget() == 1300            # its own bytes do not count
    assert b.budget() == 724             # what a leaves
    assert fixed.budget() == 600
    assert b.promote(1, 50, cols, None) and b.nbytes == 576
    # b is full at 724: a second portion evicts its first, never a's
    assert b.promote(2, 50, cols, None)
    assert b.nbytes == 576 and b.evictions == 1 and a.nbytes == 576
    a.clear()
    assert b.budget() == 1300


def test_budgets_derive_from_the_device_report(monkeypatch):
    """The automatic budgets are shares of the HBM the device reports
    (engine/hbm.py), and nothing where it reports none (CPU)."""
    import weakref

    from ydb_tpu.engine import blockcache, hbm

    monkeypatch.setattr(resident_mod, "_STORES", weakref.WeakSet())
    assert hbm.device_bytes() == 0       # the CPU backend of the tests
    assert resident_mod.default_budget() == 0
    assert blockcache.default_budget() == 0
    monkeypatch.setattr(hbm, "device_bytes", lambda: 16 << 30)
    assert resident_mod.default_budget() == 8 << 30
    assert blockcache.default_budget() == 2 << 30
    assert blockcache.DeviceBlockCache().budget() == 2 << 30
    assert ResidentStore("hbm_auto").budget() == 8 << 30


def test_device_slice_binding_moves_resident_columns():
    """Columns promoted before a mesh binding sit on the default
    device; binding the store moves them to ITS device, so the mesh
    scan computes there (and budgets are per device)."""
    import jax

    devs = jax.devices()
    assert len(devs) >= 2
    resident_mod.RESIDENT_FORCE = True
    st = ResidentStore("slice_move")
    st.promote(7, 8, {"v": np.arange(8, dtype=np.int64)}, None)
    ent = st.lookup(7, ("v",))["v"]
    assert ent.data.devices() == {devs[0]}
    st.set_device_slice(1, devs[1], 1 << 20)
    assert ent.data.devices() == ent.validity.devices() == {devs[1]}
    np.testing.assert_array_equal(np.asarray(ent.data), np.arange(8))


# ---------------- block assembly (resident._assemble) ----------------

ASM_CAP = 1 << 14
ASM_SCHEMA = dtypes.schema(
    ("i4", dtypes.INT32), ("i8", dtypes.INT64), ("u4", dtypes.UINT32),
    ("f8", dtypes.DOUBLE), ("b", dtypes.BOOL),
)
ASM_NAMES = ASM_SCHEMA.names

#: portion lengths of one resident run against ASM_CAP = 2^14 and the
#: granule (2^13): below, on and across both, one to four portions
ASM_RUNS = {
    "one_portion_on_cap": (ASM_CAP,),
    "two_portions_on_cap": (ASM_CAP, ASM_CAP),
    "two_on_the_granule": (1 << 13, 1 << 13),
    "tail_below_the_granule": (100,),
    "one_row": (1,),
    "portion_across_cap": (20_000,),
    "across_the_granule": ((1 << 13) + 1, (1 << 13) - 1, 5),
    "three_across_cap": (16_000, 9_000, 7_777),
    "whole_between_pieces": (5, ASM_CAP, 3),
    "cap_rows_off_the_block_grid": (5, ASM_CAP),
    "four_small_in_one_block": (10, 20, 30, 40),
    "four_mixed": (ASM_CAP, 12_345, ASM_CAP + 1, 4_000),
}


def _asm_portion(rng, rows):
    cols = {
        "i4": rng.integers(-2 ** 31, 2 ** 31, rows).astype(np.int32),
        "i8": rng.integers(-2 ** 62, 2 ** 62, rows).astype(np.int64),
        "u4": rng.integers(0, 2 ** 32, rows).astype(np.uint32),
        "f8": rng.standard_normal(rows),
        "b": rng.random(rows) < 0.5,
    }
    cols["f8"][::7] = -0.0
    cols["f8"][3::11] = np.nan
    valid = {n: rng.random(rows) < 0.8 for n in cols}
    return cols, valid


def _bits(a):
    a = np.asarray(a)
    return a.view(np.uint64) if a.dtype == np.float64 else a


class _Counts:
    resident_blocks_whole = 0
    resident_blocks_assembled = 0


@pytest.mark.parametrize("case", sorted(ASM_RUNS))
def test_assembled_blocks_equal_the_numpy_reference(case):
    """Every block cut from a run of resident portions equals the plain
    reference (the run's true rows concatenated, cut at ``cap``, padded
    with 0 / False) in data, validity and ``length``, bit for bit; a
    portion that fills a block hands over the entry's own arrays."""
    lens = ASM_RUNS[case]
    rng = np.random.default_rng(len(case) * 1000 + sum(lens))
    store = ResidentStore("asm_" + case, budget=1 << 30)
    run, host = [], []
    src = _Counts()
    for pid, rows in enumerate(lens):
        cols, valid = _asm_portion(rng, rows)
        assert store.promote(pid, rows, cols, valid)
        ent = store.lookup(pid, ASM_NAMES)
        held = resident_mod.resident_rows(rows)
        assert all(e.data.shape == e.validity.shape == (held,)
                   for e in ent.values())
        run.append((ent, rows, src))
        host.append((cols, valid))
    total = sum(lens)
    assert store.snapshot()["rows"] == total
    assert store.nbytes == sum(
        resident_mod.resident_rows(r) for r in lens) * (4 + 8 + 4 + 8 + 1
                                                        + 5)
    blocks = list(resident_mod._device_blocks(
        run, ASM_NAMES, ASM_SCHEMA, ASM_CAP, None))
    assert len(blocks) == -(-total // ASM_CAP)
    starts = np.cumsum((0,) + lens)
    whole = 0
    for b, blk in enumerate(blocks):
        lo, hi = b * ASM_CAP, min((b + 1) * ASM_CAP, total)
        assert blk.length.dtype == np.int32 and int(blk.length) == hi - lo
        for n in ASM_NAMES:
            want_d = np.concatenate([c[n] for c, _ in host])[lo:hi]
            want_v = np.concatenate([v[n] for _, v in host])[lo:hi]
            pad = ASM_CAP - (hi - lo)
            want_d = np.concatenate([want_d, np.zeros(pad, want_d.dtype)])
            want_v = np.concatenate([want_v, np.zeros(pad, bool)])
            col = blk.columns[n]
            assert col.data.dtype == want_d.dtype
            assert col.data.shape == col.validity.shape == (ASM_CAP,)
            assert np.array_equal(_bits(col.data), _bits(want_d)), (b, n)
            assert np.array_equal(np.asarray(col.validity), want_v), (b, n)
        own = [p for p in range(len(lens))
               if starts[p] == lo and lens[p] == ASM_CAP]
        if own:
            whole += 1
            for n in ASM_NAMES:
                assert blk.columns[n].data is run[own[0]][0][n].data
                assert blk.columns[n].validity is \
                    run[own[0]][0][n].validity
    assert src.resident_blocks_whole == whole
    assert src.resident_blocks_assembled == len(blocks) - whole


def test_whole_blocks_share_one_length_scalar():
    """The ``length`` of a block one portion fills is made once a
    (device, value), not once a block."""
    store = ResidentStore("asm_len", budget=1 << 30)
    src = _Counts()
    run = []
    for pid in range(3):
        assert store.promote(
            pid, 256, {"v": np.arange(256, dtype=np.int64)}, None)
        run.append((store.lookup(pid, ("v",)), 256, src))
    sch = dtypes.schema(("v", dtypes.INT64))
    blocks = list(resident_mod._device_blocks(run, ("v",), sch, 256, None))
    assert len(blocks) == 3 and src.resident_blocks_whole == 3
    assert blocks[0].length is blocks[1].length is blocks[2].length
    assert int(blocks[0].length) == 256


def _straddling_table(c, name, lens):
    """A one-shard column table whose portions are ``lens`` rows long;
    returns the sum of its ``v``."""
    s = c.session()
    s.execute(f"CREATE TABLE {name} (k int64 NOT NULL, v int64 NOT NULL, "
              "PRIMARY KEY (k)) WITH (store = column, shards = 1)")
    k0 = 0
    for rows in lens:
        k = np.arange(k0, k0 + rows, dtype=np.int64)
        assert c.tables[name].insert({"k": k, "v": k * 3}).committed
        k0 += rows
    for sh in c.tables[name].shards:
        sh.resident.drain()
        assert sh.resident.snapshot()["portions"] == len(lens)
    return int(np.arange(k0, dtype=np.int64).sum()) * 3


def test_assembly_is_one_program_a_straddling_block(monkeypatch):
    """A statement over portions that straddle blocks enters the
    assembly once a straddling block and never for a whole one, says so
    on its ``scan`` span, and a second table whose other portion
    lengths fall into the same length classes builds no program."""
    from ydb_tpu.config import AppConfig
    from ydb_tpu.kqp.session import Cluster
    from ydb_tpu.obs import tracing
    from ydb_tpu.ssa import plan_fuse

    monkeypatch.setattr(plan_fuse, "FUSE_MAX_ROWS", 100)
    resident_mod.RESIDENT_FORCE = True
    calls = []
    real = resident_mod._assemble

    def counted(datas, valids, bounds, *, cap):
        calls.append(len(datas))
        return real(datas, valids, bounds, cap=cap)

    monkeypatch.setattr(resident_mod, "_assemble", counted)
    c = Cluster(config=AppConfig(scan_block_rows=1024,
                                 compact_portion_threshold=10 ** 9))
    try:
        # blocks of 1,024 rows: the first portion fills one; the second
        # and third straddle the next two; the fourth is a short tail
        want = _straddling_table(c, "t1", (1024, 1000, 1048, 500))
        s = c.session()
        for _ in range(2):
            calls.clear()
            r = s.execute("SELECT sum(v) AS x, count(*) AS n FROM t1")
            assert int(np.asarray(r.cols["x"][0])[0]) == want
            assert int(np.asarray(r.cols["n"][0])[0]) == 3572
            scans = [sp for sp in s.last_profile.spans
                     if sp["name"] == "scan"]
            assert len(scans) == 1
            attrs = scans[0]["attrs"]
            assert attrs["resident_blocks_whole"] == 1
            assert attrs["resident_blocks_assembled"] == 3
            assert attrs["resident_portions"] == 4
            assert calls == [2, 1, 1]        # pieces a straddling block
        # other lengths, the same classes (1,024 / 2,048 / 512 rows
        # held) and the same cuts: every assembly program is there
        want = _straddling_table(c, "t2", (1024, 990, 1058, 400))
        src = c.snapshot_db().sources["t2"]
        built = tracing.compile_counts()["built"]
        calls.clear()
        blocks = list(src.blocks(1024, ("v",)))
        assert calls == [2, 1, 1] and len(blocks) == 4
        assert tracing.compile_counts()["built"] == built
        assert sum(int(np.asarray(b.columns["v"].data, dtype=np.int64).sum())
                   for b in blocks) == want
        assert src.resident_blocks_whole == 1
        assert src.resident_blocks_assembled == 3
    finally:
        c.stop()
