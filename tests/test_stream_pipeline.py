"""Morsel-driven streaming pipeline (engine.stream_sched): bit-identity
against the serialized chain for plain and upsert-merge scans, chaos
blob faults healing without a consumer stall, mid-scan deadline and
abandoned-stream drain to zero under leaksan, and consumer work
stealing when the dedicated stream pool is saturated."""

import threading
import time

import numpy as np
import pytest

from ydb_tpu import chaos, dtypes
from ydb_tpu.analysis import leaksan
from ydb_tpu.chaos.deadline import Deadline, StatementCancelled, activate
from ydb_tpu.engine.blobs import MemBlobStore
from ydb_tpu.engine.reader import (PortionStreamSource, plan_clusters,
                                   stream_blocks)
from ydb_tpu.engine.shard import ColumnShard, ShardConfig
from ydb_tpu.kqp.session import Cluster
from ydb_tpu.runtime.conveyor import shared_conveyor, stream_conveyor

SCHEMA = dtypes.schema(
    ("id", dtypes.INT64, False),
    ("v", dtypes.INT64),
)

AGG_SQL = ("SELECT k % 5 AS g, SUM(v) AS sv, COUNT(*) AS n "
           "FROM kv GROUP BY k % 5 ORDER BY g")


@pytest.fixture(autouse=True)
def _clean():
    """Every test leaves the chaos subsystem disarmed."""
    yield
    chaos.clear()
    chaos.CHAOS_FORCE = None


def _shard(upsert=True):
    return ColumnShard(
        "s1", SCHEMA, MemBlobStore(), pk_column="id", upsert=upsert,
        config=ShardConfig(compact_portion_threshold=1_000_000),
    )


def _put(shard, ids, vals):
    wid = shard.write({"id": np.asarray(list(ids), dtype=np.int64),
                       "v": np.asarray(list(vals), dtype=np.int64)})
    return shard.commit([wid])


def _rows(blocks):
    """Per-block (ids, vals) lists, so identity checks cover block
    boundaries, not just totals."""
    out = []
    for blk in blocks:
        data = blk.to_numpy()
        n = int(blk.length)
        out.append((data["id"][:n].tolist(), data["v"][:n].tolist()))
    return out


def _scan(shard, cap=64):
    """Full scan through the pipeline; returns (source, block rows)."""
    src = PortionStreamSource(shard, shard.visible_portions(None))
    return src, _rows(src.blocks(cap))


def _scan_serialized(shard, cap=64):
    """The same scan through the serialized chain, called directly (a
    count-based resume takes it: reader.py ``blocks``)."""
    src = PortionStreamSource(shard, shard.visible_portions(None))
    names = src.columns_read
    return _rows(stream_blocks(
        src.payload_stream(plan_clusters(src.metas, src.dedup), names),
        names, shard.schema.select(names),
        min(cap, max(src.num_rows, 1))))


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


# ---------------- bit-identity: pipeline == serialized chain ---------


def test_bit_identity_plain_scan():
    shard = _shard(upsert=False)
    for off in range(6):
        base = off * 100
        _put(shard, range(base, base + 100),
             (i * 3 for i in range(base, base + 100)))

    serialized = _scan_serialized(shard)
    src, pipelined = _scan(shard)

    assert pipelined == serialized  # same blocks, same order, same rows
    stats = src.last_pipeline
    assert stats is not None and stats["morsels_io"] > 0  # it DID fly


def test_bit_identity_upsert_merge():
    # overlapping PK ranges force merge clusters (inline K-way merge
    # morsels) interleaved with cold single-portion IO morsels
    shard = _shard(upsert=True)
    _put(shard, range(0, 200), (i * 2 for i in range(0, 200)))
    _put(shard, range(100, 300), (i * 5 for i in range(100, 300)))
    _put(shard, range(50, 150), (i * 9 for i in range(50, 150)))
    _put(shard, range(1000, 1200), (i for i in range(1000, 1200)))

    serialized = _scan_serialized(shard)
    src, pipelined = _scan(shard)

    assert pipelined == serialized
    stats = src.last_pipeline
    assert stats is not None
    assert stats["morsels_merge"] > 0 and stats["morsels_io"] > 0


# ---------------- chaos: blob faults heal, consumer never stalls -----


def test_chaos_blob_io_error_heals_under_pipeline():
    c, s = _kv_cluster()
    want = s.execute(AGG_SQL)
    chaos.CHAOS_FORCE = True
    chaos.install(chaos.Scenario(seed=33, sites={
        "blob.get_range": {"kind": "io_error", "p": 0.6, "budget": 6},
    }))
    t0 = time.monotonic()
    got = s.execute(AGG_SQL)
    assert time.monotonic() - t0 < 30.0  # healed, not stalled
    snap = chaos.counters_snapshot()
    assert snap["sites"]["blob.get_range"]["fired"] > 0
    _same_result(got, want)


def test_chaos_blob_latency_does_not_stall_consumer():
    # pure-delay faults on every blob read: flights just take longer,
    # the consumer keeps draining in order and the result is identical
    c, s = _kv_cluster()
    want = s.execute(AGG_SQL)
    chaos.CHAOS_FORCE = True
    chaos.install(chaos.Scenario(seed=7, sites={
        "blob.get_range": {"kind": "delay", "p": 1.0,
                           "latency": 0.005},
    }))
    t0 = time.monotonic()
    got = s.execute(AGG_SQL)
    assert time.monotonic() - t0 < 30.0
    assert chaos.counters_snapshot()["sites"]["blob.get_range"][
        "fired"] > 0
    _same_result(got, want)


def test_chaos_torn_read_heals_under_pipeline():
    # a torn read truncates the payload mid-chunk: the zero-copy
    # decode raises a transient kind and the flight re-fetches
    c, s = _kv_cluster()
    want = s.execute(AGG_SQL)
    chaos.CHAOS_FORCE = True
    chaos.install(chaos.Scenario(seed=5, sites={
        "blob.get_range": {"kind": "torn", "p": 1.0, "budget": 2},
    }))
    got = s.execute(AGG_SQL)
    assert chaos.counters_snapshot()["sites"]["blob.get_range"][
        "fired"] == 2
    _same_result(got, want)


# ---------------- cancellation / abandonment: drain to zero ----------


def test_mid_scan_deadline_drains_morsel_flights():
    shard = _shard(upsert=False)
    for off in range(8):
        base = off * 200
        _put(shard, range(base, base + 200),
             (i * 3 for i in range(base, base + 200)))

    with leaksan.activate():
        src = PortionStreamSource(shard, shard.visible_portions(None))
        with activate(Deadline(seconds=0.0)):
            with pytest.raises(StatementCancelled):
                for _ in src.blocks(64):
                    pass
        deadline = time.monotonic() + 5.0
        while leaksan.live("stream.morsel") and \
                time.monotonic() < deadline:
            time.sleep(0.005)
        assert leaksan.live("stream.morsel") == []
        stream_conveyor().wait_idle(timeout=10.0)
        shared_conveyor().wait_idle(timeout=10.0)
        while leaksan.counts() and time.monotonic() < deadline:
            time.sleep(0.005)  # a worker may close its handle post-idle
        assert leaksan.counts() == {}
    # flights WERE admitted before the cancellation landed
    stats = src.last_pipeline
    assert stats is not None and stats["morsels_io"] > 0


def test_abandoned_stream_drains_morsel_flights():
    shard = _shard(upsert=False)
    for off in range(8):
        base = off * 200
        _put(shard, range(base, base + 200),
             (i * 3 for i in range(base, base + 200)))

    with leaksan.activate():
        src = PortionStreamSource(shard, shard.visible_portions(None))
        it = src.blocks(64)
        next(it)
        it.close()  # consumer walks away mid-stream
        deadline = time.monotonic() + 5.0
        while leaksan.live("stream.morsel") and \
                time.monotonic() < deadline:
            time.sleep(0.005)
        assert leaksan.live("stream.morsel") == []
        stream_conveyor().wait_idle(timeout=10.0)
        shared_conveyor().wait_idle(timeout=10.0)
        while leaksan.counts() and time.monotonic() < deadline:
            time.sleep(0.005)  # a worker may close its handle post-idle
        assert leaksan.counts() == {}
    assert src.last_pipeline is not None


# ---------------- work stealing: saturated pool never blocks ---------


def test_consumer_steals_when_stream_pool_saturated():
    shard = _shard(upsert=False)
    for off in range(6):
        base = off * 100
        _put(shard, range(base, base + 100),
             (i * 3 for i in range(base, base + 100)))
    serialized = _scan_serialized(shard)

    gate = threading.Event()
    cv = stream_conveyor()
    try:
        for _ in range(16):  # park every stream worker behind the gate
            cv.submit("test_gate", gate.wait)
        src, pipelined = _scan(shard)
    finally:
        gate.set()
    cv.wait_idle(timeout=10.0)

    assert pipelined == serialized  # stolen flights, identical stream
    stats = src.last_pipeline
    assert stats is not None and stats["stolen"] > 0
