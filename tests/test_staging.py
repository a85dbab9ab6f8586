"""Low-copy block staging pipeline tests (PR 3 tentpole, part 2).

Covers: rechunk's aligned pass-through / single-buffer fast paths,
TableBlock.from_numpy tail-only padding (padding validity never leaks),
the shared-pool depth-k prefetch in stream_blocks (incl. abandoned
generators not leaking producer tasks), per-scan stage timers and
the scan-executor LRU cap.
"""

import gc
import time

import numpy as np
import pytest

from ydb_tpu import dtypes
from ydb_tpu.blocks.block import TableBlock
from ydb_tpu.engine.blobs import DirBlobStore
from ydb_tpu.engine.reader import rechunk, stream_blocks
from ydb_tpu.engine.shard import ColumnShard, ShardConfig
from ydb_tpu.obs import probes
from ydb_tpu.runtime.conveyor import shared_conveyor
from ydb_tpu.ssa import Agg, AggSpec, GroupByStep, Program
from ydb_tpu.ssa.program import Call, Col, FilterStep, Op, lit

SCHEMA = dtypes.schema(("a", dtypes.INT64), ("b", dtypes.DOUBLE))


def _payload(n, seed=0):
    rng = np.random.default_rng(seed)
    return ({"a": rng.integers(0, 100, n).astype(np.int64),
             "b": rng.random(n)},
            {"a": np.ones(n, dtype=bool),
             "b": rng.random(n) > 0.2})


def test_rechunk_aligned_payload_passes_arrays_through():
    p = _payload(64)
    out = list(rechunk(iter([p]), ("a", "b"), 64))
    assert len(out) == 1
    cols, valid = out[0]
    # identity, not a copy: the aligned fast path
    assert cols["a"] is p[0]["a"]
    assert valid["b"] is p[1]["b"]


def test_rechunk_single_buffered_piece_skips_concat():
    p = _payload(40)
    out = list(rechunk(iter([p]), ("a", "b"), 64))
    assert len(out) == 1
    # whole-payload piece: original arrays flush through unconcatenated
    assert out[0][0]["a"] is p[0]["a"]


def test_rechunk_recut_matches_naive_concat():
    pieces = [_payload(n, seed=i) for i, n in enumerate([10, 64, 3, 57,
                                                         128, 1])]
    cap = 48
    got = list(rechunk(iter(pieces), ("a", "b"), cap))
    cat_a = np.concatenate([p[0]["a"] for p in pieces])
    cat_vb = np.concatenate([p[1]["b"] for p in pieces])
    assert sum(len(c["a"]) for c, _ in got) == len(cat_a)
    assert all(len(c["a"]) == cap for c, _ in got[:-1])
    np.testing.assert_array_equal(
        np.concatenate([c["a"] for c, _ in got]), cat_a)
    np.testing.assert_array_equal(
        np.concatenate([v["b"] for _, v in got]), cat_vb)


def test_from_numpy_tail_padding_never_leaks_validity():
    cols, valid = _payload(70)
    blk = TableBlock.from_numpy(cols, SCHEMA, valid, capacity=128)
    assert int(blk.length) == 70
    for name in ("a", "b"):
        v = np.asarray(blk.columns[name].validity)
        assert not v[70:].any(), f"padding validity leaked in {name}"
    np.testing.assert_array_equal(blk.to_numpy()["a"], cols["a"])
    # default validity (None) must also stay False in the tail
    blk2 = TableBlock.from_numpy(cols, SCHEMA, None, capacity=96)
    for name in ("a", "b"):
        v = np.asarray(blk2.columns[name].validity)
        assert v[:70].all() and not v[70:].any()


def test_from_numpy_aligned_no_padding():
    cols, valid = _payload(128)
    blk = TableBlock.from_numpy(cols, SCHEMA, valid, capacity=128)
    assert blk.capacity == 128 and int(blk.length) == 128
    np.testing.assert_array_equal(
        np.asarray(blk.columns["b"].validity), valid["b"])


@pytest.mark.parametrize("depth", [0, 1, 3])
def test_stream_blocks_prefetch_depths_agree(depth):
    pieces = [_payload(n, seed=i) for i, n in enumerate([100, 30, 250])]
    base = list(stream_blocks(iter(pieces), ("a", "b"), SCHEMA, 64,
                              prefetch=False))
    got = list(stream_blocks(iter(pieces), ("a", "b"), SCHEMA, 64,
                             depth=depth))
    assert len(got) == len(base)
    for g, b in zip(got, base):
        assert int(g.length) == int(b.length)
        np.testing.assert_array_equal(np.asarray(g.columns["a"].data),
                                      np.asarray(b.columns["a"].data))


def test_stream_blocks_empty_stream_emits_one_block():
    out = list(stream_blocks(iter([]), ("a", "b"), SCHEMA, 16))
    assert len(out) == 1 and int(out[0].length) == 0


def test_abandoned_stream_releases_shared_pool_producer():
    def slow_payloads():
        for i in range(50):
            time.sleep(0.01)
            yield _payload(64, seed=i)

    gen = stream_blocks(slow_payloads(), ("a", "b"), SCHEMA, 64, depth=2)
    next(gen)  # producer is now parked on the bounded queue
    gen.close()  # GeneratorExit -> stop flag + drain
    del gen
    gc.collect()
    # the producer task must exit promptly instead of leaking a worker
    shared_conveyor().wait_idle(timeout=10.0)


def _mk_shard(tmp_path, rows=500):
    shard = ColumnShard(
        "t", SCHEMA, DirBlobStore(str(tmp_path)),
        config=ShardConfig(compact_portion_threshold=10 ** 9,
                           scan_block_rows=128,
                           scan_cache_entries=2))
    rng = np.random.default_rng(1)
    shard.commit([shard.write({
        "a": rng.integers(0, 10, rows).astype(np.int64),
        "b": rng.random(rows)})])
    return shard


def _prog(threshold):
    return Program((
        FilterStep(Call(Op.GE, Col("a"), lit(threshold))),
        GroupByStep(("a",), (AggSpec(Agg.COUNT_ALL, None, "n"),)),
    ))


def test_scan_reports_stage_timers_and_fires_probe(tmp_path):
    shard = _mk_shard(tmp_path)
    with probes.TraceSession("columnshard.scan.stages") as sess:
        out = shard.scan(_prog(0))
    assert out.num_rows > 0
    stages = shard.last_scan_stages
    for key in ("read", "merge", "stage", "compute"):
        assert key in stages, stages
    assert stages["read"] > 0.0
    assert stages["compute"] > 0.0
    assert sess.counts["columnshard.scan.stages"] == 1
    (_, params), = sess.events
    assert params["shard"] == "t" and "stage" in params


def test_scan_cache_lru_bounded(tmp_path):
    shard = _mk_shard(tmp_path)
    for t in range(4):
        shard.scan(_prog(t))
    assert len(shard._scan_cache) <= 2
    # most-recent program stays cached (LRU keeps the tail)
    key3 = (_prog(3), ())
    assert any(k[0] == _prog(3) for k in shard._scan_cache)
    # re-scanning a cached program must not grow the cache
    shard.scan(_prog(3))
    assert len(shard._scan_cache) <= 2
    assert key3  # silence lint: structural key shape documented above


def test_scan_results_unchanged_by_staging_pipeline(tmp_path):
    # end-to-end: the low-copy + prefetch path produces the same result
    # as the synchronous path
    shard = _mk_shard(tmp_path, rows=700)
    out = shard.scan(_prog(2))
    a = shard.source_at().columns["a"]
    expect = {int(v): int((a[a >= 2] == v).sum())
              for v in np.unique(a[a >= 2])}
    got = {int(k): int(n) for k, n in zip(out.column("a"),
                                          out.column("n"))}
    assert got == expect
