"""Native host kernels: C++ and numpy twins must agree bit-for-bit
(routing and merges must not depend on whether the toolchain exists)."""

import numpy as np
import pytest

from ydb_tpu import native
from ydb_tpu.native import BloomFilter, hash_rows, kway_merge


@pytest.fixture
def both_paths(monkeypatch):
    """Run a fn under (native, fallback) and return both results."""
    def run(fn):
        a = fn()
        monkeypatch.setattr(native, "_lib", False)
        b = fn()
        monkeypatch.setattr(native, "_lib", None)
        return a, b
    return run


def test_native_library_builds():
    import os

    if os.environ.get("YDB_TPU_NO_NATIVE"):
        pytest.skip("native explicitly disabled")
    import shutil

    if shutil.which("g++") is None:
        pytest.skip("no C++ toolchain; fallback twins cover behavior")
    assert native.available()


def test_hash_rows_native_matches_numpy(both_paths):
    rng = np.random.default_rng(7)
    keys = [rng.integers(-2**40, 2**40, 1000),
            rng.integers(0, 100, 1000)]
    valids = [rng.random(1000) < 0.9, np.ones(1000, dtype=bool)]
    a, b = both_paths(lambda: hash_rows(keys, valids))
    np.testing.assert_array_equal(a, b)
    # validity flips change the hash
    v2 = [~valids[0], valids[1]]
    assert (hash_rows(keys, valids) != hash_rows(keys, v2)).any()


def test_kway_merge_native_matches_numpy(both_paths):
    rng = np.random.default_rng(3)
    runs = [np.sort(rng.integers(0, 500, n))
            for n in (100, 0, 57, 333)]
    for dedup in (False, True):
        (ar, ai), (br, bi) = both_paths(
            lambda: kway_merge(runs, dedup=dedup))
        np.testing.assert_array_equal(ar, br)
        np.testing.assert_array_equal(ai, bi)


def test_kway_merge_order_and_dedup():
    runs = [np.array([1, 3, 5]), np.array([1, 2, 5, 9])]
    run_i, row_i = kway_merge(runs)
    merged = [int(runs[r][i]) for r, i in zip(run_i, row_i)]
    assert merged == [1, 1, 2, 3, 5, 5, 9]
    run_i, row_i = kway_merge(runs, dedup=True)
    merged = [(int(runs[r][i]), int(r)) for r, i in zip(run_i, row_i)]
    # newest-wins: duplicates resolve to the higher run index
    assert merged == [(1, 1), (2, 1), (3, 0), (5, 1), (9, 1)]


def test_kway_merge_empty():
    run_i, row_i = kway_merge([])
    assert len(run_i) == 0 and len(row_i) == 0
    run_i, row_i = kway_merge([np.empty(0, dtype=np.int64)], dedup=True)
    assert len(run_i) == 0


def test_bloom_filter_native_matches_numpy(both_paths):
    rng = np.random.default_rng(11)
    present = rng.integers(0, 2**63, 500).astype(np.uint64)
    probes = rng.integers(0, 2**63, 2000).astype(np.uint64)

    def run():
        bf = BloomFilter.for_items(500)
        bf.add(present)
        return bf.query(np.concatenate([present, probes]))

    a, b = run_twice = both_paths(run)
    np.testing.assert_array_equal(a, b)
    # no false negatives; false-positive rate sane at 10 bits/item
    assert a[:500].all()
    fp = a[500:].mean()
    assert fp < 0.05


def test_hash_rows_used_by_shuffle_routing():
    """A DQ channel routes a row to consumer ``hash_rows % n``, the hash
    computed on the device bit for bit as here."""
    from ydb_tpu import dtypes
    from ydb_tpu.blocks.block import TableBlock
    from ydb_tpu.dq.compute import _route_dest

    k = np.arange(100, dtype=np.int64)
    ok = np.arange(100) % 7 != 0
    block = TableBlock.from_numpy(
        {"k": k}, dtypes.schema(("k", dtypes.INT64)), {"k": ok})
    dest, counts = _route_dest(block, ("k",), 3)
    h = hash_rows([k], [ok])
    assert h.dtype == np.uint64 and len(h) == 100
    want = (h % np.uint64(3)).astype(np.int64)
    np.testing.assert_array_equal(np.asarray(dest)[:100], want)
    assert (np.asarray(dest)[100:] == 3).all()
    np.testing.assert_array_equal(np.asarray(counts),
                                  np.bincount(want, minlength=3))
