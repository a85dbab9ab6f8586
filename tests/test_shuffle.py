"""Hash-shuffle (all_to_all repartition) tests on the CPU mesh."""

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ydb_tpu import dtypes
from ydb_tpu.blocks import TableBlock
from ydb_tpu.parallel.dist import _local, _relocal, stack_blocks
from ydb_tpu.parallel.mesh import SHARD_AXIS, make_mesh, shard_map
from ydb_tpu.parallel.shuffle import hash_rows, repartition


def _stacked_random(n_dev, rows_per_dev, seed=3):
    rng = np.random.default_rng(seed)
    sch = dtypes.schema(("k", dtypes.INT64), ("v", dtypes.INT64))
    blocks = []
    for d in range(n_dev):
        n = rows_per_dev - (d % 3)  # uneven live counts
        blocks.append(TableBlock.from_numpy(
            {
                "k": rng.integers(0, 1000, n),
                "v": rng.integers(0, 10, n) + d * 1000,
            },
            sch, capacity=rows_per_dev,
        ))
    return blocks, sch


def test_repartition_preserves_rows_and_colocates_keys():
    n_dev = 8
    mesh = make_mesh(n_dev)
    blocks, sch = _stacked_random(n_dev, 64)

    def step(stacked):
        blk = _local(stacked)
        return _relocal(repartition(blk, ["k"], n_dev))

    fn = jax.jit(shard_map(
        step, mesh=mesh, in_specs=P(SHARD_AXIS), out_specs=P(SHARD_AXIS),
        check_vma=False,
    ))
    stacked = jax.device_put(
        stack_blocks(blocks), NamedSharding(mesh, P(SHARD_AXIS))
    )
    out = fn(stacked)

    # reassemble per-device results from the stacked output
    data_k = np.asarray(out.columns["k"].data)
    data_v = np.asarray(out.columns["v"].data)
    lens = np.asarray(out.length)
    got = []
    per_dev_keys = []
    for d in range(n_dev):
        k = data_k[d][: lens[d]]
        v = data_v[d][: lens[d]]
        got.extend(zip(k.tolist(), v.tolist()))
        per_dev_keys.append(set(k.tolist()))

    want = []
    for b in blocks:
        c = b.to_numpy()
        want.extend(zip(c["k"].tolist(), c["v"].tolist()))
    assert sorted(got) == sorted(want)  # no row lost or duplicated

    # same key never appears on two shards
    for i in range(n_dev):
        for j in range(i + 1, n_dev):
            assert not (per_dev_keys[i] & per_dev_keys[j])


def test_hash_rows_distinguishes_null_from_zero():
    from ydb_tpu.blocks.block import Column

    d = jnp.array([0, 0], dtype=jnp.int64)
    v = jnp.array([True, False])
    h = hash_rows([Column(d, v)])
    assert int(h[0]) != int(h[1])


def test_hash_rows_deterministic_across_partitions():
    """The row hash is a pure function of (value, validity): dict-id
    string columns (int32 codes) and scaled decimals (int64) hash to the
    same destination no matter which device/partition holds the row —
    the property repartition's key colocation rests on."""
    from ydb_tpu.blocks.block import Column

    rng = np.random.default_rng(9)
    ids = rng.integers(0, 50, 256).astype(np.int32)  # dict codes
    dec = (rng.integers(-10 ** 6, 10 ** 6, 256) * 100).astype(np.int64)
    ok = rng.random(256) > 0.1
    full = hash_rows([Column(jnp.asarray(ids), jnp.asarray(ok)),
                      Column(jnp.asarray(dec), jnp.asarray(ok))])
    for s in range(4):  # round-robin partitions, as the mesh shards
        part = hash_rows([
            Column(jnp.asarray(ids[s::4]), jnp.asarray(ok[s::4])),
            Column(jnp.asarray(dec[s::4]), jnp.asarray(ok[s::4]))])
        np.testing.assert_array_equal(
            np.asarray(part), np.asarray(full)[s::4])


def test_null_keys_colocate_on_one_shard():
    """NULL join keys (canonical zeroed slots) form one hash class: the
    exchange lands every NULL-key row on the same device."""
    n_dev = 8
    mesh = make_mesh(n_dev)
    sch = dtypes.schema(("k", dtypes.INT64), ("v", dtypes.INT64))
    rng = np.random.default_rng(5)
    blocks = []
    for d in range(n_dev):
        k = rng.integers(1, 1000, 64)
        ok = np.ones(64, dtype=bool)
        ok[d::7] = False
        k[~ok] = 0  # canonical NULL slot, as the kernels emit
        blocks.append(TableBlock.from_numpy(
            {"k": k, "v": rng.integers(0, 10, 64)}, sch,
            validity={"k": ok, "v": np.ones(64, dtype=bool)},
            capacity=64))
    n_null = sum(int((~b.validity_numpy()["k"]).sum()) for b in blocks)
    assert n_null > 0

    def step(stacked):
        blk = _local(stacked)
        return _relocal(repartition(blk, ["k"], n_dev))

    fn = jax.jit(shard_map(
        step, mesh=mesh, in_specs=P(SHARD_AXIS), out_specs=P(SHARD_AXIS),
        check_vma=False,
    ))
    out = fn(jax.device_put(
        stack_blocks(blocks), NamedSharding(mesh, P(SHARD_AXIS))))
    lens = np.asarray(out.length)
    ok = np.asarray(out.columns["k"].validity)
    per_dev_nulls = [int((~ok[d][: lens[d]]).sum()) for d in range(n_dev)]
    assert sum(per_dev_nulls) == n_null  # no NULL row lost
    assert sum(1 for c in per_dev_nulls if c) == 1, per_dev_nulls


def test_size_buckets_uniform_and_gates():
    from ydb_tpu.parallel import shuffle as sh
    from ydb_tpu.ssa.plan_fuse import shape_class

    old = sh.SHUFFLE_STATS_FORCE
    try:
        sh.SHUFFLE_STATS_FORCE = True
        # uniform keys over 8 destinations: mean x margin, far under
        # full capacity (the >=4x exchange reduction the bench asserts)
        assert sh.size_buckets(1 << 15, 8) <= (1 << 15) // 4
        # the estimate is shape-class rounded (zero-retrace re-runs)
        b = sh.size_buckets(1 << 15, 8, heavy=100)
        assert b == shape_class(b)
        # a heavy hitter widens the bucket, never past full capacity
        assert sh.size_buckets(1 << 15, 8, heavy=1 << 20) == 1 << 15
        # degenerate 1-shard mesh: no exchange, full capacity
        assert sh.size_buckets(1 << 15, 1) == 1 << 15
        sh.SHUFFLE_STATS_FORCE = False
        assert sh.size_buckets(1 << 15, 8) == 1 << 15  # stats off
    finally:
        sh.SHUFFLE_STATS_FORCE = old


def test_stats_sized_bucket_exchanges_uniform_keys_losslessly():
    """Uniform keys over 8 destinations, the send bucket sized from a
    real count-min sketch of them (mean load x margin + the heavy-hitter
    bound): at least 4x under full capacity, never overflowed, and the
    exchange delivers the row multiset the full-capacity one does, every
    key on one shard."""
    from ydb_tpu.parallel import shuffle as sh
    from ydb_tpu.stats.sketch import CountMinSketch

    n_dev, rows = 8, 8192
    mesh = make_mesh(n_dev)
    sch = dtypes.schema(("k", dtypes.INT64), ("v", dtypes.INT64))
    rng = np.random.default_rng(11)
    keys = [rng.integers(0, 1 << 30, rows).astype(np.int64)
            for _ in range(n_dev)]
    sk = CountMinSketch()
    for arr in keys:
        sk.add_many(arr)
    old = sh.SHUFFLE_STATS_FORCE
    sh.SHUFFLE_STATS_FORCE = True
    try:
        stats_b = sh.size_buckets(rows, n_dev, heavy=sk.max_freq())
    finally:
        sh.SHUFFLE_STATS_FORCE = old
    assert rows / stats_b >= 4, (stats_b, sk.max_freq())

    stacked = stack_blocks([TableBlock.from_numpy(
        {"k": keys[d],
         "v": np.arange(rows, dtype=np.int64) + d * rows},
        sch, capacity=rows) for d in range(n_dev)])

    def exchange(bucket):
        def step(st):
            blk, worst = repartition(_local(st), ["k"], n_dev,
                                     bucket_rows=bucket, with_counts=True)
            return _relocal(blk), worst
        fn = jax.jit(shard_map(
            step, mesh=mesh, in_specs=P(SHARD_AXIS),
            out_specs=(P(SHARD_AXIS), P()), check_vma=False))
        out, worst = fn(jax.device_put(
            stacked, NamedSharding(mesh, P(SHARD_AXIS))))
        assert int(np.asarray(worst)) <= bucket  # no overflow
        lens = np.asarray(out.length)
        ks = np.asarray(out.columns["k"].data)
        vs = np.asarray(out.columns["v"].data)
        got, per_dev = [], []
        for d in range(n_dev):
            got.extend(zip(ks[d][: lens[d]].tolist(),
                           vs[d][: lens[d]].tolist()))
            per_dev.append(set(ks[d][: lens[d]].tolist()))
        return sorted(got), per_dev

    want = sorted((int(k), d * rows + i)
                  for d in range(n_dev) for i, k in enumerate(keys[d]))
    for bucket in (stats_b, rows):
        got, per_dev = exchange(bucket)
        assert got == want  # no row lost or duplicated
        for i in range(n_dev):
            for j in range(i + 1, n_dev):
                assert not (per_dev[i] & per_dev[j])


def test_heavy_bound_joint_keys():
    from ydb_tpu.parallel.shuffle import heavy_bound
    from ydb_tpu.stats.cost import ColumnStats

    class TS:
        def __init__(self, cols):
            self.columns = cols

    stats = {"a": TS({"k": ColumnStats(heavy=500)}),
             "b": TS({"k": ColumnStats(heavy=200),
                      "j": ColumnStats(heavy=40)})}
    assert heavy_bound(stats, ["k"]) == 500  # max across tables
    # composite key: bounded by its rarest component
    assert heavy_bound(stats, ["k", "j"]) == 40
    assert heavy_bound(stats, ["missing"]) == 0
    assert heavy_bound(None, ["k"]) == 0


def test_repartition_overflow_reports_worst_and_grow_roundtrips():
    """100% skew with an undersized bucket: the traced worst count
    exceeds the capacity (rows were dropped), and re-exchanging at the
    observed size is lossless — the grace respill protocol."""
    n_dev = 8
    rows = 256
    mesh = make_mesh(n_dev)
    sch = dtypes.schema(("k", dtypes.INT64), ("v", dtypes.INT64))
    blocks = [TableBlock.from_numpy(
        {"k": np.full(rows, 3, dtype=np.int64),
         "v": np.arange(rows, dtype=np.int64) + d * rows},
        sch, capacity=rows) for d in range(n_dev)]
    stacked = stack_blocks(blocks)

    def run(B):
        def step(st):
            blk, worst = repartition(_local(st), ["k"], n_dev,
                                     bucket_rows=B, with_counts=True)
            return _relocal(blk), worst
        fn = jax.jit(shard_map(
            step, mesh=mesh, in_specs=P(SHARD_AXIS),
            out_specs=(P(SHARD_AXIS), P()), check_vma=False))
        return fn(jax.device_put(
            stacked, NamedSharding(mesh, P(SHARD_AXIS))))

    out, worst = run(64)  # undersized: every device sends all 256 rows
    assert int(np.asarray(worst)) == rows  # the observed grow target
    out, worst = run(int(np.asarray(worst)))
    assert int(np.asarray(worst)) <= rows
    lens = np.asarray(out.length)
    got = []
    for d in range(n_dev):
        got.extend(np.asarray(out.columns["v"].data)[d][: lens[d]].tolist())
    assert sorted(got) == list(range(n_dev * rows))  # lossless


def test_mesh_walk_round_up_is_shape_class():
    from ydb_tpu.parallel.mesh_exec import _round_up
    from ydb_tpu.ssa.plan_fuse import shape_class

    for n in (1, 1000, 1024, 5000, 1 << 17, (1 << 17) + 1):
        assert _round_up(n) == shape_class(n)
