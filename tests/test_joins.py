"""Join kernels + multi-table plan execution (Q3/Q5), cross-checked
against independent python-dict reference joins."""

import numpy as np
import pytest

from ydb_tpu import dtypes
from ydb_tpu.blocks import TableBlock
from ydb_tpu.plan import Database, execute_plan, to_host
from ydb_tpu.engine.scan import ColumnSource
from ydb_tpu.ssa import join as jk
from ydb_tpu.ssa import kernels
from ydb_tpu.workload import tpch


def _block(**cols):
    sch = []
    arrays = {}
    validity = {}
    for name, spec in cols.items():
        arr, t = spec[0], spec[1]
        sch.append((name, t))
        arrays[name] = np.asarray(arr)
        if len(spec) > 2:
            validity[name] = np.asarray(spec[2])
    return TableBlock.from_numpy(arrays, dtypes.schema(*sch), validity or None)


def test_lookup_join_inner_left_semi_anti():
    probe = _block(
        k=([1, 2, 3, 2, 9], dtypes.INT64),
        pv=([10, 20, 30, 21, 90], dtypes.INT64),
    )
    build = _block(
        bk=([2, 3, 4], dtypes.INT64),
        bv=([200, 300, 400], dtypes.INT64),
    )
    joined, found = jk.lookup_join(probe, build, ["k"], ["bk"], ["bv"])
    inner = kernels.compact(joined, found)
    res = TableBlock.to_numpy(inner)
    np.testing.assert_array_equal(res["k"], [2, 3, 2])
    np.testing.assert_array_equal(res["bv"], [200, 300, 200])

    # left: unmatched rows keep NULL payload
    lres = joined.validity_numpy()
    assert lres["bv"].tolist() == [False, True, True, True, False]

    semi = kernels.compact(probe, found)
    assert TableBlock.to_numpy(semi)["k"].tolist() == [2, 3, 2]
    anti = kernels.compact(probe, ~found & probe.row_mask())
    assert TableBlock.to_numpy(anti)["k"].tolist() == [1, 9]


def test_lookup_join_null_keys_never_match():
    probe = _block(k=([1, 1], dtypes.INT64, [True, False]))
    build = _block(bk=([1], dtypes.INT64), bv=([5], dtypes.INT64))
    _, found = jk.lookup_join(probe, build, ["k"], ["bk"], ["bv"])
    assert np.asarray(found)[:2].tolist() == [True, False]


def test_two_column_key_packing():
    probe = _block(
        a=([1, 1, 2], dtypes.INT64),
        b=([7, 8, 7], dtypes.INT64),
    )
    build = _block(
        x=([1, 2], dtypes.INT64),
        y=([7, 7], dtypes.INT64),
        v=([100, 200], dtypes.INT64),
    )
    _, found = jk.lookup_join(probe, build, ["a", "b"], ["x", "y"], ["v"])
    assert np.asarray(found)[:3].tolist() == [True, False, True]


def test_expand_join_n_to_m():
    probe = _block(k=([1, 2, 3], dtypes.INT64), p=([10, 20, 30], dtypes.INT64))
    build = _block(k2=([2, 2, 1, 5], dtypes.INT64),
                   q=([201, 202, 101, 501], dtypes.INT64))
    out, total = jk.expand_join(
        probe, build, ["k"], ["k2"], ["k", "p"], ["q"], out_capacity=16
    )
    assert int(total) == 3
    res = TableBlock.to_numpy(out)
    got = sorted(zip(res["k"].tolist(), res["q"].tolist()))
    assert got == [(1, 101), (2, 201), (2, 202)]


def test_expand_join_overflow_reports_total():
    probe = _block(k=([7] * 4, dtypes.INT64))
    build = _block(k2=([7] * 4, dtypes.INT64), q=(list(range(4)), dtypes.INT64))
    out, total = jk.expand_join(
        probe, build, ["k"], ["k2"], ["k"], ["q"], out_capacity=8
    )
    assert int(total) == 16  # 4x4 cross on same key; caller must retry
    assert int(out.length) == 8


# ---------------- reference joins for Q3/Q5 ----------------


@pytest.fixture(scope="module")
def data():
    return tpch.TpchData(sf=0.01, seed=23)


@pytest.fixture(scope="module")
def db(data):
    return Database(
        sources={
            t: ColumnSource(cols, data.schema(t), data.dicts)
            for t, cols in data.tables.items()
        },
        dicts=data.dicts,
    )


def _ref_q3(data):
    t = data.tables
    d = tpch._days("1995-03-15")
    seg = data.dicts["c_mktsegment"].eq_id(b"BUILDING")
    cust = set(t["customer"]["c_custkey"][
        t["customer"]["c_mktsegment"] == seg].tolist())
    omask = (t["orders"]["o_orderdate"] < d) & np.isin(
        t["orders"]["o_custkey"], list(cust))
    orders = {
        k: (dt, sp)
        for k, dt, sp in zip(
            t["orders"]["o_orderkey"][omask],
            t["orders"]["o_orderdate"][omask],
            t["orders"]["o_shippriority"][omask],
        )
    }
    li = t["lineitem"]
    lmask = li["l_shipdate"] > d
    agg = {}
    for ok, price, disc in zip(
        li["l_orderkey"][lmask], li["l_extendedprice"][lmask],
        li["l_discount"][lmask],
    ):
        if int(ok) in orders:
            dt, sp = orders[int(ok)]
            key = (int(ok), int(dt), int(sp))
            agg[key] = agg.get(key, 0) + int(price) * (100 - int(disc))
    rows = sorted(agg.items(), key=lambda kv: (-kv[1], kv[0][1], kv[0][0]))[:10]
    return rows


def test_q3_matches_reference(db, data):
    out = to_host(execute_plan(tpch.q3_plan(), db))
    ref = _ref_q3(data)
    assert out.num_rows == len(ref)
    for i, ((ok, dt, sp), rev) in enumerate(ref):
        assert int(out.cols["l_orderkey"][0][i]) == ok
        assert int(out.cols["o_orderdate"][0][i]) == dt
        assert int(out.cols["revenue"][0][i]) == rev


def _ref_q5(data):
    t = data.tables
    d0, d1 = tpch._days("1994-01-01"), tpch._days("1995-01-01")
    asia = data.dicts["r_name"].eq_id(b"ASIA")
    rk = set(t["region"]["r_regionkey"][
        t["region"]["r_name"] == asia].tolist())
    nations = {
        int(nk): int(nm)
        for nk, nrk, nm in zip(
            t["nation"]["n_nationkey"], t["nation"]["n_regionkey"],
            t["nation"]["n_name"])
        if int(nrk) in rk
    }
    omask = (t["orders"]["o_orderdate"] >= d0) & (
        t["orders"]["o_orderdate"] < d1)
    orders = dict(zip(
        t["orders"]["o_orderkey"][omask].tolist(),
        t["orders"]["o_custkey"][omask].tolist(),
    ))
    supp = dict(zip(t["supplier"]["s_suppkey"].tolist(),
                    t["supplier"]["s_nationkey"].tolist()))
    cust = dict(zip(t["customer"]["c_custkey"].tolist(),
                    t["customer"]["c_nationkey"].tolist()))
    li = t["lineitem"]
    agg = {}
    for ok, sk, price, disc in zip(
        li["l_orderkey"].tolist(), li["l_suppkey"].tolist(),
        li["l_extendedprice"].tolist(), li["l_discount"].tolist(),
    ):
        ck = orders.get(ok)
        if ck is None:
            continue
        sn = supp[sk]
        if sn not in nations or cust[ck] != sn:
            continue
        agg[sn] = agg.get(sn, 0) + price * (100 - disc)
    return sorted(
        ((nations[sn], rev) for sn, rev in agg.items()),
        key=lambda kv: -kv[1],
    )


def test_q5_matches_reference(db, data):
    out = to_host(execute_plan(tpch.q5_plan(), db))
    ref = _ref_q5(data)
    assert out.num_rows == len(ref)
    np.testing.assert_array_equal(
        out.cols["revenue"][0], [rev for _, rev in ref]
    )
    np.testing.assert_array_equal(
        out.cols["n_name"][0], [nm for nm, _ in ref]
    )


def test_lookup_join_int64_max_key_matches():
    """No value sentinel: INT64_MAX is a legitimate joinable key."""
    big = np.iinfo(np.int64).max
    probe = _block(k=([big, 5], dtypes.INT64))
    build = _block(bk=([big], dtypes.INT64), bv=([1], dtypes.INT64))
    _, found = jk.lookup_join(probe, build, ["k"], ["bk"], ["bv"])
    assert np.asarray(found)[:2].tolist() == [True, False]
    out, total = jk.expand_join(
        probe, build, ["k"], ["bk"], ["k"], ["bv"], out_capacity=8
    )
    assert int(total) == 1
    assert TableBlock.to_numpy(out)["k"].tolist() == [big]


# ---- the sorts a cold start pays for (ROADMAP S10) -------------------


@pytest.mark.parametrize("rows", (1, 7, 1000, 5000))
@pytest.mark.parametrize("classes", (2, 5))
def test_stable_partition_is_the_stable_argsort(rows, classes):
    """``kernels.stable_partition`` answers ``argsort(stable=True)`` of a
    flag or a small class number: rows of one class keep their order."""
    import jax.numpy as jnp

    rng = np.random.default_rng(rows * classes)
    last = (rng.random(rows) < 0.4 if classes == 2
            else rng.integers(0, classes, rows).astype(np.int32))
    got = np.asarray(kernels.stable_partition(jnp.asarray(last), classes))
    assert got.dtype == np.int32
    assert (got == np.argsort(last, kind="stable")).all()


def test_stable_partition_falls_back_where_a_row_needs_the_class_bits():
    """A row number and its class share 32 bits: past ``2^32 / classes``
    rows the (class, row) pair sorts instead. Held on shapes alone."""
    import jax

    few = jax.ShapeDtypeStruct((1 << 12,), np.dtype("int32"))
    many = jax.ShapeDtypeStruct(((1 << 29) + 1,), np.dtype("int32"))
    part = lambda x: kernels.stable_partition(x, classes=5)
    assert "ui32" in jax.jit(part).lower(few).as_text()
    assert jax.eval_shape(part, many).shape == many.shape
    assert "ui32" not in jax.jit(part).lower(many).as_text()


@pytest.mark.parametrize("keys", ("small", "wide", "extremes"))
@pytest.mark.parametrize("rows", (1, 7, 1000, 5000))
def test_sorted_build_is_the_two_key_lexsort(rows, keys):
    """Three stable 32-bit passes order the build side as
    ``lexsort((key, dead))`` does: negative keys, keys that differ in
    the high word only, INT64_MIN / INT64_MAX among the live rows."""
    import jax.numpy as jnp

    rng = np.random.default_rng(rows)
    if keys == "small":
        bk = rng.integers(-5, 5, rows, dtype=np.int64)
    elif keys == "wide":
        bk = rng.integers(-2**40, 2**40, rows, dtype=np.int64)
        bk[::3] &= ~np.int64(0xFFFFFFFF)        # equal low words
    else:
        bk = rng.integers(-2**63, 2**63 - 1, rows, dtype=np.int64)
        bk[rng.random(rows) < 0.3] = np.iinfo(np.int64).max
        bk[rng.random(rows) < 0.1] = np.iinfo(np.int64).min
    live = rng.random(rows) < 0.7
    order, bk_sorted, n_live = jk._sorted_build(jnp.asarray(bk),
                                               jnp.asarray(live))
    want = np.lexsort((bk, ~live))
    assert (np.asarray(order) == want).all()
    assert int(n_live) == live.sum()
    assert (np.asarray(bk_sorted)[:live.sum()] == bk[want][:live.sum()]).all()
    assert (np.diff(np.asarray(bk_sorted)) >= 0).all()


_KEY_KINDS = ("bool", "i64", "u64", "i32", "u32", "i8", "u16")


def _key(kind, n, rng):
    if kind == "bool":
        return rng.random(n) < 0.5
    if kind == "i64":
        x = rng.integers(-2**63, 2**63 - 1, n, dtype=np.int64)
        x[::3] = rng.integers(-3, 3, len(x[::3]))      # ties, both signs
        return x
    if kind == "u64":
        return rng.integers(0, 2**64 - 1, n, dtype=np.uint64)
    if kind == "i32":
        return (rng.integers(-2**31, 2**31 - 1, n)
                >> int(rng.integers(0, 30))).astype(np.int32)
    if kind == "u32":
        return (rng.integers(0, 2**32 - 1, n)
                >> int(rng.integers(0, 30))).astype(np.uint32)
    if kind == "i8":
        return rng.integers(-128, 127, n).astype(np.int8)
    return rng.integers(0, 65535, n).astype(np.uint16)


@pytest.mark.parametrize("rows", (1, 2, 50, 3000))
@pytest.mark.parametrize("seed", range(4))
def test_stable_lexsort_is_numpys(rows, seed):
    """One stable pass a 32-bit word of a key orders rows as
    ``np.lexsort`` does (the last key primary, ties in their order):
    flags, signed and unsigned keys of 8 to 64 bits, mixed."""
    import jax.numpy as jnp

    rng = np.random.default_rng(100 * rows + seed)
    for _ in range(5):
        keys = [_key(k, rows, rng)
                for k in rng.choice(_KEY_KINDS, size=rng.integers(1, 6))]
        got = np.asarray(kernels.stable_lexsort(
            [jnp.asarray(k) for k in keys]))
        assert got.dtype == np.int32
        assert (got == np.lexsort(tuple(keys))).all()


def test_stable_lexsort_keeps_the_comparator_sort_for_a_float_key():
    """NaNs and signed zeros have their place in XLA's comparator: a
    floating key leaves the whole sort to ``jnp.lexsort``."""
    import jax.numpy as jnp

    rng = np.random.default_rng(7)
    f = np.where(rng.random(300) < 0.1, np.nan,
                 rng.integers(-3, 3, 300).astype(np.float64))
    f[::17] = -0.0
    keys = [jnp.asarray(f), jnp.asarray(_key("i8", 300, rng)),
            jnp.asarray(_key("bool", 300, rng))]
    assert (np.asarray(kernels.stable_lexsort(keys))
            == np.asarray(jnp.lexsort(tuple(keys)))).all()


@pytest.mark.parametrize("program", ("compact", "sorted_build",
                                     "repartition", "group_ids_sorted",
                                     "sort_perm", "group_keys"))
def test_no_program_sorts_a_64_bit_or_a_two_key_operand(program):
    """What XLA's TPU sort costs to compile follows its operands: an
    (int64, bool) pair with its row numbers took 208-239 s for a
    described v5e at 917,504 rows, a ``uint32`` alone 26 s (PERF.md
    section 6, PR 35). The join's and the exchange's programs hold
    only sorts of one 32-bit key, alone or with its row numbers, and
    ``compact`` holds no sort, gather or scatter at all (PR 36); nor
    does a whole group-by whose output keys are its sorted keys
    compacted at the segment heads add a sort to ``group_ids_sorted``'s
    (PR 40)."""
    import re

    import jax
    import jax.numpy as jnp

    from ydb_tpu.parallel import shuffle
    from ydb_tpu.parallel.mesh import SHARD_AXIS, make_mesh, shard_map
    from jax.sharding import PartitionSpec as P

    rows = 1 << 12
    blk = _block(k=(np.arange(rows), dtypes.INT64),
                 v=(np.arange(rows), dtypes.INT64))
    if program == "compact":
        text = jax.jit(kernels.compact).lower(
            blk, jnp.zeros(rows, bool)).as_text()
    elif program == "sorted_build":
        text = jax.jit(jk._sorted_build).lower(
            jnp.zeros(rows, jnp.int64), jnp.zeros(rows, bool)).as_text()
    elif program in ("group_ids_sorted", "sort_perm", "group_keys"):
        # Q3's group-by and its ORDER BY: int64 and int32 keys, their
        # validities, the live flag
        cols = [blk.columns["k"], blk.columns["v"]]
        fn = ((lambda: kernels.sort_perm(cols, [True, False],
                                         blk.row_mask()))
              if program == "sort_perm" else
              (lambda: kernels.group_ids_sorted(cols, blk.row_mask(), rows)))
        text = jax.jit(fn).lower().as_text()
        if program == "group_keys":
            # the lowered group-by above the one-hot tier, keys and all:
            # as many sorts as its group ids alone
            from ydb_tpu.ssa import AggSpec, GroupByStep, Program, \
                compile_program
            from ydb_tpu.ssa.ops import Agg

            ids_alone = text.count('"stablehlo.sort"')
            cp = compile_program(Program((GroupByStep(
                keys=("k", "v"),
                aggs=(AggSpec(Agg.COUNT_ALL, None, "n"),)),)), blk.schema)
            text = jax.jit(cp.run).lower(blk, {}).as_text()
            assert cp.notes["key_tier"] == "segment"
            assert text.count('"stablehlo.sort"') == ids_alone
    else:
        mesh = make_mesh(1, devices=jax.devices()[:1])
        text = jax.jit(shard_map(
            lambda b: shuffle.repartition(b, ["k"], 1, bucket_rows=rows),
            mesh=mesh, in_specs=P(), out_specs=P(),
            check_vma=False)).lower(blk).as_text()
    if program == "compact":
        # since PR 36 a prefix count and log2(capacity) rounds of
        # shift-and-select: nothing sorts, gathers or scatters at all
        moved = re.findall(r"stablehlo\.\w*(?:sort|gather|scatter)\w*", text)
        assert not moved, sorted(set(moved))
        assert "stablehlo.select" in text and "stablehlo.while" in text
        return
    # a sort's comparator takes two scalars an operand, keys first
    sorts = re.findall(r'"stablehlo\.sort"\([^\n]*\n\s*\^bb0\(([^\n]*)\):',
                       text)
    assert sorts, text[:400]
    for args in sorts:
        kinds = re.findall(r"tensor<(\w+)>", args)[::2]
        assert len(kinds) <= 2 and kinds[0] in ("ui32", "i32"), args
