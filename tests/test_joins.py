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
    joined, found, _ = jk.lookup_join(probe, build, ["k"], ["bk"], ["bv"])
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
    _, found, _ = jk.lookup_join(probe, build, ["k"], ["bk"], ["bv"])
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
    _, found, _ = jk.lookup_join(probe, build, ["a", "b"], ["x", "y"],
                                 ["v"])
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
    _, found, _ = jk.lookup_join(probe, build, ["k"], ["bk"], ["bv"])
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


# ---- the lookup's two paths: a direct-addressed table, a binary search ----

I64 = np.iinfo(np.int64)
KINDS = ("inner", "left", "semi", "anti")
PROBE_CAP, BUILD_CAP = 2048, 1024
#: the direct table of a 2,048-slot probe into a 1,024-slot build:
#: shape_class(max(4 * 1,024, min(2^21, 2,048)))
SLOTS = 4096


def _side(key: str, live, dead=(), nulls=(), capacity=BUILD_CAP,
          seed=0) -> TableBlock:
    """A join side of ``capacity`` slots: ``live`` keys first (the rows
    at ``nulls`` with a NULL key), then ``dead`` keys past its length,
    valid and real-looking as a compacted block's padding can be, and a
    payload column of distinct values."""
    keys = np.zeros(capacity, np.int64)
    n, d = len(live), len(dead)
    keys[:n] = live
    keys[n:n + d] = dead
    valid = np.zeros(capacity, bool)
    valid[:n + d] = True
    valid[list(nulls)] = False
    pay = np.random.default_rng(seed).permutation(capacity) + 1
    sch = dtypes.schema((key, dtypes.INT64), (key + "v", dtypes.INT64))
    full = TableBlock.from_numpy({key: keys, key + "v": pay}, sch,
                                 {key: valid, key + "v": valid}, capacity)
    return TableBlock(full.columns, np.int32(n), sch)


def _lookup_cases():
    rng = np.random.default_rng(45)
    far = 10 ** 15
    dense = np.arange(1, 501)
    return {
        # probe misses below and above the build's keys
        "dense": (dense, (), (), rng.integers(0, 600, 1500), (), (), True),
        # the first live row of a repeated key wins, on both paths
        "duplicates": (rng.integers(1, 60, 400), (), (),
                       rng.integers(0, 70, 1500), (), (), True),
        # NULL keys on both sides; dead build rows whose keys the probe
        # asks for and one far away (neither matches nor widens the
        # span), dead probe rows whose keys the build has
        "nulls_and_dead": (np.arange(1, 301), (301, 302, far), (0, 5, 77),
                           rng.integers(0, 320, 1500), (410, 303, 302),
                           (3, 4, 5, 7), True),
        "empty_build": ((), (1, 2, 3), (), rng.integers(0, 5, 1500), (),
                        (), False),
        "negative": (np.arange(-700, -200), (), (),
                     rng.integers(-800, 0, 1500), (), (), True),
        "int64_max_end": (np.arange(I64.max - 9, I64.max, dtype=np.int64)
                          .tolist() + [I64.max], (), (),
                          [I64.min, I64.max, I64.max - 20, I64.max - 3, 0],
                          (), (), True),
        "int64_min_end": (np.arange(I64.min, I64.min + 10, dtype=np.int64),
                          (), (), [I64.max, I64.min, I64.min + 9, -1, 0],
                          (), (), True),
        "int64_both_ends": ([I64.min, I64.min + 1, 0, I64.max], (), (),
                            [I64.max, I64.min, 0, 1, I64.min + 1], (), (),
                            False),
        "span_slots_less_one": ([0, SLOTS - 1, 17, 4000], (), (),
                                [0, SLOTS - 1, SLOTS, 17, -1, 4000], (), (),
                                True),
        "span_slots": ([0, SLOTS, 17, 4000], (), (),
                       [0, SLOTS, SLOTS - 1, 17, -1, 4000], (), (), False),
    }


LOOKUP_CASES = _lookup_cases()


def _sides(case: str):
    live, dead, nulls, plive, pdead, pnulls, _ = LOOKUP_CASES[case]
    probe = _side("k", plive, pdead, pnulls, PROBE_CAP, seed=1)
    build = _side("bk", live, dead, nulls, BUILD_CAP, seed=2)
    return probe, build


def _every_kind_program(search_only: bool):
    """Every kind's output and path flag in one program: as the rule
    picks, or (``search_only``) compiled with the search alone."""
    import unittest.mock

    import jax

    @jax.jit
    def run(probe, build):
        with unittest.mock.patch.object(
                jk, "_direct_slots",
                (lambda *a: None) if search_only else jk._direct_slots):
            return {kind: jk._lookup_join_of_kind.__wrapped__(
                probe, build, ("k",), ("bk",), ("bkv",), "", kind)
                for kind in KINDS}

    return run


#: one program a path for every case: their shapes are one pair of
#: shape classes
_EVERY_KIND = {False: _every_kind_program(False),
               True: _every_kind_program(True)}


@pytest.mark.parametrize("case", sorted(LOOKUP_CASES))
def test_the_direct_path_is_the_search_bit_for_bit(case):
    """Each kind's output on the path the input picks against the same
    join compiled with the search alone: the rows, their order, every
    column's data and validity (a left join's payload data under its
    NULL excepted: unspecified on both paths), and the inner join's
    matches against a dict of the live build rows, the first of a
    repeated key winning."""
    probe, build = _sides(case)
    got = _EVERY_KIND[False](probe, build)
    want = _EVERY_KIND[True](probe, build)
    assert {bool(got[k][1]) for k in KINDS} == {LOOKUP_CASES[case][-1]}
    assert not any(bool(want[k][1]) for k in KINDS)
    for kind in KINDS:
        g, w = got[kind][0], want[kind][0]
        n = int(w.length)
        assert int(g.length) == n, kind
        for name, wc in w.columns.items():
            gc = g.columns[name]
            ok = np.asarray(wc.validity)[:n]
            np.testing.assert_array_equal(
                np.asarray(gc.validity)[:n], ok, err_msg=(kind, name))
            gd, wd = np.asarray(gc.data)[:n], np.asarray(wc.data)[:n]
            if kind == "left" and name == "bkv":
                gd, wd = gd[ok], wd[ok]
            np.testing.assert_array_equal(gd, wd, err_msg=(kind, name))

    bk, bv = (np.asarray(build.columns[c].data) for c in ("bk", "bkv"))
    bok = np.asarray(build.columns["bk"].validity)
    first = {}
    for i in range(int(build.length)):
        if bok[i]:
            first.setdefault(int(bk[i]), int(bv[i]))
    pk = np.asarray(probe.columns["k"].data)
    pok = np.asarray(probe.columns["k"].validity)
    want_inner = [(int(k), first[int(k)])
                  for k, ok in zip(pk[:int(probe.length)],
                                   pok[:int(probe.length)])
                  if ok and int(k) in first]
    inner = got["inner"][0]
    n = int(inner.length)
    assert list(zip(np.asarray(inner.columns["k"].data)[:n].tolist(),
                    np.asarray(inner.columns["bkv"].data)[:n].tolist())
                ) == want_inner


@pytest.mark.parametrize("shape", ("two_keys", "small_probe", "one_key"))
def test_only_one_integer_key_into_a_smaller_build_compiles_the_table(shape):
    """The static rule: a two-column key and a probe smaller than its
    build compile today's program alone, no conditional and no scatter
    into a table; one integer key into a build no larger than the
    probe compiles both branches."""
    import jax

    two = dtypes.schema(("a", dtypes.INT64), ("b", dtypes.INT64),
                        ("v", dtypes.INT64))
    cols = {n: np.arange(1000) for n in ("a", "b", "v")}
    blk = lambda cap: TableBlock.from_numpy(cols, two, None, cap)
    probe, build = (blk(PROBE_CAP), blk(BUILD_CAP))
    keys = ("a",)
    if shape == "two_keys":
        keys = ("a", "b")
    elif shape == "small_probe":
        probe, build = build, probe
    slots = jk._direct_slots([build.columns[k] for k in keys],
                             probe.capacity, build.capacity)
    text = jax.jit(
        lambda p, b: jk._lookup_join_of_kind.__wrapped__(
            p, b, keys, keys, (), "", "semi")).lower(probe, build).as_text()
    branches = text.count("stablehlo.case") + text.count("stablehlo.if")
    if shape == "one_key":
        assert slots == SLOTS and branches == 1 and "scatter" in text
    else:
        assert slots is None and branches == 0 and "scatter" not in text


def test_dense_and_sparse_builds_share_one_program():
    """A dense build takes the table, a sparse one of the same shapes
    the search, in the program the first built: nothing compiles for
    the second."""
    dense = _side("bk", np.arange(1, 700))
    sparse = _side("bk", np.arange(1, 700) * 1000)
    probe = _side("k", np.arange(0, 1500) * 3, capacity=PROBE_CAP, seed=1)
    run = lambda build: jk.run_equi_join_path(
        probe, build, ["k"], ["bk"], kind="left", payload=["bkv"])
    _, direct = run(dense)
    size = jk._lookup_join_of_kind._cache_size()
    out, search = run(sparse)
    assert bool(direct) and not bool(search)
    assert jk._lookup_join_of_kind._cache_size() == size
    found = np.asarray(out.columns["bkv"].validity)[:1500]
    assert found.sum() == len(set(range(0, 4500, 3))
                              & set(range(1000, 700000, 1000)))
