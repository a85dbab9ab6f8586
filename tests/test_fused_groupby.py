"""The group-by lowering against the independent numpy oracle.

Every case compiles its program and cross-checks the device answer
against engine/oracle.py, across dtypes, NULL patterns, decimals, and
all three group-id tiers (dense one-hot, sorted, and the
>ONEHOT_GROUP_LIMIT scatter/Pallas tier).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ydb_tpu import dtypes
from ydb_tpu.blocks import DictionarySet, TableBlock
from ydb_tpu.engine.oracle import OracleTable, run_oracle
from ydb_tpu.ssa import (
    Agg,
    AggSpec,
    GroupByStep,
    Program,
    compile_program,
)
from ydb_tpu.ssa import kernels, pallas_kernels


def _block(cols, validity=None):
    sch = []
    arrays = {}
    for name, (arr, t) in cols.items():
        sch.append((name, t))
        arrays[name] = np.asarray(arr)
    return TableBlock.from_numpy(
        arrays, dtypes.schema(*sch), validity or None)


def _run(prog, blk, dicts=None, key_spaces=None):
    cp = compile_program(prog, blk.schema, dicts, key_spaces)
    out = jax.jit(cp.run)(
        blk, {k: jnp.asarray(v) for k, v in cp.aux.items()})
    return out.host_columns()


def _run_oracle(prog, blk, dicts=None):
    data, valid = blk.host_columns()
    table = OracleTable(
        {n: (data[n], valid[n]) for n in data}, blk.schema)
    out = run_oracle(prog, table, dicts)
    return ({n: v[0] for n, v in out.cols.items()},
            {n: v[1] for n, v in out.cols.items()})


def _sorted_by(data, valid, keys):
    # NULL key groups carry arbitrary data under validity=False: align
    # rows by (validity, value) per key so both runs sort alike
    subkeys = []
    for k in reversed(keys):
        subkeys.append(np.asarray(data[k]))
        subkeys.append(np.asarray(valid[k]))
    return np.lexsort(tuple(subkeys))


def _assert_equivalent(prog, blk, dicts=None, key_spaces=None,
                       keys=("k",)):
    fd, fv = _run(prog, blk, dicts, key_spaces)
    od, ov = _run_oracle(prog, blk, dicts)
    for name in fd:
        f, fvv = np.asarray(fd[name]), np.asarray(fv[name])
        o, ovv = np.asarray(od[name]), np.asarray(ov[name])
        if keys:
            fo, oo = _sorted_by(fd, fv, keys), _sorted_by(od, ov, keys)
            f, fvv, o, ovv = f[fo], fvv[fo], o[oo], ovv[oo]
        np.testing.assert_array_equal(fvv, ovv,
                                      err_msg=f"oracle validity {name}")
        live = fvv
        # key columns under validity=False hold arbitrary padding;
        # SOME is "any valid value": only its validity is comparable
        if name.startswith("some_"):
            continue
        if np.issubdtype(f.dtype, np.integer) or f.dtype == bool:
            np.testing.assert_array_equal(
                f[live], o[live], err_msg=f"device vs oracle {name}")
        else:
            np.testing.assert_allclose(
                f[live], o[live], rtol=1e-9,
                err_msg=f"device vs oracle {name}")


_ALL_AGGS = (
    AggSpec(Agg.COUNT_ALL, None, "n"),
    AggSpec(Agg.SUM, "d", "sum_d"),
    AggSpec(Agg.SUM, "f", "sum_f"),
    AggSpec(Agg.SUM, "i", "sum_i"),
    AggSpec(Agg.AVG, "d", "avg_d"),
    AggSpec(Agg.AVG, "f", "avg_f"),
    AggSpec(Agg.COUNT, "i", "cnt_i"),
    AggSpec(Agg.MIN, "i", "min_i"),
    AggSpec(Agg.MAX, "f", "max_f"),
    AggSpec(Agg.VAR_SAMP, "f", "var_f"),
    AggSpec(Agg.STDDEV_SAMP, "d", "std_d"),
    AggSpec(Agg.SOME, "i", "some_i"),
)


def _mixed_block(n=4000, nulls=True, seed=11, key_vals=5):
    rng = np.random.default_rng(seed)
    cols = {
        "k": (rng.integers(0, key_vals, n).astype(np.int64),
              dtypes.INT64),
        "d": (rng.integers(-(10 ** 6), 10 ** 6, n).astype(np.int64),
              dtypes.decimal(2)),
        "f": (rng.normal(50.0, 9.0, n), dtypes.DOUBLE),
        "i": (rng.integers(-1000, 1000, n).astype(np.int64),
              dtypes.INT64),
    }
    validity = None
    if nulls:
        validity = {
            "d": rng.random(n) > 0.15,
            "f": rng.random(n) > 0.05,
            "i": rng.random(n) > 0.5,
        }
    return _block(cols, validity)


@pytest.mark.parametrize("nulls", [False, True])
def test_dense_tier_all_aggs(nulls):
    blk = _mixed_block(nulls=nulls)
    prog = Program((GroupByStep(("k",), _ALL_AGGS),))
    _assert_equivalent(prog, blk, key_spaces={"k": 5})


@pytest.mark.parametrize("nulls", [False, True])
def test_sorted_tier_all_aggs(nulls):
    # no key_spaces bound -> lexicographic-sort group ids
    blk = _mixed_block(nulls=nulls, key_vals=37)
    prog = Program((GroupByStep(("k",), _ALL_AGGS),))
    _assert_equivalent(prog, blk)


def test_null_group_key():
    rng = np.random.default_rng(5)
    n = 2000
    blk = _block(
        {"k": (rng.integers(0, 4, n).astype(np.int64), dtypes.INT64),
         "i": (rng.integers(0, 100, n).astype(np.int64), dtypes.INT64)},
        {"k": rng.random(n) > 0.3, "i": np.ones(n, dtype=bool)},
    )
    prog = Program((GroupByStep(
        ("k",),
        (AggSpec(Agg.COUNT_ALL, None, "n"),
         AggSpec(Agg.SUM, "i", "s"),
         AggSpec(Agg.MIN, "i", "lo"))),))
    # NULL keys form their own group in both tiers
    _assert_equivalent(prog, blk, key_spaces={"k": 4})
    _assert_equivalent(prog, blk)


def test_string_keys_and_string_minmax():
    dicts = DictionarySet()
    d = dicts.for_column("s")
    rng = np.random.default_rng(9)
    n = 3000
    ids = d.encode([b"pear", b"apple", b"fig", b"plum"])
    blk = _block(
        {"s": (rng.choice(ids, n), dtypes.STRING),
         "v": (rng.integers(0, 50, n).astype(np.int64), dtypes.INT64)},
    )
    prog = Program((GroupByStep(
        ("s",),
        (AggSpec(Agg.COUNT_ALL, None, "n"),
         AggSpec(Agg.MIN, "s", "first_s"),
         AggSpec(Agg.MAX, "s", "last_s"),
         AggSpec(Agg.SUM, "v", "sv"))),))
    _assert_equivalent(prog, blk, dicts=dicts, keys=("s",))


def test_keyless_global_aggregate():
    blk = _mixed_block(n=1500)
    prog = Program((GroupByStep((), _ALL_AGGS),))
    _assert_equivalent(prog, blk, keys=())


def test_large_group_scatter_tier():
    # > ONEHOT_GROUP_LIMIT dense groups: the 2D scatter (or Pallas)
    # tier instead of the masked sums over the hit matrix
    rng = np.random.default_rng(3)
    n, k = 20_000, 700
    assert k > kernels.ONEHOT_GROUP_LIMIT
    blk = _block(
        {"k": (rng.integers(0, k, n).astype(np.int64), dtypes.INT64),
         "d": (rng.integers(0, 10 ** 6, n).astype(np.int64),
               dtypes.decimal(2)),
         "f": (rng.normal(0, 5, n), dtypes.DOUBLE)},
        {"d": rng.random(n) > 0.1, "f": np.ones(n, dtype=bool)},
    )
    prog = Program((GroupByStep(
        ("k",),
        (AggSpec(Agg.COUNT_ALL, None, "n"),
         AggSpec(Agg.SUM, "d", "sd"),
         AggSpec(Agg.AVG, "f", "af"),
         AggSpec(Agg.MAX, "d", "hi"))),))
    _assert_equivalent(prog, blk, key_spaces={"k": k})


def test_pallas_fused_multi_matches_scatter_tier():
    # the fused multi-column tile kernel (interpret mode on CPU) against
    # the 2D scatter fallback of fused_group_reduce
    rng = np.random.default_rng(8)
    n, k, s = 5000, 900, 6
    vals = jnp.asarray(rng.integers(0, 1000, (n, s)), dtype=jnp.float32)
    gid = jnp.asarray(rng.integers(0, k + 25, n), dtype=jnp.int32)
    ref = kernels.fused_group_reduce(vals, gid, k, dtype=jnp.float32)
    got = pallas_kernels.grouped_sum_multi(vals, gid, k, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-6)


def test_decimal_sum_exactness_via_limb_split():
    # values whose f64 accumulation would round ((2^50 + 1) * 1024 is
    # past 2^53): the int64 decimal sums must stay bit-exact
    n = 1024
    big = (1 << 50) + 1
    prog = Program((GroupByStep(
        ("k",), (AggSpec(Agg.SUM, "d", "s"),)),))
    # negative values exercise the sign
    for v in (big, -big):
        blk = _block(
            {"k": (np.zeros(n, dtype=np.int64), dtypes.INT64),
             "d": (np.full(n, v, dtype=np.int64), dtypes.decimal(2))},
        )
        fd, _ = _run(prog, blk, key_spaces={"k": 1})
        assert int(fd["s"][0]) == n * v


def test_nullable_flag_does_not_change_results():
    # identical data, schema declared nullable vs non-nullable: the
    # lowering's static count/mask collapse must be invisible
    rng = np.random.default_rng(2)
    n = 3000
    k = rng.integers(0, 6, n).astype(np.int64)
    v = rng.integers(0, 10 ** 5, n).astype(np.int64)
    specs = (AggSpec(Agg.COUNT_ALL, None, "n"),
             AggSpec(Agg.SUM, "v", "s"),
             AggSpec(Agg.AVG, "v", "a"),
             AggSpec(Agg.COUNT, "v", "c"))
    prog = Program((GroupByStep(("k",), specs),))
    outs = {}
    for nullable in (False, True):
        sch = dtypes.Schema((
            dtypes.Field("k", dtypes.INT64, nullable),
            dtypes.Field("v", dtypes.INT64, nullable),
        ))
        blk = TableBlock.from_numpy({"k": k, "v": v}, sch)
        outs[nullable], _ = _run(prog, blk, key_spaces={"k": 6})
    order0 = np.argsort(outs[False]["k"])
    order1 = np.argsort(outs[True]["k"])
    for name in outs[False]:
        np.testing.assert_array_equal(
            np.asarray(outs[False][name])[order0],
            np.asarray(outs[True][name])[order1], err_msg=name)


def _numpy_group_sums(vals, gid, k):
    live = gid < k
    want = np.zeros((k, vals.shape[1]), dtype=vals.dtype)
    np.add.at(want, gid[live], vals[live])
    return want


def test_onehot_tier_off_the_gemm_is_bit_exact():
    """The one-hot tier reduces on the vector unit, not on a GEMM (on a
    TPU an f64 dot runs as f32 MXU passes and an s64 dot does not
    exist). Integer banks must equal numpy int64 bit for bit, including
    sums past 2^32, which is where the chip's f64 dot went wrong, and
    float banks to rounding."""
    rng = np.random.default_rng(11)
    n, k = 4096, 7
    ints = rng.integers(-(1 << 40), 1 << 50, (n, 5)).astype(np.int64)
    flts = rng.random((n, 3)) * 1e6
    gid = rng.integers(0, k + 1, n).astype(np.int32)
    np.testing.assert_array_equal(
        np.asarray(kernels.fused_group_reduce(
            jnp.asarray(ints), jnp.asarray(gid), k)),
        _numpy_group_sums(ints, gid, k))
    np.testing.assert_allclose(
        np.asarray(kernels.fused_group_reduce(
            jnp.asarray(flts), jnp.asarray(gid), k)),
        _numpy_group_sums(flts, gid, k), rtol=1e-12)


def _tier_case(dtype, n, k, seed):
    rng = np.random.default_rng(seed)
    if dtype == "int64":
        # three slots near 2^62 / n: every group's sum stays inside
        # int64 and far outside f64's 2^53 integers
        hi = (1 << 62) // n
        vals = rng.integers(hi // 2, hi, (n, 3)).astype(np.int64)
        vals[::2] *= -1
    elif dtype == "int32":
        vals = rng.integers(-1000, 1000, (n, 3)).astype(np.int32)
    else:
        vals = rng.normal(0.0, 1e6, (n, 3))
    # ids in [0, k]: k is the drop slot of dead rows
    gid = rng.integers(0, k + 1, n).astype(np.int32)
    return vals, gid


@pytest.mark.parametrize("k", [1, 12, 512])
@pytest.mark.parametrize("dtype", ["int64", "int32", "float64"])
def test_onehot_tier_exact(dtype, k):
    """The tier every TPC-H Q1/Q6 partial, combine and final program
    takes, against numpy: integers bit for bit, floats to rounding."""
    assert k <= kernels.ONEHOT_GROUP_LIMIT
    vals, gid = _tier_case(dtype, 2048, k, seed=k)
    got = np.asarray(kernels.fused_group_reduce(
        jnp.asarray(vals), jnp.asarray(gid), k))
    want = _numpy_group_sums(vals, gid, k)
    assert got.dtype == want.dtype
    if dtype == "float64":
        np.testing.assert_allclose(got, want, rtol=1e-9, atol=1e-3)
    else:
        np.testing.assert_array_equal(got, want)


def test_tier_boundary_agrees(monkeypatch):
    """512 groups take the masked sums, 513 the scatter: the same rows
    (none in the 513th group) must give the same sums either side."""
    monkeypatch.setattr(pallas_kernels, "FORCE", False)
    k = kernels.ONEHOT_GROUP_LIMIT
    rng = np.random.default_rng(21)
    n = 8192
    ints = rng.integers(-(1 << 50), 1 << 50, (n, 4)).astype(np.int64)
    flts = rng.normal(0.0, 1e3, (n, 2))
    gid = rng.integers(0, k, n).astype(np.int32)
    for vals in (ints, flts):
        lo = np.asarray(kernels.fused_group_reduce(
            jnp.asarray(vals), jnp.asarray(gid), k))
        hi = np.asarray(kernels.fused_group_reduce(
            jnp.asarray(vals), jnp.asarray(gid), k + 1))
        assert not hi[k].any()
        if vals.dtype == np.int64:
            np.testing.assert_array_equal(lo, hi[:k])
            np.testing.assert_array_equal(
                lo, _numpy_group_sums(vals, gid, k))
        else:
            np.testing.assert_allclose(lo, hi[:k], rtol=1e-9, atol=1e-6)
