"""The output key columns of a sort-derived group-by above the one-hot
tier (``key_tier == "segment"``): ``group_ids_sorted`` already holds the
keys in sorted order and the first row of every group, so the lowering
takes group ``g``'s key from the ``g``-th segment head by one
``kernels.compact`` where it used to scatter every row's key by its
group id. Every case is held to a plain numpy reference (groups in key
order, NULLs after the values of their key, one NULL group a key) and to
the shape of the lowered program: no scatter but the reduce's adds, the
permutation's inverse and the first row's flag."""

import collections

import jax
import numpy as np
import pytest

from ydb_tpu import dtypes
from ydb_tpu.blocks import TableBlock
from ydb_tpu.ssa import (
    AggSpec,
    Col,
    FilterStep,
    GroupByStep,
    Program,
    compile_program,
    kernels,
)
from ydb_tpu.ssa.ops import Agg

ROWS = 1400
CAPACITY = 1536         # above ONEHOT_GROUP_LIMIT slots, dead ones behind

TYPES = {"a": dtypes.INT64, "b": dtypes.INT32, "c": dtypes.INT16,
         "d": dtypes.BOOL, "v": dtypes.INT64, "keep": dtypes.BOOL}


def draw(rng, name, n, distinct):
    if name == "d":
        return rng.random(n) < (0.5 if distinct > 1 else 2)
    if name == "a":     # both words of an int64 decide, and its sign
        pool = rng.integers(-(1 << 62), 1 << 62, distinct)
    else:
        info = np.iinfo(TYPES[name].physical)
        pool = rng.integers(info.min, info.max, distinct, endpoint=True)
    return pool[rng.integers(0, distinct, n)].astype(TYPES[name].physical)


def garbage(rng, name, n):
    """What lies under a NULL: anything, and not alike."""
    if name == "d":
        return rng.random(n) < 0.5
    return rng.integers(-100, 100, n).astype(TYPES[name].physical)


def reference(cols, keys, keep, cap):
    """The groups of the kept rows in key order: per key its values
    ascending, then its one NULL group; the first ``cap`` of them."""
    groups = collections.defaultdict(lambda: [0, 0])
    for i in np.flatnonzero(keep):
        key = tuple((not cols[k][1][i],
                     int(cols[k][0][i]) if cols[k][1][i] else 0)
                    for k in keys)
        groups[key][0] += 1
        groups[key][1] += int(cols["v"][0][i])
    order = sorted(groups)[:cap]
    want = {k: (np.array([key[j][1] for key in order], dtype=np.int64),
                np.array([not key[j][0] for key in order], dtype=bool))
            for j, k in enumerate(keys)}
    want["n"] = (np.array([groups[key][0] for key in order], dtype=np.int64),
                 np.ones(len(order), dtype=bool))
    want["s"] = (np.array([groups[key][1] for key in order], dtype=np.int64),
                 np.ones(len(order), dtype=bool))
    return want


def plain_scatters(jaxpr):
    """Every scatter of the program that is no scatter-add, as
    (operand shape, operand dtype, updates shape)."""
    found = []
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name.startswith("scatter") and "add" not in name:
            found.append((eqn.invars[0].aval.shape,
                          str(eqn.invars[0].aval.dtype),
                          eqn.invars[2].aval.shape))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found.extend(plain_scatters(sub))
    return found


def block_of(cols, capacity):
    return TableBlock.from_numpy(
        {name: c[0] for name, c in cols.items()},
        dtypes.schema(*((name, TYPES[name]) for name in cols)),
        {name: c[1] for name, c in cols.items()}, capacity=capacity)


def run(cols, keys, capacity, max_groups=None):
    blk = block_of(cols, capacity)
    prog = Program((
        FilterStep(Col("keep")),
        GroupByStep(keys=keys, max_groups=max_groups,
                    aggs=(AggSpec(Agg.COUNT_ALL, None, "n"),
                          AggSpec(Agg.SUM, "v", "s")))))
    cp = compile_program(prog, blk.schema)
    out = jax.jit(cp.run)(blk, {})
    n = int(out.length)
    got = {}
    for name, c in out.columns.items():
        valid = np.asarray(c.validity)
        assert not valid[n:].any(), name
        # what a NULL's slot holds is not part of the answer
        got[name] = (np.where(valid[:n], np.asarray(c.data)[:n],
                              0).astype(np.int64), valid[:n])
    return got, dict(cp.notes), plain_scatters(
        jax.make_jaxpr(cp.run)(blk, {}).jaxpr)


def check(cols, keys, keep, capacity=CAPACITY, max_groups=None):
    cols = dict(cols, keep=(keep, np.ones(len(keep), dtype=bool)))
    got, notes, scatters = run(cols, keys, capacity, max_groups)
    slots = min(capacity, max_groups or capacity)
    assert slots > kernels.ONEHOT_GROUP_LIMIT
    assert notes["key_tier"] == "segment"
    assert notes["groups"] == slots and notes["reduce_tier"] == "scatter"
    want = reference(cols, keys, keep, slots)
    assert list(got) == list(keys) + ["n", "s"]
    for name in want:
        assert np.array_equal(got[name][0], want[name][0]), name
        assert np.array_equal(got[name][1], want[name][1]), name
    # what is left of the scatters however many keys there are: the
    # inverse of the sort's permutation and the first row's boundary
    # flag (the parent added two of ``slots`` rows a key column: the
    # key's data and its validity by group id)
    assert sorted(scatters) == sorted([
        ((capacity,), "int32", (capacity,)),
        ((capacity,), "bool", ())]), scatters
    return got


def columns(rng, keys, n, distinct, nullable=()):
    cols = {}
    for k in keys:
        data = draw(rng, k, n, distinct)
        valid = np.ones(n, dtype=bool)
        if k in nullable:
            valid = rng.random(n) > 0.15
            data = np.where(valid, data, garbage(rng, k, n))
        cols[k] = (data, valid)
    cols["v"] = (rng.integers(-1000, 1000, n), np.ones(n, dtype=bool))
    return cols


KEYS = (("a",), ("b",), ("c",), ("a", "b"), ("c", "d"), ("b", "a"),
        ("a", "b", "c"), ("d", "c", "a"), ("c", "b", "d"))


@pytest.mark.parametrize("keys", KEYS, ids="-".join)
def test_the_keys_of_a_sorted_layout_are_its_segment_heads(keys):
    rng = np.random.default_rng(40 + len(keys))
    cols = columns(rng, keys, ROWS, 90)
    got = check(cols, keys, np.ones(ROWS, dtype=bool))
    assert 40 < len(got["n"][0]) < ROWS


@pytest.mark.parametrize("keys,nullable", (
    (("a",), ("a",)), (("b", "d"), ("b", "d")), (("a", "c"), ("c",)),
    (("c", "a", "b"), ("c", "b")), (("d", "a", "c"), ("d", "a", "c"))),
    ids=lambda v: "-".join(v))
def test_nulls_with_garbage_beneath_them_are_one_group_a_key(keys, nullable):
    rng = np.random.default_rng(7 * len(keys) + len(nullable))
    cols = columns(rng, keys, ROWS, 12, nullable)
    got = check(cols, keys, np.ones(ROWS, dtype=bool))
    for k in nullable:
        assert not got[k][1].all() and got[k][1].any()
    if len(keys) == 1:
        assert (~got[keys[0]][1]).sum() == 1
        assert not got[keys[0]][1][-1]          # after the values


@pytest.mark.parametrize("keys", (("a",), ("b", "c"), ("a", "d", "b")),
                         ids="-".join)
@pytest.mark.parametrize("kept", (0.5, 0.02), ids=("half", "few"))
def test_dead_rows_among_the_live_ones_start_no_group(keys, kept):
    rng = np.random.default_rng(3)
    cols = columns(rng, keys, ROWS, 300, nullable=keys[-1:])
    keep = rng.random(ROWS) < kept
    assert keep.any() and not keep[:keep.sum()].all()     # no prefix
    got = check(cols, keys, keep)
    assert got["n"][0].sum() == keep.sum()


@pytest.mark.parametrize("keys", (("a",), ("c", "a")), ids="-".join)
def test_every_row_dead_gives_no_group(keys):
    rng = np.random.default_rng(5)
    cols = columns(rng, keys, ROWS, 50)
    got = check(cols, keys, np.zeros(ROWS, dtype=bool))
    assert len(got["n"][0]) == 0


@pytest.mark.parametrize("keys,nullable", (
    (("a",), ()), (("b", "d"), ()), (("a", "c"), ("a", "c"))),
    ids=lambda v: "-".join(v) or "not_null")
def test_one_group(keys, nullable):
    rng = np.random.default_rng(6)
    cols = columns(rng, keys, ROWS, 1)
    for k in nullable:      # the one group is the NULL group
        cols[k] = (garbage(rng, k, ROWS), np.zeros(ROWS, dtype=bool))
    got = check(cols, keys, np.ones(ROWS, dtype=bool))
    assert got["n"][0].tolist() == [ROWS]


@pytest.mark.parametrize("keys", (("a",), ("b", "d"), ("d", "a", "c")),
                         ids="-".join)
def test_as_many_groups_as_slots(keys):
    rng = np.random.default_rng(8)
    n = 1024
    cols = columns(rng, keys, n, 40)
    wide = max(keys, key=lambda k: np.dtype(TYPES[k].physical).itemsize)
    cols[wide] = (rng.permutation(n).astype(TYPES[wide].physical) - 300,
                  np.ones(n, dtype=bool))
    got = check(cols, keys, np.ones(n, dtype=bool), capacity=n)
    assert len(got["n"][0]) == n and (got["n"][0] == 1).all()


@pytest.mark.parametrize("keys,nullable", (
    (("a",), ()), (("b", "c"), ("b",)), (("a", "d", "c"), ("a",))),
    ids=lambda v: "-".join(v) or "not_null")
def test_a_cap_below_the_group_count_keeps_the_first_groups_in_key_order(
        keys, nullable):
    rng = np.random.default_rng(9)
    cols = columns(rng, keys, ROWS, 1200, nullable)
    keep = rng.random(ROWS) < 0.9
    got = check(cols, keys, keep, max_groups=600)
    assert len(got["n"][0]) == 600
    assert got["n"][0].sum() < keep.sum()       # the rest fell off


@pytest.mark.parametrize("layout", ("dense", "onehot", "keyless"))
def test_the_other_layouts_say_their_own_key_tier(layout):
    """``dense``: a bounded key space decodes the key from the slot
    number; ``onehot``: a sort-derived layout of at most
    ONEHOT_GROUP_LIMIT slots gathers each group's first row; a keyless
    aggregate has no key."""
    rng = np.random.default_rng(10)
    n = 400
    cols = columns(rng, ("b", "d"), n, 7)
    cols["keep"] = (np.ones(n, dtype=bool), np.ones(n, dtype=bool))
    keys = {"dense": ("d",), "onehot": ("b",), "keyless": ()}[layout]
    blk = block_of(cols, 512)
    cp = compile_program(Program((GroupByStep(
        keys=keys, aggs=(AggSpec(Agg.COUNT_ALL, None, "n"),)),)), blk.schema)
    out = jax.jit(cp.run)(blk, {})
    assert cp.notes.get("key_tier") == (
        None if layout == "keyless" else layout)
    assert int(np.asarray(out.columns["n"].data)[:int(out.length)].sum()) == n
