"""``kernels.compact``: a block's kept rows moved to the front by a
prefix count and log2(capacity) rounds of shift-and-select (PR 36),
held to numpy's ``a[mask]`` on the live prefix."""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ydb_tpu import dtypes
from ydb_tpu.blocks import TableBlock
from ydb_tpu.blocks.block import Column
from ydb_tpu.ssa import kernels

CAPACITIES = (1, 7, 1024, 5000, 65539)
SELECTIVITIES = (0, 0.1, 0.54, 1)

_compact = jax.jit(kernels.compact)


def _full_block(arrays, schema, validity, length):
    """A block whose every slot holds data, ``length`` of them live: the
    rows beyond are what a filter's mask must not let through."""
    capacity = len(next(iter(arrays.values())))
    blk = TableBlock.from_numpy(arrays, schema, validity, capacity=capacity)
    assert blk.capacity == capacity
    return TableBlock(blk.columns, jnp.int32(length), blk.schema)


def _bits(a):
    a = np.asarray(a)
    return a.view(f"u{a.dtype.itemsize}") if a.dtype.kind == "f" else a


def _check(out, arrays, validity, keep):
    n = int(out.length)
    assert n == int(keep.sum())
    for name, src in arrays.items():
        col = out.columns[name]
        assert col.data.dtype == src.dtype, name
        assert col.data.shape == col.validity.shape == src.shape
        assert (_bits(col.data)[:n] == _bits(src)[keep]).all(), name
        got = np.asarray(col.validity)
        assert (got[:n] == validity[name][keep]).all(), name
        assert not got[n:].any(), name


@pytest.mark.parametrize("selectivity", SELECTIVITIES)
@pytest.mark.parametrize("capacity", CAPACITIES)
def test_compact_is_numpys_mask_on_the_live_prefix(capacity, selectivity):
    """int64 and int32 columns with NULLs, over capacities that are one
    row, under one prefix block, a whole number of them and not, and
    past 2^16; the block part live, ``selected`` random over ALL slots,
    so it is True beyond ``length`` too and must be dropped there."""
    rng = np.random.default_rng(capacity * 7 + int(selectivity * 100))
    length = int(rng.integers(0, capacity + 1))
    arrays = {"a": rng.integers(-2 ** 62, 2 ** 62, capacity),
              "b": rng.integers(-2 ** 31, 2 ** 31 - 1, capacity).astype(
                  np.int32)}
    validity = {"a": rng.random(capacity) < 0.8,
                "b": rng.random(capacity) < 0.5}
    schema = dtypes.schema(("a", dtypes.INT64), ("b", dtypes.INT32))
    selected = rng.random(capacity) < selectivity
    blk = _full_block(arrays, schema, validity, length)
    out = _compact(blk, jnp.asarray(selected))
    _check(out, arrays, validity, selected & (np.arange(capacity) < length))


def _column_of(kind, n, rng):
    if kind == "int64":
        return rng.integers(-2 ** 63, 2 ** 63 - 1, n), dtypes.INT64
    if kind == "int32":
        return (rng.integers(-2 ** 31, 2 ** 31 - 1, n).astype(np.int32),
                dtypes.INT32)
    if kind == "uint32":
        return (rng.integers(0, 2 ** 32 - 1, n).astype(np.uint32),
                dtypes.UINT32)
    if kind == "float32":
        f = rng.standard_normal(n).astype(np.float32)
        f[::5] = np.nan
        f[1::7] = -0.0
        f[2::11] = np.float32("-inf")
        # a NaN with a payload: moved by bits, not by value
        f[3::13] = np.array([0x7FC00123], np.uint32).view(np.float32)[0]
        return f, dtypes.FLOAT
    if kind == "bool":
        return rng.random(n) < 0.5, dtypes.BOOL
    assert kind == "string"          # a dictionary id on the device
    return rng.integers(0, 40, n).astype(np.int32), dtypes.STRING


@pytest.mark.parametrize("nulls", (False, True))
@pytest.mark.parametrize("kind", ("int64", "int32", "uint32", "float32",
                                  "bool", "string"))
def test_compact_moves_every_physical_type_by_its_bits(kind, nulls):
    capacity = 3000
    rng = np.random.default_rng(len(kind) + 10 * nulls)
    data, typ = _column_of(kind, capacity, rng)
    assert data.dtype == typ.physical
    arrays = {"c": data, "row": np.arange(capacity)}
    validity = {"c": (rng.random(capacity) < 0.6 if nulls
                      else np.ones(capacity, bool)),
                "row": np.ones(capacity, bool)}
    schema = dtypes.schema(("c", typ), ("row", dtypes.INT64))
    selected = rng.random(capacity) < 0.54
    length = capacity - 17
    out = _compact(_full_block(arrays, schema, validity, length),
                   jnp.asarray(selected))
    _check(out, arrays, validity, selected & (np.arange(capacity) < length))
    assert out.schema == schema


@pytest.mark.parametrize("columns", (32, 33, 65))
def test_compact_carries_more_validities_than_one_word_holds(columns):
    """ClickBench-width tables: a validity is one bit of a uint32 word,
    the 33rd column starts a second word, the 65th a third."""
    capacity = 2048
    rng = np.random.default_rng(columns)
    names = [f"c{i}" for i in range(columns)]
    arrays = {n: rng.integers(0, 1000, capacity).astype(np.int32)
              for n in names}
    validity = {n: rng.random(capacity) < 0.5 for n in names}
    schema = dtypes.schema(*[(n, dtypes.INT32) for n in names])
    selected = rng.random(capacity) < 0.54
    out = _compact(_full_block(arrays, schema, validity, capacity - 5),
                   jnp.asarray(selected))
    _check(out, arrays, validity,
           selected & (np.arange(capacity) < capacity - 5))


@pytest.mark.parametrize("pattern", ("first", "last", "evens", "tail_half",
                                     "head_half"))
def test_compact_at_the_shifts_extremes(pattern):
    """A row that travels capacity - 1 slots sets every bit of its
    distance; one that travels none sets no bit."""
    capacity = 4096
    selected = np.zeros(capacity, bool)
    selected[{"first": slice(0, 1), "last": slice(capacity - 1, None),
              "evens": slice(0, None, 2),
              "tail_half": slice(capacity // 2, None),
              "head_half": slice(0, capacity // 2)}[pattern]] = True
    arrays = {"a": np.arange(capacity) * 3 + 1}
    validity = {"a": np.arange(capacity) % 3 != 0}
    out = _compact(
        _full_block(arrays, dtypes.schema(("a", dtypes.INT64)), validity,
                    capacity), jnp.asarray(selected))
    _check(out, arrays, validity, selected)


@pytest.mark.parametrize("capacity,rounds", ((1, 0), (2, 1), (1024, 10),
                                             (1025, 11), (65539, 17)))
def test_compact_makes_a_round_a_bit_of_the_capacity(capacity, rounds):
    """One algorithm whose only parameter is the shape it sees: ONE loop
    of a round a bit of capacity - 1 (none at capacity 1), and nothing
    that sorts, gathers or scatters."""
    blk = _full_block({"a": np.arange(capacity)},
                      dtypes.schema(("a", dtypes.INT64)),
                      {"a": np.ones(capacity, bool)}, capacity)
    mask = jnp.zeros(capacity, bool)
    loops = [e.params["length"]
             for e in jax.make_jaxpr(kernels.compact)(blk, mask).jaxpr.eqns
             if e.primitive.name == "scan"]
    assert loops == [rounds]
    text = jax.jit(kernels.compact).lower(blk, mask).as_text()
    assert not re.search(r"stablehlo\.\w*(sort|gather|scatter)", text)


def test_compact_under_shard_map_as_the_exchange_calls_it():
    """``shuffle.repartition`` compacts what it received, inside
    ``shard_map``: each device its own block, mask and length."""
    from ydb_tpu.parallel.dist import _local, _relocal, stack_blocks
    from ydb_tpu.parallel.mesh import SHARD_AXIS, make_mesh, shard_map

    n_dev, capacity = 4, 1536
    mesh = make_mesh(n_dev, devices=jax.devices()[:n_dev])
    rng = np.random.default_rng(36)
    schema = dtypes.schema(("k", dtypes.INT64), ("s", dtypes.STRING))
    sides = []
    for d in range(n_dev):
        arrays = {"k": rng.integers(0, 10 ** 12, capacity),
                  "s": rng.integers(0, 9, capacity).astype(np.int32)}
        validity = {"k": rng.random(capacity) < 0.9,
                    "s": rng.random(capacity) < 0.7}
        length = capacity - 100 * d
        sides.append((arrays, validity, length,
                      rng.random(capacity) < (0, 0.1, 0.54, 1)[d]))
    stacked = stack_blocks([_full_block(a, schema, v, n)
                            for a, v, n, _ in sides])
    masks = jnp.stack([jnp.asarray(m) for *_, m in sides])
    sharding = NamedSharding(mesh, P(SHARD_AXIS))
    fn = jax.jit(shard_map(
        lambda st, m: _relocal(kernels.compact(_local(st), m[0])),
        mesh=mesh, in_specs=P(SHARD_AXIS), out_specs=P(SHARD_AXIS),
        check_vma=False))
    out = fn(jax.device_put(stacked, sharding),
             jax.device_put(masks, sharding))
    for d, (arrays, validity, length, selected) in enumerate(sides):
        one = TableBlock(
            {n: Column(c.data[d], c.validity[d])
             for n, c in out.columns.items()}, out.length[d], out.schema)
        _check(one, arrays, validity,
               selected & (np.arange(capacity) < length))
