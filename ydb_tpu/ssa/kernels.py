# ydb-devmem: device-module — pure jnp kernels: every body runs under
# the compiled program trace (XLA temporaries, not HBM residents)
"""Device kernel primitives for SSA programs (pure jnp — XLA fuses these).

TPU analog of the reference's block operators:
  * masked elementwise ops with Arrow null semantics
    (arrow compute + ydb/library/arrow_kernels/operations.h)
  * ``compact`` — BlockCompress (mkql_block_compress.h): row compaction by
    stable-partition permutation, applied only at block boundaries
  * ``grouped_aggregate`` — BlockCombineHashed / ch.group_by
    (mkql_block_agg.cpp:1637, arrow_clickhouse/Aggregator.h:568): dense or
    sort-derived group ids + scatter-reduce with a *static* group capacity;
    invalid rows scatter to an out-of-bounds index in 'drop' mode instead
    of branching
  * ``sort_block`` — WideTopSort / BlockTop (mkql_block_top.cpp): a
    stable sort of the whole block, one pass a 32-bit word of the keys;
    under a LIMIT far below the capacity (``sort_tier``:
    ``limit * TOPK_ROOM <= capacity``, a key, none floating) a top-k:
    an exact radix selection of the ``limit`` first rows of that order
    (``_select_first``), then the sort of those rows alone
  * ``rollup`` — GROUP BY ROLLUP's levels from its finest grouping, each
    from the one above it by runs of equal key prefixes (no sort);
    ``window_rank`` — rank / dense_rank / row_number, NULL keys as SQL
    has them

All primitives keep static shapes; "how many" results there are is always a
traced int32 scalar, never a shape.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ydb_tpu.blocks.block import Column, TableBlock

# ---------------- null-propagating elementwise ----------------


def binop(fn, a: Column, b: Column) -> Column:
    return Column(fn(a.data, b.data), a.validity & b.validity)


def unop(fn, a: Column) -> Column:
    return Column(fn(a.data), a.validity)


def kleene_and(a: Column, b: Column) -> Column:
    data = a.data & b.data
    # false AND anything = false (valid); else valid iff both valid
    valid = (
        (~a.data & a.validity) | (~b.data & b.validity) | (a.validity & b.validity)
    )
    return Column(data, valid)


def kleene_or(a: Column, b: Column) -> Column:
    data = a.data | b.data
    valid = (
        (a.data & a.validity) | (b.data & b.validity) | (a.validity & b.validity)
    )
    return Column(data, valid)


def safe_div(a: Column, b: Column, float_result: bool) -> Column:
    zero = b.data == 0
    denom = jnp.where(zero, jnp.ones_like(b.data), b.data)
    if float_result:
        data = a.data / denom
    else:
        data = _trunc_div(a.data, denom)
    return Column(data, a.validity & b.validity & ~zero)


def _trunc_div(a, b):
    """SQL integer division truncates toward zero (-7/2 = -3), unlike
    Python/jnp floor division (-7//2 = -4)."""
    q = a // b
    exact = a - q * b == 0
    neg = (a < 0) ^ (b < 0)
    return jnp.where(~exact & neg, q + 1, q)


def trunc_mod(a, b):
    """SQL remainder takes the dividend's sign: -7 % 2 = -1."""
    return a - b * _trunc_div(a, b)


def pred_mask(col: Column) -> jax.Array:
    """Boolean predicate -> selection mask (NULL counts as False)."""
    return col.data & col.validity


def dict_gather(table: jax.Array, ids: Column) -> Column:
    """Lookup a plan-time table (dictionary mask/rank) by string ids."""
    safe = jnp.clip(ids.data, 0, table.shape[0] - 1)
    return Column(table[safe], ids.validity)


# ---------------- calendar (branchless civil-from-days) ----------------


def civil_from_days(days):
    """days since 1970-01-01 -> (year, month, day), vectorized int32 math."""
    z = days.astype(jnp.int64) + 719468
    era = jnp.floor_divide(z, 146097)
    doe = z - era * 146097
    yoe = (doe - doe // 1460 + doe // 36524 - doe // 146096) // 365
    y = yoe + era * 400
    doy = doe - (365 * yoe + yoe // 4 - yoe // 100)
    mp = (5 * doy + 2) // 153
    d = doy - (153 * mp + 2) // 5 + 1
    m = jnp.where(mp < 10, mp + 3, mp - 9)
    y = jnp.where(m <= 2, y + 1, y)
    return y.astype(jnp.int32), m.astype(jnp.int32), d.astype(jnp.int32)


def days_from_civil(y, m, d):
    """(year, month, day) -> days since 1970-01-01 (inverse of
    civil_from_days; Hinnant's algorithm, vectorized)."""
    y = y.astype(jnp.int64) - (m <= 2)
    era = jnp.floor_divide(y, 400)
    yoe = y - era * 400
    doy = (153 * jnp.where(m > 2, m - 3, m + 9) + 2) // 5 + d - 1
    doe = yoe * 365 + yoe // 4 - yoe // 100 + doy
    return era * 146097 + doe - 719468


# ---------------- filter / compact ----------------


@jax.named_scope("ydb.apply_filter")
def apply_filter(block: TableBlock, mask: jax.Array) -> TableBlock:
    """The rows ``mask`` keeps, moved to the front by ``compact`` (the
    block's length becomes their count). A consumer that can work under
    a mask (an aggregation, a sort) keeps the mask and skips this."""
    return compact(block, mask)


def stable_argsort(key: jax.Array) -> jax.Array:
    """``argsort(key, stable=True)`` of a 32-bit key with int32 row
    numbers: ``jnp.argsort`` carries an int64 iota through the sort
    under x64, a 64-bit operand the TPU emulates."""
    rows = jnp.arange(key.shape[0], dtype=jnp.int32)
    return jax.lax.sort((key, rows), num_keys=1, is_stable=True)[1]


def stable_partition(last: jax.Array, classes: int = 2) -> jax.Array:
    """The permutation that orders rows by a small class number
    (``last``: bool, or int in [0, classes)), rows of one class in
    their own order: what ``argsort(last, stable=True)`` answers, as
    ONE single-operand sort of ``class << shift | row``. It serves the
    exchange's bucket sort (parallel/shuffle.py) and a flag's pass of
    ``stable_lexsort``; ``compact`` moves its rows without a
    permutation since PR 36. The keys are
    distinct, so the sort needs neither a second operand nor stability,
    and XLA's TPU sort, which is what a cold start pays for (ROADMAP
    S10), compiles it in a tenth of the time of the stable (class, row)
    pair (for a described v5e at 2^20 rows: 5.8 s against 55 s)."""
    rows = last.shape[0]
    shift = 32 - (classes - 1).bit_length()
    if rows > (1 << shift):
        return stable_argsort(last.astype(jnp.int32))
    key = (last.astype(jnp.uint32) << shift) | jnp.arange(
        rows, dtype=jnp.uint32)
    return (jax.lax.sort(key, is_stable=False)
            & jnp.uint32((1 << shift) - 1)).astype(jnp.int32)


def _words32(key: jax.Array) -> list[jax.Array]:
    """A sort key as the words one orders it by, least significant
    first: a flag as it is, a 64-bit integer as its low (unsigned) then
    its high word, anything narrower as one int32 or uint32."""
    if key.dtype == jnp.bool_:
        return [key]
    signed = jnp.issubdtype(key.dtype, jnp.signedinteger)
    if key.dtype.itemsize == 8:
        return [(key & 0xFFFFFFFF).astype(jnp.uint32),
                (key >> 32).astype(jnp.int32 if signed else jnp.uint32)]
    return [key.astype(jnp.int32 if signed else jnp.uint32)]


def stable_lexsort(keys) -> jax.Array:
    """``jnp.lexsort(keys)`` (the LAST key is primary, equal rows keep
    their order) as one stable pass a 32-bit word of a key, from the
    least significant: a flag by ``stable_partition``, a 64-bit integer
    as its low then its high word. XLA's TPU sort compiles by its
    operands: Q3's group-by over three keys, their validities and the
    live flag (seven operands and the row numbers) took 26 s at 8,192
    rows for a described v5e and minutes at 32,768, the passes 2 s and
    16 s (PERF.md section 6, PR 35). A floating key keeps the one
    comparator sort: its order has NaNs and signed zeros."""
    if any(jnp.issubdtype(k.dtype, jnp.floating) for k in keys):
        return jnp.lexsort(tuple(keys)).astype(jnp.int32)
    order = None
    for key in keys:
        for word in _words32(key):
            if order is not None:
                word = word[order]
            step = (stable_partition(word) if word.dtype == jnp.bool_
                    else stable_argsort(word))
            order = step if order is None else order[step]
    return order


#: the width of the prefix count's first level: a 1-D ``jnp.cumsum`` at
#: 2^20 rows takes XLA's TPU compiler 22.7 s to build, rows of 1,024
#: and then their totals 1.6 s (ROADMAP S10)
PREFIX_BLOCK = 1024
#: validities ride through ``compact`` as one bit each of a uint32 word
VALIDITY_WORD_BITS = 32


def _rejected_before(keep: jax.Array) -> jax.Array:
    """int32[capacity]: the rows before each row that ``keep`` rejects,
    by a two-level prefix sum (within rows of PREFIX_BLOCK, then over
    the rows' totals), the capacity padded to a whole row."""
    capacity = keep.shape[0]
    padded = -(-capacity // PREFIX_BLOCK) * PREFIX_BLOCK
    rejected = jnp.pad(~keep, (0, padded - capacity)).astype(
        jnp.int32).reshape(-1, PREFIX_BLOCK)
    within = jnp.cumsum(rejected, axis=1)
    totals = within[:, -1]
    before = jnp.cumsum(totals) - totals
    return (within - rejected + before[:, None]).reshape(-1)[:capacity]


def _shift_up(a: jax.Array, s: jax.Array) -> jax.Array:
    """``a`` moved ``s`` rows towards row 0, zeros behind it."""
    return jax.lax.dynamic_slice(jnp.pad(a, (0, a.shape[0])), (s,), a.shape)


@jax.named_scope("ydb.compact")
def compact(block: TableBlock, selected: jax.Array) -> TableBlock:
    """Move selected live rows to the front (stable), update length.

    selected: bool[capacity]; a row at or beyond ``length`` is dropped
    whatever it says. Rows at and beyond the new length are padding:
    validity False, data unspecified.

    A stream compaction without a sort, a gather or a scatter (a 1-D
    gather costs a TPU 7-27 ms per 2^20 indices whatever the bytes):
    row ``i`` has to travel ``d[i]`` = the rejected rows before it, and
    round ``b`` of ceil(log2(capacity)) moves the rows whose ``d`` has
    bit ``b`` set by 2^b: a slice and a select over each column in its
    own dtype (Hacker's Delight's "compress" in Steele's
    parallel-prefix form). Two kept rows never meet: for ``i < j``,
    ``(d[j] mod m) - (d[i] mod m) <= d[j] - d[i] < j - i``. The
    validities ride as one uint32 word per 32 columns; a vacated or
    rejected row carries ``d`` = 0 and never moves. The rounds are one
    loop body, so a program holds it once whatever the capacity.
    """
    keep = selected & block.row_mask()
    n = jnp.sum(keep).astype(jnp.int32)
    cols = list(block.columns.values())
    words = tuple(
        functools.reduce(jnp.bitwise_or, (
            c.validity.astype(jnp.uint32) << bit
            for bit, c in enumerate(cols[at:at + VALIDITY_WORD_BITS])))
        for at in range(0, len(cols), VALIDITY_WORD_BITS))

    def one_round(b, state):
        carried, d = state
        s = jnp.int32(1) << b
        moves = ((d >> b) & 1) != 0
        arrives = _shift_up(moves, s)
        carried = tuple(jnp.where(arrives, _shift_up(x, s), x)
                        for x in carried)
        return carried, jnp.where(arrives, _shift_up(d, s),
                                  jnp.where(moves, 0, d))

    carried, _ = jax.lax.fori_loop(
        0, max(block.capacity - 1, 0).bit_length(), one_round,
        (tuple(c.data for c in cols) + words,
         jnp.where(keep, _rejected_before(keep), 0)))
    live = jnp.arange(block.capacity, dtype=jnp.int32) < n
    words = carried[len(cols):]
    return TableBlock({
        name: Column(
            carried[i],
            (((words[i // VALIDITY_WORD_BITS] >> (i % VALIDITY_WORD_BITS))
              & 1) != 0) & live)
        for i, name in enumerate(block.columns)}, n, block.schema)


# ---------------- grouped aggregation ----------------


@jax.named_scope("ydb.group_ids_dense")
def group_ids_dense(
    keys: list[Column],
    bounds: list[int],
    live: jax.Array,
) -> tuple[jax.Array, int]:
    """Dense group ids from small-cardinality keys (dict ids / bounded ints).

    NULL key values get their own slot per key (SQL GROUP BY semantics), so
    each key contributes (bound + 1) values; id 0 means NULL.
    Rows not live get id = num_groups (scatter-drop sentinel).
    """
    num_groups = 1
    gid = jnp.zeros(keys[0].data.shape, dtype=jnp.int32)
    for k, b in zip(keys, bounds):
        enc = jnp.where(k.validity, k.data.astype(jnp.int32) + 1, 0)
        gid = gid * (b + 1) + enc
        num_groups *= b + 1
    gid = jnp.where(live, gid, num_groups)
    return gid, num_groups


class SortedKeys(NamedTuple):
    """What ``group_ids_sorted``'s one sort leaves of the keys: each key
    column in sorted order (the data zero under a NULL) and
    ``boundary``, the first row of each live group. Group ``g``'s key is
    the columns at the ``g``-th boundary: a ``compact`` by ``boundary``
    puts it in slot ``g``."""

    columns: tuple[Column, ...]
    boundary: jax.Array     # bool[capacity]


@jax.named_scope("ydb.group_ids_sorted")
def group_ids_sorted(
    keys: list[Column], live: jax.Array, max_groups: int
) -> tuple[jax.Array, jax.Array, SortedKeys]:
    """Generic exact group ids via lexicographic sort (no device hash table).

    Returns (gid[capacity] int32 with dead rows = max_groups, n_groups
    scalar, the sorted keys and their segment heads). Group ids are
    assigned in sorted key order, so downstream per-group outputs come
    out key-ordered.
    """
    # what lies under a NULL is made alike before the sort, so that all
    # NULLs of a key form ONE run whatever keys follow it
    keys = [Column(jnp.where(k.validity, k.data, jnp.zeros_like(k.data)),
                   k.validity) for k in keys]
    # sort dead rows last; a key's NULLs after its values
    sort_keys = []
    for k in reversed(keys):
        sort_keys.append(k.data)
        sort_keys.append(~k.validity)
    sort_keys.append(~live)
    perm = stable_lexsort(sort_keys)  # last key is primary
    # invert the permutation with one linear scatter (not a second sort)
    inv = jnp.zeros_like(perm).at[perm].set(
        jnp.arange(perm.shape[0], dtype=perm.dtype)
    )

    live_s = live[perm]
    changed = jnp.zeros(live.shape, dtype=bool)
    sorted_keys = []
    for k in keys:
        d, v = k.data[perm], k.validity[perm]
        sorted_keys.append(Column(d, v))
        prev_d = jnp.roll(d, 1)
        prev_v = jnp.roll(v, 1)
        diff = (d != prev_d) | (v != prev_v)
        changed = changed | diff
    changed = changed.at[0].set(True)
    # boundaries only count within the live prefix
    boundary = changed & live_s
    seg_sorted = jnp.cumsum(boundary.astype(jnp.int32)) - 1
    n_groups = jnp.maximum(jnp.max(jnp.where(live_s, seg_sorted, -1)) + 1, 0)
    seg_sorted = jnp.where(live_s, seg_sorted, max_groups)
    gid = seg_sorted[inv]
    return (gid, n_groups.astype(jnp.int32),
            SortedKeys(tuple(sorted_keys), boundary))


#: Below this many groups the one-hot masked reduction beats any scatter:
#: XLA lowers it to ONE vectorized pass over the rows with the groups on
#: the lane axis — no serialization, exact in every dtype. This is the
#: within-block analog of BlockCombineHashed's small-key fast path
#: (mkql_block_agg.cpp:1637); TPUs have no scatter unit, so "hash table"
#: becomes "lane-broadcast compare + reduce".
ONEHOT_GROUP_LIMIT = 512


def group_hits(gid: jax.Array, num_groups: int) -> jax.Array:
    """bool (rows x groups) one-hot hit matrix from drop-encoded group
    ids (dead/invalid rows carry gid >= num_groups and match no group).

    This is THE shared expansion of the group-by: built once per
    GroupByStep and reused by every linear bank, MIN/MAX reduction and
    the per-group first-row index."""
    groups = jnp.arange(num_groups, dtype=jnp.int32)
    return gid[:, None] == groups[None, :]


def first_live_index(hits: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Per-group first hit row: (index int32[groups], found bool[groups]).

    Empty groups report index 0 with found=False; callers gather with
    the clamped index and mask by ``found``. One expansion serves every
    GROUP BY key column (they all share the same live mask)."""
    n = hits.shape[0]
    rows = jnp.arange(n, dtype=jnp.int32)
    first = jnp.min(jnp.where(hits, rows[:, None], n), axis=0)
    found = first < n
    return jnp.minimum(first, max(n - 1, 0)), found


def reduce_tier(num_groups: int, dtype, n_slots: int) -> str:
    """The tier ``fused_group_reduce`` takes for a bank of ``n_slots``
    accumulators of ``dtype`` over ``num_groups`` groups: ``onehot``,
    ``pallas`` or ``scatter`` (the rule is in its docstring)."""
    if num_groups <= ONEHOT_GROUP_LIMIT:
        return "onehot"
    from ydb_tpu.ssa import pallas_kernels

    if pallas_kernels.enabled() and pallas_kernels.supported_fused(
            dtype, num_groups, n_slots):
        return "pallas"
    return "scatter"


@jax.named_scope("ydb.fused_group_reduce")
def fused_group_reduce(stacked: jax.Array, gid: jax.Array,
                       num_groups: int, dtype=None) -> jax.Array:
    """All linear aggregates of one accumulator dtype in one reduction:
    (rows x slots) stacked inputs -> (groups x slots) per-group sums.

    ``stacked`` columns are pre-masked (invalid contributions already
    zero); ``gid`` is drop-encoded (dead rows >= num_groups). The tier
    follows from the group count alone, the same on every backend:

      * groups <= ONEHOT_GROUP_LIMIT: one masked where+sum per slot
        over the shared hit matrix, on the vector unit. Exact in every
        dtype (int64 decimal sums are integer adds). Not a GEMM: a TPU
        has no f64 and no s64 dot, XLA splits an f64 dot into f32 MXU
        passes whose accumulators round integer sums (TPC-H Q1 came
        back with wrong decimal sums from a [72x12]^T [72x23] dot on a
        v5e), and the emulated f64 GEMM measured 145 ms against 1.9 ms
        for 2^20 rows x 11 int64 slots there.
      * larger, Pallas-eligible dtype: the fused multi-column one-hot
        tile kernel (pallas_kernels.grouped_sum_multi).
      * otherwise: one 2D scatter-add, one pass for all slots.
    """
    dtype = jnp.dtype(dtype or stacked.dtype)
    stacked = stacked.astype(dtype)
    tier = reduce_tier(num_groups, dtype, stacked.shape[1])
    if tier == "onehot":
        hits = group_hits(gid, num_groups)
        zero = jnp.zeros((), dtype=dtype)
        return jnp.stack(
            [jnp.sum(jnp.where(hits, stacked[:, j][:, None], zero), axis=0,
                     dtype=dtype)
             for j in range(stacked.shape[1])], axis=1)
    if tier == "pallas":
        from ydb_tpu.ssa import pallas_kernels

        return pallas_kernels.grouped_sum_multi(stacked, gid, num_groups)
    out = jnp.zeros((num_groups, stacked.shape[1]), dtype=dtype)
    return out.at[gid].add(stacked, mode="drop")


def _onehot_hits(valid_row, gid, num_groups: int):
    groups = jnp.arange(num_groups, dtype=jnp.int32)
    return (gid[:, None] == groups[None, :]) & valid_row[:, None]


def _onehot_reduce(values, valid_row, gid, num_groups: int, fill,
                   reduce_fn):
    """Masked (rows x groups) reduction — the shared one-hot fast path."""
    hit = _onehot_hits(valid_row, gid, num_groups)
    vals = jnp.where(hit, values[:, None],
                     jnp.asarray(fill, dtype=values.dtype))
    return reduce_fn(vals, axis=0)


@jax.named_scope("ydb.scatter_first")
def scatter_first(values: jax.Array, valid_row, gid, num_groups: int):
    """Per-group 'some' value: any valid row's value wins (scatter, drop OOB)."""
    if num_groups <= ONEHOT_GROUP_LIMIT and values.ndim == 1:
        n = values.shape[0]
        rows = jnp.arange(n, dtype=jnp.int32)
        hit = _onehot_hits(valid_row, gid, num_groups)
        first = jnp.min(jnp.where(hit, rows[:, None], n), axis=0)
        return jnp.where(first < n, values[jnp.minimum(first, n - 1)],
                         jnp.zeros((), dtype=values.dtype))
    idx = jnp.where(valid_row, gid, num_groups)
    out = jnp.zeros((num_groups,) + values.shape[1:], dtype=values.dtype)
    return out.at[idx].set(values, mode="drop")


@jax.named_scope("ydb.scatter_min")
def scatter_min(values, valid_row, gid, num_groups: int):
    init = _extreme(values.dtype, maximum=True)
    if num_groups <= ONEHOT_GROUP_LIMIT:
        return _onehot_reduce(values, valid_row, gid, num_groups, init,
                              jnp.min)
    idx = jnp.where(valid_row, gid, num_groups)
    out = jnp.full((num_groups,), init, dtype=values.dtype)
    return out.at[idx].min(values, mode="drop")


@jax.named_scope("ydb.scatter_max")
def scatter_max(values, valid_row, gid, num_groups: int):
    init = _extreme(values.dtype, maximum=False)
    if num_groups <= ONEHOT_GROUP_LIMIT:
        return _onehot_reduce(values, valid_row, gid, num_groups, init,
                              jnp.max)
    idx = jnp.where(valid_row, gid, num_groups)
    out = jnp.full((num_groups,), init, dtype=values.dtype)
    return out.at[idx].max(values, mode="drop")


def _extreme(dtype, maximum: bool):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.inf if maximum else -jnp.inf
    if dtype == jnp.bool_:
        return True if maximum else False
    info = jnp.iinfo(dtype)
    return info.max if maximum else info.min


# ---------------- sort / top-k ----------------


def _sort_keys(keys: list[Column], descending: list[bool],
               live: jax.Array) -> list[jax.Array]:
    """The keys of the order as ``stable_lexsort`` takes them, the least
    significant first: per ORDER BY key its data (a descending integer
    complemented: exact, overflow-free; a float negated) and, above it,
    its null flag (NULLS LAST in either direction); the dead flag above
    all, so dead rows sink to the end."""
    sort_keys = []
    for k, desc in zip(reversed(keys), reversed(descending)):
        d = k.data
        if desc:
            if d.dtype == jnp.bool_:
                d = ~d
            elif jnp.issubdtype(d.dtype, jnp.integer):
                d = ~d  # exact order reversal, overflow-free
            else:
                d = -d
        # NULLs last regardless of direction; the null flag is appended
        # after the data key so it is more significant in the lexsort
        sort_keys.append(d)
        sort_keys.append(~k.validity)
    sort_keys.append(~live)
    return sort_keys


@jax.named_scope("ydb.sort_perm")
def sort_perm(
    keys: list[Column],
    descending: list[bool],
    live: jax.Array,
) -> jax.Array:
    """Stable multi-key sort permutation; dead rows sink to the end.

    Descending numeric keys negate via bitwise complement on ints (exact,
    overflow-free) and negation on floats; NULLS LAST within each key.
    """
    return stable_lexsort(_sort_keys(keys, descending, live))


#: a LIMIT engages the top-k where the capacity holds it this many
#: times over (``sort_tier``). It guards two things, both read on a v5e
#: (PERF.md section 6, PR 38): the selection's rounds cost ~1.2 ms
#: whatever the capacity, which the whole sort beats below ~2,000 slots
#: (a LIMIT 10 engages from 2,560), and the ``limit`` rows' own sort
#: grows with the limit toward the whole block's
TOPK_ROOM = 256


def sort_tier(limit: int | None, capacity: int, key_dtypes) -> str:
    """The way ``sort_block`` takes, settled at trace time from what it
    sees of its input: ``select`` (a top-k: the ``limit`` first rows of
    the order are found by an exact selection and only they are sorted)
    where there is a key, none of them floating (a float's order has
    NaNs and signed zeros: ``stable_lexsort`` leaves it to the
    comparator sort) and ``0 < limit * TOPK_ROOM <= capacity``, so a
    LIMIT near the capacity and a block of a few thousand slots keep the
    ``whole`` sort, as every sort without a limit does."""
    key_dtypes = list(key_dtypes)
    if (limit and key_dtypes and limit * TOPK_ROOM <= capacity
            and not any(jnp.issubdtype(d, jnp.floating)
                        for d in key_dtypes)):
        return "select"
    return "whole"


def _select_first(sort_keys: list[jax.Array], k: int) -> jax.Array:
    """int32[k]: the rows that ``stable_lexsort(sort_keys)`` puts first,
    in the order of their row numbers, found without ordering the rest.

    The order's words are walked once, from the most significant (the
    32-bit words of ``sort_keys`` from its last key, each mapped to an
    unsigned word of the same order) down to the row number, which makes
    the order total and is what a stable sort ties by. Per word a radix
    select over the rows still tied with the k-th (``cand``): one round
    a bit, each a compare and a count over the capacity, finds the
    word's value ``t`` at the ``need``-th candidate; candidates below
    ``t`` are taken, ``need`` falls by their count, and the candidates
    become those equal to ``t``. After the row number one candidate is
    left and ``need`` is 1: the taken rows and that one are the first
    ``k``, exactly, whatever ties. The rounds of a word are ONE loop
    body (an unrolled round costs XLA's CPU backend a kernel each).
    The ``k`` row numbers come off the flags by ``compact``'s prefix
    count (``_rejected_before``) and ``k`` binary searches in it."""
    capacity = sort_keys[0].shape[0]
    words = []
    for key in reversed(sort_keys):
        for word in reversed(_words32(key)):
            bits = 1 if word.dtype == jnp.bool_ else 32
            signed = word.dtype == jnp.int32
            word = word.astype(jnp.uint32)
            if signed:      # the sign bit flipped: the unsigned order
                word = word ^ jnp.uint32(1 << 31)   # is the signed one
            words.append((word, bits))
    words.append((jnp.arange(capacity, dtype=jnp.uint32),
                  max(capacity - 1, 1).bit_length()))

    cand = jnp.ones((capacity,), dtype=bool)
    taken = jnp.zeros((capacity,), dtype=bool)
    need = jnp.int32(k)
    for word, bits in words:

        def one_round(i, t):    # traced at once: this word's closure
            bit = jnp.uint32(1) << (bits - 1 - i).astype(jnp.uint32)
            enough = jnp.sum(cand & (word <= (t | (bit - 1))),
                             dtype=jnp.int32) >= need
            return jnp.where(enough, t, t | bit)

        t = jax.lax.fori_loop(0, bits, one_round, jnp.uint32(0))
        below = cand & (word < t)
        taken = taken | below
        need = need - jnp.sum(below, dtype=jnp.int32)
        cand = cand & (word == t)
    first = taken | cand
    # the first rows up to and with each row: the n-th is where that
    # count first reads n
    upto = (jnp.arange(capacity, dtype=jnp.int32) + first
            - _rejected_before(first))
    return jnp.searchsorted(
        upto, jnp.arange(1, k + 1, dtype=jnp.int32)).astype(jnp.int32)


@jax.named_scope("ydb.sort_block")
def sort_block(
    block: TableBlock,
    keys: list[str],
    descending: list[bool],
    limit: int | None = None,
    live: jax.Array | None = None,
) -> TableBlock:
    """Sort live (optionally pre-masked) rows; one lexsort pass does both
    the selection compaction (non-live rows sink) and the ordering.

    Under a ``limit`` far below the capacity (``sort_tier`` says
    ``select``) it is a top-k: ``_select_first`` finds the ``limit``
    first rows of that same order, only those rows are gathered and
    sorted, and the result is padded back to the capacity, so no sort
    and no gather has the capacity's length. The rows, their order
    (ties, NULLS LAST, masked and dead rows) and the length are the
    whole sort's, row for row."""
    if live is None:
        live = block.row_mask()
    else:
        live = live & block.row_mask()
    key_cols = [block.columns[k] for k in keys]
    columns = block.columns
    selects = sort_tier(limit, block.capacity,
                        (c.data.dtype for c in key_cols)) == "select"
    if selects:
        first = _select_first(_sort_keys(key_cols, descending, live), limit)
        columns = {n: Column(c.data[first], c.validity[first])
                   for n, c in columns.items()}
        key_cols = [columns[k] for k in keys]
        live = live[first]
    perm = sort_perm(key_cols, descending, live)
    cols = {
        n: Column(c.data[perm], c.validity[perm] & live[perm])
        for n, c in columns.items()
    }
    length = jnp.sum(live).astype(jnp.int32)
    if limit is not None:
        length = jnp.minimum(length, jnp.int32(limit))
    # zero validity past the length so padding never leaks
    cut = jnp.arange(live.shape[0], dtype=jnp.int32) < length
    cols = {n: Column(c.data, c.validity & cut) for n, c in cols.items()}
    if selects:
        rest = (0, block.capacity - limit)
        cols = {n: Column(jnp.pad(c.data, rest), jnp.pad(c.validity, rest))
                for n, c in cols.items()}
    return TableBlock(cols, length, block.schema)


# ---------------- prefix scans ----------------


def _blocked_scan(x: jax.Array, kind: str) -> jax.Array:
    """The inclusive running sum (``kind`` "sum") or maximum ("max") of
    ``x`` along its rows, in rows of PREFIX_BLOCK and then over the
    rows' totals, as ``_rejected_before`` counts: a 1-D scan of 2^20
    rows takes XLA's TPU compiler 22.7 s to build."""
    capacity = x.shape[0]
    padded = -(-capacity // PREFIX_BLOCK) * PREFIX_BLOCK
    fill = 0 if kind == "sum" else _extreme(x.dtype, maximum=False)
    rows = jnp.pad(x, (0, padded - capacity),
                   constant_values=fill).reshape(-1, PREFIX_BLOCK)
    if kind == "sum":
        within = jnp.cumsum(rows, axis=1, dtype=x.dtype)
        totals = within[:, -1]
        before = jnp.cumsum(totals, dtype=x.dtype) - totals
        out = within + before[:, None]
    else:
        within = jax.lax.cummax(rows, axis=1)
        upto = jax.lax.cummax(within[:, -1])
        before = jnp.concatenate([jnp.full((1,), fill, x.dtype), upto[:-1]])
        out = jnp.maximum(within, before[:, None])
    return out.reshape(-1)[:capacity]


def _alike_under_null(c: Column) -> jax.Array:
    """A key's data with zero under its NULLs (a flag as int32), so that
    all NULLs of the key compare equal, whatever bits lie under them."""
    d = c.data.astype(jnp.int32) if c.data.dtype == jnp.bool_ else c.data
    return jnp.where(c.validity, d, jnp.zeros_like(d))


def _changed(sorted_words: list[jax.Array]) -> jax.Array:
    """bool[capacity]: row 0, and each row whose words differ from the
    row's before it."""
    ch = jnp.arange(sorted_words[0].shape[0], dtype=jnp.int32) == 0
    for w in sorted_words:
        ch = ch | (w != jnp.roll(w, 1))
    return ch


# ---------------- grouping sets: ROLLUP ----------------


def _rollup_order(block: TableBlock, keys) -> TableBlock:
    """The block's rows ordered by its keys (NULLs alike, after the
    values of their key), the live rows first: every run of equal key
    prefixes contiguous."""
    sort_keys = []
    for k in reversed(keys):
        c = block.columns[k]
        sort_keys += [_alike_under_null(c), ~c.validity]
    sort_keys.append(~block.row_mask())
    perm = stable_lexsort(sort_keys)
    return TableBlock({n: Column(c.data[perm], c.validity[perm])
                       for n, c in block.columns.items()},
                      block.length, block.schema)


def _prefix_heads(keys: list[Column], live: jax.Array) -> list[jax.Array]:
    """``heads[m]`` for m = 0..len(keys): the first live row of each run
    of equal values of the first ``m`` keys, NULLs alike, over rows
    whose runs are contiguous (``heads[0]`` the first row alone)."""
    changed = jnp.arange(live.shape[0], dtype=jnp.int32) == 0
    heads = [changed & live]
    for k in keys:
        changed = changed | _changed([_alike_under_null(k),
                                      k.validity.astype(jnp.int32)])
        heads.append(changed & live)
    return heads


@jax.named_scope("ydb.rollup")
def rollup_counts(block: TableBlock, keys, ordered: bool) -> jax.Array:
    """int32[len(keys) + 1]: the rows of each ROLLUP level of ``block``
    (one row per distinct tuple of ``keys``, a GROUP BY's output),
    indexed by the keys it keeps: the grand total's 1 first, the
    block's own live rows last. ``ordered`` says the block's rows
    already come in runs of equal key prefixes (a group-by's output)."""
    if not ordered:
        block = _rollup_order(block, keys)
    heads = _prefix_heads([block.columns[k] for k in keys],
                          block.row_mask())
    counts = jnp.stack([jnp.sum(h, dtype=jnp.int32) for h in heads])
    return counts.at[0].set(1)


def _roll_up(cols: dict, keys, rolls, live, m: int, cap: int):
    """The level that keeps the first ``m`` keys, from its parent level
    (``cols`` in runs of equal prefixes of ``keys``, ``live`` a prefix
    of its rows): (columns in ``cap`` slots, rows). A SUM or a COUNT is
    the difference of a running sum at consecutive run heads (exact in
    integers; a float one is summed by a scatter, which keeps no
    running total to cancel), a MIN or a MAX a scatter by run."""
    capacity = live.shape[0]
    key_cols = [cols[k] for k in keys]
    slots = jnp.arange(cap, dtype=jnp.int32)
    out = {k: Column(jnp.zeros(cap, c.data.dtype), jnp.zeros(cap, bool))
           for k, c in zip(keys[m:], key_cols[m:])}
    if m == 0:      # the grand total: one row, even over no rows
        one = slots < 1
        for name, kind in rolls:
            c = cols[name]
            ok = live & (c.validity | (kind == "count"))
            if kind in ("sum", "count"):
                data = jnp.sum(jnp.where(ok, c.data, 0), dtype=c.data.dtype)
            else:
                fill = _extreme(c.data.dtype, maximum=kind == "min")
                reduce = jnp.min if kind == "min" else jnp.max
                data = reduce(jnp.where(ok, c.data, fill))
            valid = one & (jnp.any(ok) | (kind == "count"))
            out[name] = Column(
                jnp.where(valid, data, 0).astype(c.data.dtype), valid)
        return out, jnp.int32(1)
    head = _prefix_heads(key_cols[:m], live)[m]
    rows = jnp.sum(head, dtype=jnp.int32)
    carried = {f"k{i}": c for i, c in enumerate(key_cols[:m])}
    totals = {}
    for i, (name, kind) in enumerate(rolls):
        c = cols[name]
        ok = live & (c.validity | (kind == "count"))
        if kind in ("sum", "count") and not jnp.issubdtype(
                c.data.dtype, jnp.floating):
            x = jnp.where(ok, c.data, 0).astype(c.data.dtype)
            running = _blocked_scan(x, "sum")
            carried[f"s{i}"] = Column(running - x, live)
            totals[f"s{i}"] = running[-1]
        if kind == "sum":
            n_ok = ok.astype(jnp.int32)
            running = _blocked_scan(n_ok, "sum")
            carried[f"n{i}"] = Column(running - n_ok, live)
            totals[f"n{i}"] = running[-1]
    heads = compact(TableBlock(carried, jnp.int32(capacity), None), head)
    live_out = slots < rows

    def run_total(key):
        """Each run's total: the running sum before the next run's head
        (before the end of the rows, for the last run) less that before
        its own."""
        before = heads.columns[key].data
        after = jnp.where(jnp.arange(capacity, dtype=jnp.int32) + 1 < rows,
                          jnp.roll(before, -1), totals[key])
        return (after - before)[:cap]

    for i, k in enumerate(keys[:m]):
        c = heads.columns[f"k{i}"]
        out[k] = Column(c.data[:cap], c.validity[:cap] & live_out)
    if any(kind in ("min", "max") or jnp.issubdtype(
            cols[name].data.dtype, jnp.floating) for name, kind in rolls):
        run = jnp.where(live, _blocked_scan(head.astype(jnp.int32), "sum")
                        - 1, cap)
    for i, (name, kind) in enumerate(rolls):
        c = cols[name]
        ok = live & (c.validity | (kind == "count"))
        if f"s{i}" in carried:
            data = run_total(f"s{i}")
        elif kind in ("sum", "count"):
            data = jnp.zeros(cap, c.data.dtype).at[
                jnp.where(ok, run, cap)].add(c.data, mode="drop")
        else:
            data = (scatter_min if kind == "min" else scatter_max)(
                c.data, ok, run, cap)
        if kind == "count":
            valid = live_out
        elif kind == "sum":
            valid = live_out & (run_total(f"n{i}") > 0)
        else:
            valid = live_out & (jnp.zeros(cap, jnp.int32).at[
                jnp.where(ok, run, cap)].add(1, mode="drop") > 0)
        out[name] = Column(jnp.where(valid, data, jnp.zeros_like(data)),
                           valid)
    return out, rows


@jax.named_scope("ydb.rollup")
def rollup(block: TableBlock, keys, rolls, caps, out_cap: int,
           ordered: bool) -> tuple[TableBlock, jax.Array]:
    """GROUP BY ROLLUP(``keys``) from its finest level ``block`` (one row
    per distinct key tuple: a GROUP BY's output, its rows in runs of
    equal key prefixes where ``ordered``): every level of the rollup in
    one block of ``out_cap`` slots, the finest level's rows first (the
    block's own), then the level without the last key, ..., the grand
    total last. A level's rolled-up keys are NULL (rows whose key was
    NULL in the data stay rows of their own level); ``rolls`` pairs each
    aggregate column with what a level does with its finest values:
    ``sum`` (NULL where no value under it is), ``count`` (never NULL),
    ``min`` or ``max``. ``caps[m]`` is the slots of the level that keeps
    the first ``m`` keys (at least its rows, at most the level above
    it's), ``out_cap`` at least all the levels' rows. Each level comes
    from the one above it, one run of equal prefixes a row, with no
    sort: a run's head is where a prefix key changes. Returns the block
    and each level's rows, as ``rollup_counts`` gives them."""
    if not ordered:
        block = _rollup_order(block, keys)
    n = len(keys)
    names = list(keys) + [name for name, _ in rolls]
    live = block.row_mask()
    cols = {k: Column(jnp.where(block.columns[k].validity & live,
                                block.columns[k].data, 0),
                      block.columns[k].validity & live) for k in names}
    levels = [(cols, block.length)]
    counts = [block.length]
    for m in range(n - 1, -1, -1):
        cols, rows = _roll_up(cols, keys, rolls, live, m, caps[m])
        live = jnp.arange(caps[m], dtype=jnp.int32) < rows
        levels.append((cols, rows))
        counts.append(rows)
    room = out_cap + max(c.capacity for c in
                         (lv[0][names[0]] for lv in levels))
    out = {}
    for name in names:
        data = jnp.zeros(room, block.columns[name].data.dtype)
        valid = jnp.zeros(room, bool)
        at = jnp.int32(0)
        for lv, rows in levels:
            data = jax.lax.dynamic_update_slice(data, lv[name].data, (at,))
            valid = jax.lax.dynamic_update_slice(
                valid, lv[name].validity, (at,))
            at = at + rows
        out[name] = (data, valid)
    total = functools.reduce(jnp.add, counts)
    keep = jnp.arange(out_cap, dtype=jnp.int32) < total
    return (TableBlock({n_: Column(d[:out_cap], v[:out_cap] & keep)
                        for n_, (d, v) in out.items()}, total,
                       block.schema.select(names)
                       if block.schema is not None else None),
            jnp.stack(counts[::-1]))


# ---------------- ranking windows ----------------


@jax.named_scope("ydb.window")
def window_rank(func: str, partition: list[Column], order: list[Column],
                descending, live: jax.Array) -> tuple[jax.Array, jax.Array]:
    """rank / dense_rank / row_number OVER (PARTITION BY ``partition``
    ORDER BY ``order``) of the ``live`` rows: (int64[capacity], the
    number of partitions). A NULL partition key is one partition; NULL
    order keys come last in either direction, as ``sort_block`` puts
    them, and are peers of each other. One ``stable_lexsort``, a pass a
    32-bit word of the keys (the data under a NULL made alike first, as
    ``group_ids_sorted`` does), run heads found in sorted order, the
    values sorted back into the rows' order."""
    parts = [(_alike_under_null(c), c.validity) for c in partition]
    ords = [(_alike_under_null(c), c.validity) for c in order]
    sort_keys = []
    for (d, v), desc in zip(reversed(ords), reversed(tuple(descending))):
        if desc:
            d = -d if jnp.issubdtype(d.dtype, jnp.floating) else ~d
        sort_keys += [d, ~v]
    for d, v in reversed(parts):
        sort_keys += [d, ~v]
    sort_keys.append(~live)
    perm = stable_lexsort(sort_keys)
    idx = jnp.arange(live.shape[0], dtype=jnp.int32)
    live_s = live[perm]
    new_part = _changed([live_s] + [w[perm] for d, v in parts
                                    for w in (d, v)])
    seg_start = _blocked_scan(jnp.where(new_part, idx, 0), "max")
    if func == "row_number":
        out = idx - seg_start + 1
    else:
        new_peer = new_part | _changed([
            w[perm] for d, v in ords for w in (d, v)]) if ords else new_part
        if func == "rank":
            out = (_blocked_scan(jnp.where(new_peer, idx, 0), "max")
                   - seg_start + 1)
        else:       # dense_rank: peer runs so far, less those before
            peers = _blocked_scan(new_peer.astype(jnp.int32), "sum")
            out = peers - _blocked_scan(
                jnp.where(new_part, peers, 0), "max") + 1
    # the values back in row order by one sort keyed by the permutation
    # (its keys distinct, so unstable): a scatter of them by ``perm``
    # took 1.8 s of the window's 4.0 at 11.5M rows on a v5e
    _, values = jax.lax.sort((perm, out.astype(jnp.int32)), num_keys=1,
                             is_stable=False)
    return (values.astype(jnp.int64),
            jnp.sum(new_part & live_s, dtype=jnp.int32))
