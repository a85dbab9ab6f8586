# ydb-devmem: device-module — join kernels: every body runs under the
# compiled join program's trace (XLA temporaries, not HBM residents)
"""Device equi-join kernels.

The reference joins rows with hash tables (GraceJoin partitioned hash join
mkql_grace_join.cpp:558, MapJoin broadcast mkql_map_join.cpp). Dynamic hash
tables don't exist on TPU; the TPU-native designs here are sort-based with
static shapes:

  * ``lookup_join`` — N:1 join (probe side may repeat keys; build keys
    unique, e.g. any FK -> PK join). Output shape == probe shape; a
    found-mask drives inner/left/semi/anti variants. This covers every
    TPC-H and TPC-DS dimension join. Each probe row finds its build row
    on one of two paths:

    - direct: the build's row numbers scattered into a table indexed by
      ``key - min key``, one gather per probe row. Dense surrogate keys
      (``1..N`` a dimension, a date's day numbers) are what it is for.
    - search: the build sorted by key once, then ``searchsorted`` (a
      loop of ceil(log2(capacity + 1)) gathers) and a gather per row.

    The rule that picks is the input's, never a setting: the direct
    branch is compiled only for one integer key column and a probe at
    least as large as its build (``_direct_slots``), and it runs, by a
    ``lax.cond`` on the device, only where the live build keys span
    fewer than its table's slots; otherwise the search runs, in the
    same program.
  * ``expand_join`` — N:M join via prefix-sum expansion into a static
    output capacity: per-probe match counts -> cumulative offsets ->
    each output slot maps back to (probe row, k-th match) with two
    searchsorted passes. Exact while total matches <= out capacity; the
    returned total lets callers detect overflow and re-run with a larger
    capacity (grace-style bucketing keeps capacities bounded after a
    hash repartition).

Multi-key joins pack keys into one int64 via the shuffle hash (exact for
<=64-bit concatenations; otherwise hash with verify-on-gather).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ydb_tpu.blocks.block import Column, TableBlock


def _key_i64(cols: list[Column]) -> jax.Array:
    """Combine key columns into one int64 per row, exactly.

    One column passes through; two int columns pack as (a << 32) | b —
    exact while both values fit in 32 bits (all TPC-H/ClickBench composite
    keys do, e.g. partsupp's (partkey, suppkey)). Wider composites need a
    pre-assigned join-key column (planner's job), not a lossy hash: a hash
    here would silently drop/duplicate matches on collision. Liveness /
    NULL handling lives entirely in _join_keys_live.
    """
    if len(cols) == 1:
        return cols[0].data.astype(jnp.int64)
    if len(cols) == 2:
        a = cols[0].data.astype(jnp.int64)
        b = cols[1].data.astype(jnp.int64)
        return (a << 32) | (b & jnp.int64(0xFFFFFFFF))
    raise NotImplementedError(
        ">2 join key columns: pre-pack a composite key column"
    )


@jax.named_scope("ydb.sorted_build")
def _sorted_build(bk: jax.Array, blive: jax.Array):
    """Sort build keys with dead rows last, WITHOUT a value sentinel
    (sentinels collide with legitimate INT64_MAX keys).

    Returns (order, bk_sorted, n_live): live keys sorted in the prefix
    [0, n_live); suffix positions are overwritten with the prefix's last
    value so the whole array stays sorted for searchsorted. Matches are
    validated against idx < n_live, so suffix duplicates never count.
    """
    # primary: liveness (live first), then key. As stable 32-bit passes
    # (the key's low word, its high word, liveness) and not one
    # (int64, bool) lexsort: for a described v5e at 917,504 rows the
    # whole match program compiles in under 40 s against 208-239 s,
    # whatever the row count (ROADMAP S10)
    from ydb_tpu.ssa.kernels import stable_lexsort

    order = stable_lexsort((bk, ~blive))
    bk_sorted = bk[order]
    n_live = jnp.sum(blive).astype(jnp.int32)
    cap = bk.shape[0]
    last_live = bk_sorted[jnp.maximum(n_live - 1, 0)]
    pos = jnp.arange(cap, dtype=jnp.int32)
    bk_sorted = jnp.where(pos < n_live, bk_sorted, last_live)
    return order, bk_sorted, n_live


def _join_keys_live(block: TableBlock, keys: list[str]) -> tuple:
    cols = [block.columns[k] for k in keys]
    live = block.row_mask()
    for c in cols:
        live = live & c.validity  # NULL keys drop out of equi-joins
    return _key_i64(cols), live


#: the direct table's slots follow the probe up to here: 2^21 covers
#: every TPC-DS dimension's key span (``customer``'s 2,000,000 is the
#: widest), an int32 table of 8 MB
DIRECT_SLOTS_CAP = 1 << 21


def _direct_slots(key_cols: list[Column], probe_capacity: int,
                  build_capacity: int) -> int | None:
    """The static half of the lookup's rule, from shapes and types: the
    direct table's slot count, or None where no direct branch is
    compiled. Two packed columns are no dense key, and a probe smaller
    than its build would spend more filling and scattering a table the
    build's size than the search it saves. The slots are a shape class,
    so one pair of shape classes still compiles one program."""
    from ydb_tpu.ssa.plan_fuse import shape_class

    if (len(key_cols) != 1
            or not jnp.issubdtype(key_cols[0].data.dtype, jnp.integer)
            or probe_capacity < build_capacity):
        return None
    return shape_class(max(4 * build_capacity,
                           min(DIRECT_SLOTS_CAP, probe_capacity)))


def _search_rows(pk, plive, bk, blive):
    """Each probe row's build row by binary search into the sorted
    build: (src, found)."""
    order, bk_sorted, n_live = _sorted_build(bk, blive)
    idx = jnp.searchsorted(bk_sorted, pk)
    idx = jnp.clip(idx, 0, bk_sorted.shape[0] - 1)
    found = (idx < n_live) & (bk_sorted[idx] == pk) & plive
    return order[idx], found


@jax.named_scope("ydb.direct_lookup")
def _direct_rows(pk, plive, bk, blive, kmin, kmax, slots: int):
    """Each probe row's build row from a table of ``slots`` build row
    numbers indexed by ``key - kmin``: (src, found). Where live build
    keys repeat, the scatter's min keeps the first row in build order,
    the row the stable sort and a left ``searchsorted`` pick."""
    cap = bk.shape[0]
    rows = jnp.arange(cap, dtype=jnp.int32)
    at = jnp.where(blive, (bk - kmin).astype(jnp.int32), slots)
    table = jnp.full(slots, cap, jnp.int32).at[at].min(rows, mode="drop")
    inr = plive & (pk >= kmin) & (pk <= kmax)
    src = table[jnp.where(inr, (pk - kmin).astype(jnp.int32), 0)]
    found = inr & (src < cap)
    return jnp.where(found, src, 0), found


def _match_rows(pk, plive, bk, blive, slots: int | None):
    """(src, found, direct): each probe row's build row on the path the
    input picks, and whether it was the direct one (a device bool).
    The data half of the rule: the live build keys exist and span fewer
    than ``slots`` (the span taken in uint64, so keys at INT64_MIN and
    INT64_MAX cannot overflow it)."""
    if slots is None:
        return (*_search_rows(pk, plive, bk, blive),
                jnp.zeros((), jnp.bool_))
    kmin = jnp.min(jnp.where(blive, bk, jnp.iinfo(jnp.int64).max))
    kmax = jnp.max(jnp.where(blive, bk, jnp.iinfo(jnp.int64).min))
    span = (jax.lax.bitcast_convert_type(kmax, jnp.uint64)
            - jax.lax.bitcast_convert_type(kmin, jnp.uint64))
    direct = jnp.any(blive) & (span < jnp.uint64(slots))
    src, found = jax.lax.cond(
        direct,
        lambda: _direct_rows(pk, plive, bk, blive, kmin, kmax, slots),
        lambda: _search_rows(pk, plive, bk, blive))
    return src, found, direct


@jax.named_scope("ydb.lookup_join")
def lookup_join(
    probe: TableBlock,
    build: TableBlock,
    probe_keys: list[str],
    build_keys: list[str],
    payload: list[str],
    suffix: str = "",
    null_extended: bool = False,
) -> tuple[TableBlock, jax.Array, jax.Array]:
    """N:1 equi-join: gather ``payload`` columns from build into probe.

    Returns (probe + payload columns, found_mask, direct). Build keys
    must be unique among live rows (duplicate keys: the first live row
    in build order wins, on either path). Inner join = compact by found;
    left join = keep all, payload validity = found, the payload's data
    under that NULL unspecified.

    ``direct`` (a device bool) says how the probe rows found their build
    rows: in a direct-addressed table, one gather a row, where one
    integer key column joins a probe at least the build's capacity
    (``_direct_slots``, decided at trace time) and the live build keys
    span fewer than the table's slots (decided on the device); else by
    binary search into the sorted build.
    """
    pk, plive = _join_keys_live(probe, probe_keys)
    bk, blive = _join_keys_live(build, build_keys)
    slots = _direct_slots([build.columns[k] for k in build_keys],
                          probe.capacity, build.capacity)
    src, found, direct = _match_rows(pk, plive, bk, blive, slots)

    if len(set(payload)) != len(payload):
        raise ValueError(f"duplicate payload columns {payload}")
    out_cols = dict(probe.columns)
    sch = probe.schema
    for name in payload:
        c = build.columns[name]
        out_name = name + suffix
        if out_name in probe.columns:
            # a silent overwrite would leave the schema typed as the probe
            # column while the data came from the build side
            raise ValueError(
                f"payload column {out_name!r} collides with a probe column;"
                " pass a suffix"
            )
        out_cols[out_name] = Column(
            c.data[src], c.validity[src] & found
        )
        f = build.schema.field(name)
        from ydb_tpu import dtypes

        # a LEFT join NULL-extends unmatched rows, so its payload is
        # nullable no matter what the build side declares
        sch = sch.with_field(
            dtypes.Field(out_name, f.type, f.nullable or null_extended))
    return TableBlock(out_cols, probe.length, sch), found, direct


def run_equi_join(probe: TableBlock, build: TableBlock, probe_keys,
                  build_keys, **kw) -> TableBlock:
    """``run_equi_join_path``'s joined block alone: the walk, the fused
    plans and the mesh leave the lookup's path unread."""
    return run_equi_join_path(probe, build, probe_keys, build_keys,
                              **kw)[0]


def run_equi_join_path(
    probe: TableBlock,
    build: TableBlock,
    probe_keys,
    build_keys,
    kind: str = "inner",
    suffix: str = "",
    expand: bool = False,
    payload=(),
    probe_payload=(),
    build_payload=(),
) -> "tuple[TableBlock, jax.Array | None]":
    """One dispatch for every equi-join shape — the single-chip plan
    executor and the DQ grace-bucket join call THIS so their semantics
    cannot drift (test_sql_dq.py asserts bit parity between the paths).
    Returns the joined block and, for a lookup join, whether it took the
    direct path (a device bool, read back by whoever fetches anyway);
    None for an expanding join.

    Lookup (N:1) joins support inner/left/semi/anti; expand (N:M) joins
    support inner/left.

    Shapes are what a join costs to COMPILE: the build side is sorted,
    and XLA's TPU sort takes minutes to compile past ~2^14 rows (a
    (int64, bool) lexsort of 363k rows: 196 s for a v5e), once per
    distinct shape. So both sides pad to their shape class — buckets of
    14,912 and 15,101 rows share one program — and an expand join runs
    in two programs: the sort-bearing match (independent of the output
    size), then, with the exact match count read back, the emit at the
    count's shape class. No guessed capacity, no overflow retry that
    would compile the sort again.
    """
    from ydb_tpu.obs import tracing
    from ydb_tpu.ssa.plan_fuse import fit_blocks, shape_class

    def fit(block):  # zero-pad to the shape class; live prefix untouched
        cap = shape_class(block.capacity)
        return block if cap == block.capacity else fit_blocks(
            (block,), cap)

    probe, build = fit(probe), fit(build)
    if not expand:
        if kind not in ("inner", "left", "semi", "anti"):
            raise ValueError(kind)
        with tracing.span("dispatch", program="join_lookup"):
            return _lookup_join_of_kind(
                probe, build, tuple(probe_keys), tuple(build_keys),
                tuple(payload), suffix, kind)
    if kind not in ("inner", "left"):
        # expand_join silently computes INNER for anything else
        raise ValueError(f"expand join does not support kind {kind!r}")
    with tracing.span("dispatch", program="join_expand"):
        match = _expand_match_jit(probe, build, tuple(probe_keys),
                                  tuple(build_keys), kind)
    # the one sync of an expand join: the exact output size
    with tracing.span("device.wait"):
        total = int(match[-1])
    with tracing.span("dispatch", program="join_expand"):
        return _expand_emit_jit(
            probe, build, match, tuple(probe_payload),
            tuple(build_payload), shape_class(total), suffix, kind)[0], None


# The join kernels are some fifty jnp ops each. Called eagerly every op
# is its own XLA program per operand shape: one TPC-H Q3 through the DQ
# graph made 271 compiles. Under jit a join is one or two programs per
# (shape classes, keys, payload, kind).
@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6))
def _lookup_join_of_kind(probe, build, probe_keys, build_keys, payload,
                         suffix, kind):
    """The lookup join of ``kind`` and whether it took the direct path."""
    from ydb_tpu.ssa import kernels

    joined, found, direct = lookup_join(
        probe, build, list(probe_keys), list(build_keys),
        list(payload), suffix, null_extended=(kind == "left"))
    if kind == "inner":
        return kernels.compact(joined, found), direct
    if kind == "left":
        return joined, direct
    if kind == "semi":
        return kernels.compact(probe, found), direct
    return kernels.compact(probe, ~found & probe.row_mask()), direct  # anti


@jax.named_scope("ydb.expand_match")
def _expand_match(probe, build, probe_keys, build_keys, kind):
    """The sort-bearing half of an expand join: per probe row, where its
    matches start in the sorted build side and how many output rows it
    emits. Returns (order, lo, matches, offsets, total); independent of
    the output capacity."""
    pk, plive = _join_keys_live(probe, probe_keys)
    bk, blive = _join_keys_live(build, build_keys)
    # LEFT JOIN keeps probe rows whose key is NULL too (they just match
    # nothing): row liveness for emission is the block mask, while
    # _join_keys_live's plive already excludes NULL keys from matching
    row_live = probe.row_mask()

    order, bk_sorted, n_live = _sorted_build(bk, blive)
    lo = jnp.searchsorted(bk_sorted, pk, side="left")
    hi = jnp.searchsorted(bk_sorted, pk, side="right")
    # the suffix repeats the last live key: clamp ranges to the live prefix
    lo = jnp.minimum(lo, n_live)
    hi = jnp.minimum(hi, n_live)
    # int64 accounting: skewed keys can exceed 2^31 matches, and a wrapped
    # total would defeat the capacity protocol
    matches = jnp.where(plive, (hi - lo).astype(jnp.int64), jnp.int64(0))
    if kind == "left":
        counts = jnp.where(row_live, jnp.maximum(matches, 1), 0)
    else:
        counts = matches
    offsets = jnp.cumsum(counts)  # inclusive
    total = offsets[-1] if counts.shape[0] else jnp.int64(0)
    return order, lo, matches, offsets, total


@jax.named_scope("ydb.expand_emit")
def _expand_emit(probe, build, match, probe_payload, build_payload,
                 out_capacity, build_suffix, kind):
    """The other half: map each of ``out_capacity`` output slots back
    to (probe row, k-th match) and gather the payload."""
    order, lo, matches, offsets, total = match
    # counts = offsets - starts; a left join's pad slot for an unmatched
    # probe row has count 1 and matches 0
    starts = jnp.concatenate(
        [jnp.zeros((1,), offsets.dtype), offsets[:-1]])

    # map each output slot j to (probe row i, k-th match)
    j = jnp.arange(out_capacity, dtype=offsets.dtype)
    i = jnp.searchsorted(offsets, j, side="right")
    i = jnp.clip(i, 0, probe.capacity - 1)
    valid_out = j < jnp.minimum(total, out_capacity)
    k = j - starts[i]
    # matched: this output slot carries a real build match (a left join's
    # pad slot for an unmatched probe row has k == 0 == matches[i])
    matched = valid_out & (k < matches[i])
    b_src = order[jnp.clip(lo[i] + k, 0, build.capacity - 1)]

    from ydb_tpu import dtypes

    cols: dict[str, Column] = {}
    fields = []
    for name in probe_payload:
        c = probe.columns[name]
        cols[name] = Column(c.data[i], c.validity[i] & valid_out)
        fields.append(probe.schema.field(name))
    for name in build_payload:
        c = build.columns[name]
        out_name = name + build_suffix
        cols[out_name] = Column(c.data[b_src], c.validity[b_src] & matched)
        f = build.schema.field(name)
        fields.append(dtypes.Field(
            out_name, f.type, f.nullable or kind == "left"))
    length = jnp.minimum(total, out_capacity).astype(jnp.int32)
    return (
        TableBlock(cols, length, dtypes.Schema(tuple(fields))),
        total,
    )


_expand_match_jit = jax.jit(_expand_match, static_argnums=(2, 3, 4))
_expand_emit_jit = jax.jit(_expand_emit, static_argnums=(3, 4, 5, 6, 7))


def expand_join(
    probe: TableBlock,
    build: TableBlock,
    probe_keys: list[str],
    build_keys: list[str],
    probe_payload: list[str],
    build_payload: list[str],
    out_capacity: int,
    build_suffix: str = "",
    kind: str = "inner",
) -> tuple[TableBlock, jax.Array]:
    """N:M equi-join with static output capacity, for callers that trace
    it into a program of their own (fused plans, the mesh).

    ``kind``: "inner" emits matches only; "left" additionally emits every
    unmatched live probe row once with NULL build payload (LEFT OUTER).
    Returns (joined block, total rows). Rows beyond ``out_capacity``
    are truncated — callers check ``total <= out_capacity`` (host
    side) and retry bigger or pre-partition (grace) if exceeded.
    """
    match = _expand_match(probe, build, probe_keys, build_keys, kind)
    return _expand_emit(probe, build, match, probe_payload,
                        build_payload, out_capacity, build_suffix, kind)
