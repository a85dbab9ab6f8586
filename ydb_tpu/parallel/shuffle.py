"""Hash-partition shuffle over the mesh: the DQ repartitioning channel.

Reference: rows route to output partitions by key hash between stages
(TDqOutputHashPartitionConsumer, dq_output_consumer.cpp:105; vectorized
block path :338). TPU-native: each device buckets its rows by destination
shard and the buckets exchange via ``jax.lax.all_to_all`` over ICI — the
same collective shape as MoE expert dispatch (SURVEY.md §2.11).

XLA needs static shapes, so each device sends a fixed-capacity bucket to
every peer. Full local capacity is always enough — worst case all local
rows hash to one shard — but ships ndev × capacity rows per exchange;
``size_buckets`` instead sizes the bucket from column statistics (mean
destination load × safety margin + the count-min heaviest-hitter bound,
rounded to a plan_fuse shape class so same-class re-runs never retrace).
Undersized buckets cannot corrupt results: ``repartition`` returns the
traced worst per-destination count, the host compares it against the
static capacity and grows-and-retraces on overflow (the grace-join
respill protocol with ICI as the spill fabric). ``YDB_TPU_SHUFFLE_STATS=0``
restores full-capacity buckets. After the exchange each device owns
exactly the rows whose key hash maps to it — the precondition for
partitioned (grace-style) joins and re-keyed aggregation.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from ydb_tpu.blocks.block import Column, TableBlock
from ydb_tpu.parallel.mesh import SHARD_AXIS

#: in-process override for stats-sized buckets (tests compare both
#: sizings with it); None defers to the YDB_TPU_SHUFFLE_STATS
#: environment gate
SHUFFLE_STATS_FORCE: "bool | None" = None

#: headroom over the mean per-destination load: absorbs ordinary hash
#: imbalance without a grow-retrace; measured skew beyond it still
#: corrects itself through the overflow protocol
SAFETY_MARGIN = 1.5


def shuffle_stats_enabled() -> bool:
    if SHUFFLE_STATS_FORCE is not None:
        return SHUFFLE_STATS_FORCE
    return os.environ.get("YDB_TPU_SHUFFLE_STATS", "1") not in (
        "0", "", "off")


def size_buckets(local_rows: int, n_shards: int, heavy: int = 0,
                 margin: float = SAFETY_MARGIN) -> int:
    """Stats-sized per-destination send bucket for ``repartition``.

    Uniform keys spread ``local_rows`` evenly over ``n_shards``
    destinations, so the bucket holds mean × margin; a heavy hitter can
    pile its whole frequency onto one destination, so the estimate adds
    ``heavy`` (the table-wide count-min bound — every local occurrence
    routes to the same shard in the worst case). Rounded UP to a
    plan_fuse shape class (same-class re-runs reuse the compiled
    exchange) and clamped to the always-sufficient full capacity.
    Stats off (or a degenerate 1-shard mesh) keeps full capacity."""
    from ydb_tpu.ssa.plan_fuse import shape_class

    full = max(int(local_rows), 1)
    if n_shards <= 1 or not shuffle_stats_enabled():
        return full
    mean = -(-full // n_shards)
    est = int(mean * margin) + max(int(heavy), 0)
    return min(full, shape_class(est))


def row_bytes(schema) -> int:
    """Physical bytes one row ships in an exchange: column payloads
    plus one validity byte per column (host-side accounting helper —
    the traced exchange itself never calls this)."""
    return sum(f.type.physical.itemsize + 1 for f in schema.fields)


def exchange_bytes_per_device(schema, n_shards: int,
                              bucket_rows: int) -> int:
    """Bytes ONE device sends in one ``repartition`` exchange: a
    fixed-capacity bucket to every peer (static shapes — the shape of
    the all_to_all, not the live row count). Callers feed this to
    ``timeline.add_bytes("shuffle_bytes_dev<i>", ...)`` so per-device
    movement (and stats-sizing wins / skew grows) shows up as counter
    rates."""
    return int(n_shards) * int(bucket_rows) * row_bytes(schema)


def heavy_bound(stats, keys) -> int:
    """Heaviest joint-key frequency bound from aggregator statistics.

    Each key column's bound is the max matching ``ColumnStats.heavy``
    across tables (join keys may appear under the same name on both
    sides; the max stays conservative). A composite key occurs at most
    as often as its rarest component, so the joint bound is the min
    over per-key bounds — any single known component already bounds the
    pair. Unknown columns contribute nothing (0 = no bound)."""
    if not stats:
        return 0
    per_key = []
    for k in keys:
        best = 0
        for ts in stats.values():
            cs = getattr(ts, "columns", {}).get(k)
            if cs is not None:
                best = max(best, int(getattr(cs, "heavy", 0)))
        if best:
            per_key.append(best)
    return min(per_key) if per_key else 0

# splitmix64-style avalanche constants
_C1 = jnp.uint64(0xBF58476D1CE4E5B9)
_C2 = jnp.uint64(0x94D049BB133111EB)


def hash_rows(cols: list[Column]) -> jax.Array:
    """Vectorized 64-bit row hash over key columns (uint64)."""
    h = jnp.full(cols[0].data.shape, jnp.uint64(0x9E3779B97F4A7C15))
    for c in cols:
        k = c.data.astype(jnp.int64).astype(jnp.uint64)
        # null keys hash as a distinct class via the validity bit
        k = k ^ (c.validity.astype(jnp.uint64) << 63)
        x = h ^ k
        x = (x ^ (x >> 30)) * _C1
        x = (x ^ (x >> 27)) * _C2
        h = x ^ (x >> 31)
    return h


@jax.named_scope("ydb.shuffle")
def repartition(
    block: TableBlock,
    key_names: list[str],
    n_shards: int,
    bucket_rows: int | None = None,
    with_counts: bool = False,
) -> "TableBlock | tuple[TableBlock, jax.Array]":
    """Exchange rows so each shard owns hash(keys) % n_shards == its index.

    Must run inside shard_map over the ``shard`` axis. Returns a local
    block of capacity n_shards * bucket_rows. With ``with_counts``,
    returns (block, worst: int32 scalar) — the mesh-wide max rows any
    device wanted to send to one destination. worst > bucket_rows means
    rows were dropped somewhere; callers re-exchange with bucket_rows
    grown to hold ``worst`` exactly (the grace-join respill protocol,
    mkql_grace_join_imp.cpp bucket overflow, sized by the observed count
    instead of blind doubling)."""
    cap = block.capacity
    B = bucket_rows if bucket_rows is not None else cap
    live = block.row_mask()
    h = hash_rows([block.columns[k] for k in key_names])
    dest = (h % jnp.uint64(n_shards)).astype(jnp.int32)
    dest = jnp.where(live, dest, n_shards)  # dead rows -> drop bucket

    # stable-sort rows by destination => contiguous buckets
    from ydb_tpu.ssa import kernels

    order = kernels.stable_partition(dest, classes=n_shards + 1)
    dest_s = dest[order]
    # position of each row within its bucket
    ones = jnp.ones_like(dest_s, dtype=jnp.int32)
    counts = jnp.zeros(n_shards + 1, dtype=jnp.int32).at[dest_s].add(
        ones, mode="drop"
    )
    starts = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(counts)[:-1]]
    )
    pos_in_bucket = (
        jnp.arange(cap, dtype=jnp.int32) - starts[jnp.clip(dest_s, 0, n_shards)]
    )
    # scatter into (n_shards, B) send buffers; overflow/dead rows drop
    slot = jnp.where(
        (dest_s < n_shards) & (pos_in_bucket < B),
        dest_s * B + pos_in_bucket,
        n_shards * B,
    )

    sent_counts = jnp.minimum(counts[:n_shards], B)  # per-destination rows

    new_cols = {}
    for n, c in block.columns.items():
        d = c.data[order]
        v = c.validity[order]
        buf = jnp.zeros((n_shards * B,), dtype=d.dtype).at[slot].set(
            d, mode="drop"
        ).reshape(n_shards, B)
        vbuf = jnp.zeros((n_shards * B,), dtype=v.dtype).at[slot].set(
            v, mode="drop"
        ).reshape(n_shards, B)
        rd = jax.lax.all_to_all(buf, SHARD_AXIS, 0, 0, tiled=False)
        rv = jax.lax.all_to_all(vbuf, SHARD_AXIS, 0, 0, tiled=False)
        new_cols[n] = Column(rd.reshape(-1), rv.reshape(-1))

    recv_counts = jax.lax.all_to_all(
        sent_counts.reshape(n_shards, 1), SHARD_AXIS, 0, 0
    ).reshape(-1)  # rows received from each peer
    row = jnp.arange(B, dtype=jnp.int32)
    mask = (row[None, :] < recv_counts[:, None]).reshape(-1)

    big = TableBlock(
        new_cols, jnp.int32(n_shards * B), block.schema
    )
    out = kernels.compact(big, mask)
    if not with_counts:
        return out
    worst = jnp.max(counts[:n_shards])
    # a drop anywhere poisons every shard's result: reduce over the mesh
    # so every device (and the host, once) sees the same grow target
    worst = jax.lax.pmax(worst, SHARD_AXIS)
    return out, worst
