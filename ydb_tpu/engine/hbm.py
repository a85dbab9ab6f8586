"""What the HBM caches may claim, derived from the device JAX found.

One v5e has 16 GB for everything: the tables the resident tier pins,
the block cache, and the temporaries of whichever program is running
(the TPC-H Q1 partial takes 1.9 GB at a 2^20-row block, compiled for a
v5e). The automatic budgets are shares of what the device reports, so
their sum leaves room for those temporaries on any chip:

    resident tier   1/2   (device-wide, across every shard's store)
    block cache     1/8   (per cache; bypassed while the resident tier
                           is on, see ColumnShard.scan / _scan_node)
    left            3/8   program temporaries, staging, results

On the CPU backend "device" memory is host RSS and the out-of-core
tests own that bound, so both shares are 0 there.
"""

from __future__ import annotations

import functools

RESIDENT_SHARE = 2   # 1/2 of the device
BLOCK_CACHE_SHARE = 8  # 1/8 of the device


@functools.lru_cache(maxsize=None)
def _device_bytes(backend: str) -> int:
    import jax

    if backend not in ("tpu", "gpu"):
        return 0
    stats = jax.local_devices()[0].memory_stats() or {}
    return int(stats.get("bytes_limit", 0))


def device_bytes() -> int:
    """HBM bytes of one local device as it reports them (0 on CPU)."""
    import jax

    return _device_bytes(jax.default_backend())


def resident_budget() -> int:
    return device_bytes() // RESIDENT_SHARE


def block_cache_budget() -> int:
    return device_bytes() // BLOCK_CACHE_SHARE
