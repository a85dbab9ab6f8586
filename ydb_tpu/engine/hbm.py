"""What the HBM caches may claim, derived from the device JAX found.

One v5e has 16 GB for everything: the tables the resident tier pins,
the block cache, a DQ graph's channel blocks, and the temporaries of
whichever program is running (the TPC-H Q1 partial takes 1.9 GB at a
2^20-row block, compiled for a v5e). The automatic budgets are shares of
what the device reports, so their sum leaves room for those temporaries
on any chip:

    resident tier   1/2   (device-wide, across every shard's store)
    block cache     1/8   (per cache; bypassed while the resident tier
                           is on, see ColumnShard.scan / _scan_node)
    DQ channels     1/8   (device-wide, across every DQ graph: what the
                           resident tier and the programs leave,
                           ChannelBudget / channels())
    left            3/8   program temporaries, staging, results

On a v5e a join over an 18M-row bucket takes 4.5 GB beside a resident
tier of 7.75; with a quarter of the device for the channels, TPC-DS q7
peaked at 15.3 GB of the 16.9 the device reports.

On the CPU backend "device" memory is host RSS and the out-of-core
tests own that bound, so these budgets are 0 there, and the channels
have none.
"""

from __future__ import annotations

import functools
import threading

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


def channel_budget() -> int | None:
    """HBM bytes the DQ channel blocks of every graph may hold on the
    device together: the device less the resident tier's budget less the
    3/8 left to the programs; None where the backend reports no limit
    (CPU)."""
    total = device_bytes()
    if not total:
        return None
    return max(total - resident_budget() - 3 * total // 8, 0)


class ChannelBudget:
    """The HBM bytes DQ channel blocks hold on the chip, against ``limit``
    (None: no limit). The process has one (``channels()``), which every
    graph's actors take from and give back to on their own threads; a
    test may make its own for a graph (``dq/compute.build_stage_graph``)."""

    def __init__(self, limit: int | None = None):
        self.limit = limit
        self.held = 0
        self.peak = 0
        # reentrant: a dropped channel block gives its bytes back from
        # its finalizer, which may run on a thread that holds the lock
        self._lock = threading.RLock()

    def take(self, nbytes: int) -> bool:
        """Hold ``nbytes`` more, unless that would pass the limit."""
        with self._lock:
            if self.limit is not None and self.held + nbytes > self.limit:
                return False
            self.held += nbytes
            self.peak = max(self.peak, self.held)
            return True

    def release(self, nbytes: int) -> None:
        with self._lock:
            self.held -= nbytes


@functools.lru_cache(maxsize=None)
def channels() -> ChannelBudget:
    """The process's one channel budget, ``channel_budget()`` bytes."""
    return ChannelBudget(channel_budget())
