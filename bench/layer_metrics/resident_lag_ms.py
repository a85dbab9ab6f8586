"""Write path (engine/shard.py -> engine/resident.py): a freshly written
portion's time from its commit logged to its bytes admitted on the
device, the mean of `resident_lag_seconds`, in ms: a write's time to be
scannable from HBM. Eager promotions only; a declined one has no
sample."""


def read(run):
    try:
        import write_counters as wc

        h = wc.group("resident").histogram("resident_lag_seconds")
        return 1e3 * h.total / h.count if h.count else None
    except ImportError:
        return None
