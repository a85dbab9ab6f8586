"""Write path (tx/sharded.py, tx/coordinator.py): everything of a write
that is not the portion's own stages: dictionary encode, the hash route
and the mask copies a shard, the casts into the insert buffer, and the
coordinator's own time with the portion's bookkeeping and its
promotion's enqueue (`write.encode` + `write.route` + `write.buffer` +
`write.commit`), self seconds a 10^6 rows written, in ms."""


def read(run):
    try:
        import write_counters as wc

        return wc.stage_ms_per_mrow("encode", "route", "buffer", "commit")
    except ImportError:
        return None
