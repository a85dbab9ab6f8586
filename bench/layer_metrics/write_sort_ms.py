"""Write path (engine/shard.py `_commit` / `_write_portion`): the
buffered batches joined and the portion put in key order (`write.concat`
+ `write.sort`: lexsort, last of equal keys, the permutation of every
column), self seconds a 10^6 rows written, in ms."""


def read(run):
    try:
        import write_counters as wc

        return wc.stage_ms_per_mrow("sort", "concat")
    except ImportError:
        return None
