"""Write path (engine/portion.py `write_portion_blob`, engine/shard.py
`_log`): the portion's chunks encoded and put in the blob store and its
`add_portion` record logged (`write.blob` + `write.log`), self seconds a
10^6 rows written, in ms."""


def read(run):
    try:
        import write_counters as wc

        return wc.stage_ms_per_mrow("blob", "log")
    except ImportError:
        return None
