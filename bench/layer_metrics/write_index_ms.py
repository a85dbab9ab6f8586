"""Write path (engine/shard.py `_write_portion`): the portion's zone maps,
key statistics and `PortionMeta` (`write.index`), self seconds a 10^6
rows written, in ms."""


def read(run):
    try:
        import write_counters as wc

        return wc.stage_ms_per_mrow("index")
    except ImportError:
        return None
