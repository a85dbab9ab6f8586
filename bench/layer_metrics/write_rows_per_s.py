"""Write path (tx/sharded.py): rows written through `ShardedTable.insert`
over the seconds of its `write` spans (`write.rows` / `write.seconds` of
the process counters), the whole process: what the harness's own
`load <table>:` lines add up to, from inside the program."""


def read(run):
    try:
        import write_counters as wc

        seconds = wc.count("write", "seconds")
        return wc.count("write", "rows") / seconds if seconds else None
    except ImportError:
        return None
