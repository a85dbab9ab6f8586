"""Joins (ssa/join.py's lookup join, as the DQ executor's join stages
run it, dq/compute.py `_join_bucket`): the share of lookup joins that
found their build rows in the direct-addressed table rather than by
binary search, in %: 100 x the process counter `component=join`
`lookups{path=direct}` over `lookups` of both paths, one a join task,
the whole process. A program without the counter has nothing to read
here."""


def read(run):
    try:
        import write_counters as wc
    except ImportError:
        return None
    direct = wc.count("join", "lookups", path="direct")
    every = direct + wc.count("join", "lookups", path="search")
    return 100.0 * direct / every if every else None
