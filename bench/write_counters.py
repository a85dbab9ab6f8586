"""What the write-path readers (``layer_metrics/write_*.py``,
``promote_*.py``, ``resident_lag_ms.py``) share: the program's process
counters ``component=write | resident`` (``ydb_tpu.obs.counters.
root_counters()``: they outlive the deployment, and the readers run once
it is freed). They count the whole process: the load, the upsert
probe's two writes, the count check's and the warm-up's heat promotions.
A program without them reads 0 everywhere, and a reader that finds 0
where it divides has nothing to read."""


def group(component: str, **labels):
    from ydb_tpu.obs.counters import root_counters

    g = root_counters().group(component=component)
    return g.group(**labels) if labels else g


def count(component: str, name: str, **labels) -> float:
    return float(group(component, **labels).counter(name).value)


def stage_ms_per_mrow(*stages: str):
    """The write's self seconds in ``stages`` a 10^6 rows written, in
    ms; None where no row was written or no stage was timed (a program
    without the counters; profiling off)."""
    rows = count("write", "rows")
    seconds = sum(count("write", "stage_seconds", stage=s) for s in stages)
    if not rows or not seconds:
        return None
    return 1e3 * seconds / (rows / 1e6)
