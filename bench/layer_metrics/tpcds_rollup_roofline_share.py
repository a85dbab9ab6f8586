"""Kernels (kernels.rollup) under GROUP BY ROLLUP: the least time the
chip could take over the rollup stage's time, in %. The least bytes are
the program's process counter `component=rollup` `bytes_least` (each
column of the finest level read once and every level's rows written
once, data and a validity byte: dq/compute.py `_WholeInput.least_bytes`)
over its `rollups`, one execution's, at the peak HBM bandwidth
(peaks.json: 819 GB/s a v5e); the time is the mean `stages["dq_rollup"]`
of the window's statements, host seconds that hold the device's, so a
reading can only be low. Nothing to read without those counters and
that key, or on a device whose peaks are not known."""


def least_share(component: str, runs: str, stage: str, run):
    """100 x one execution's least bytes at the peak bandwidth over the
    mean seconds of the statement key ``stage``; None where anything is
    missing."""
    try:
        import jax
        import work
        from ydb_tpu.obs.counters import root_counters
    except ImportError:
        return None
    try:
        peak = work.peaks_for(jax.devices()[0].device_kind)[
            "hbm_bytes_per_s"]
    except work.UnknownDevice:
        return None
    g = root_counters().group(component=component)
    executions = g.counter(runs).value
    seconds = [s["stages"][stage] for s in run["statements"]
               if stage in (s.get("stages") or {})]
    if not executions or not seconds or not sum(seconds):
        return None
    least = g.counter("bytes_least").value / executions / peak
    return 100.0 * least / (sum(seconds) / len(seconds))


def read(run):
    return least_share("rollup", "rollups", "dq_rollup", run)
