"""Kernels (ssa/join.py, ssa/kernels.py) under star joins: the least
time the chip could take over the time it was busy in the traced window,
in %. `device_roofline_share`'s formula, listed for the cell over
TPC-DS's store channel: least time = the bytes of the columns each
completed statement references over all rows of its tables (work.py:
the three statements' `TABLES`, the same whatever executor answers them)
over the peak HBM bandwidth (peaks.json)."""


def read(run):
    trace = run.get("trace")
    if not trace or not trace.get("busy_s") or not run.get("least_seconds"):
        return None
    return 100.0 * run["least_seconds"] / trace["busy_s"]
