"""Kernels (ssa/kernels.py, ssa/join.py): the least time the chip could
take over the time it was busy in the traced window, in %. Least time =
bytes of the columns each completed statement references over all rows
of its FROM tables (work.py) over the peak HBM bandwidth (peaks.json):
bound by bandwidth."""


def read(run):
    trace = run.get("trace")
    if not trace or not trace["busy_s"] or not run["least_seconds"]:
        return None
    return 100.0 * run["least_seconds"] / trace["busy_s"]
