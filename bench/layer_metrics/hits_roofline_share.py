"""Kernels (ssa/kernels.py) under a group-by whose groups are not known
ahead: the least time the chip could take over the time it was busy in
the traced window, in %. `device_roofline_share`'s formula, listed for
the cell over `hits`: least time = the bytes of the columns each
completed statement references over all rows of `hits` (work.py: 4, 8
and 12 B a row, the same whatever implements the group-by) over the
peak HBM bandwidth (peaks.json)."""


def read(run):
    trace = run.get("trace")
    if not trace or not trace.get("busy_s") or not run.get("least_seconds"):
        return None
    return 100.0 * run["least_seconds"] / trace["busy_s"]
