"""Kernels (ssa/join.py, ssa/kernels.py, parallel/shuffle.py) under a
join over the mesh: the least time the chips could take over the time
they were busy in the traced window, in %. Least time = the bytes of the
columns each completed statement references over all rows of its FROM
tables (work.py: the same whatever implements the join) over the peak
HBM bandwidth of every traced device together (`least_seconds` is one
chip's, peaks.json; a table sharded over n chips is read by n at once).
Busy time = the mean over the devices of each one's busy seconds
(`trace_reduce.reduce`). Four devices each busy for a quarter of the
bytes at its peak read 100. No share of an ICI peak: Q3 at SF 1 sends
~30 MB a device a statement, ~0.15 ms at 1,600 Gbit/s."""


def read(run):
    trace = run.get("trace")
    if not trace or not trace.get("busy_s") or not trace.get("devices") \
            or not run.get("least_seconds"):
        return None
    return 100.0 * run["least_seconds"] / trace["devices"] / trace["busy_s"]
