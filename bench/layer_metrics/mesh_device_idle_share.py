"""Device, over a mesh: 1 - busy over the traced window, in %, with busy
the mean over the mesh's devices of each one's union of
device-operation intervals (`trace_reduce.reduce`). A mesh that works
one device at a time reads 1 - 1/n at best."""


def read(run):
    trace = run.get("trace")
    if not trace or not trace.get("busy_s") or not trace.get("window_s"):
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
