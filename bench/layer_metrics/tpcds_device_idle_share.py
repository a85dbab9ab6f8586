"""Device: 1 - the union of device-operation intervals over the traced
window, in %. `device_idle_share`'s formula, listed for the cell over
TPC-DS's store channel: what is idle there is the host moving the star
joins' sides through DQ's channels."""


def read(run):
    trace = run.get("trace")
    if not trace or not trace.get("busy_s"):
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
