"""HBM tiers: the most the device held at once, from
memory_stats()["peak_bytes_in_use"] after the window, in GB."""


def read(run):
    peak = run["memory_peak_bytes"]
    return peak / 1e9 if peak else None
