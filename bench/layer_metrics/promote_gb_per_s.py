"""Write path (engine/resident.py `_put_and_admit`): bytes admitted to
the resident tier over the seconds of the `resident.promote.put` spans
(the pads and the device put of every column), in GB/s: the host-to-HBM
rate of a promotion, eager or heat-driven."""


def read(run):
    try:
        import write_counters as wc

        seconds = wc.count("resident", "promote_seconds", stage="put")
        if not seconds:
            return None
        return wc.count("resident", "promote_bytes") / seconds / 1e9
    except ImportError:
        return None
