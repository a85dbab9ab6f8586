"""Write path (engine/resident.py `promote_async`): promotions asked for
and not queued, over the reasons (`inflight_full`: four in flight;
`in_flight`: this portion already; `disabled`). Each is a portion that
reaches HBM only after two scans have missed it. 0 is a reading; a
program that counts no promotion at all has nothing to read."""


def read(run):
    try:
        import write_counters as wc

        declined = sum(wc.count("resident", "promote_declined", reason=r)
                       for r in ("inflight_full", "in_flight", "disabled"))
        if not declined and not wc.count("resident", "promotions"):
            return None
        return declined
    except ImportError:
        return None
