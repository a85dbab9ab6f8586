"""Plan + executor choice (engine/scan.py, dq/compute.py, ssa/join.py):
the statement thread's self time in `dispatch` spans (enqueueing device
programs) and, on the DQ executor, in `dq.pump` and `dq.exchange` (the
actor loop and the host side of the channels): `stages["dispatch"]`,
mean per statement, in ms. Over `launches_per_stmt` it is the host cost
of one launch."""


def read(run):
    got = [s["stages"]["dispatch"] for s in run["statements"]
           if "dispatch" in (s.get("stages") or {})]
    if not got:
        return None
    return 1000.0 * sum(got) / len(got)
