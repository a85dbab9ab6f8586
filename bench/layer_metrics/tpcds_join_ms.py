"""Joins (ssa/join.py as the DQ executor's join stages run it,
dq/compute.py): the statement thread's self time beneath the join
stages' spans, each bucket's two sides concatenated and staged on the
device, the join's programs enqueued and waited for, its output copied
out: `stages["dq_join"]`, mean per statement, in ms. A program whose
profiles have no such key, or a statement without a DQ join, has
nothing to read here."""


def read(run):
    got = [s["stages"]["dq_join"] for s in run["statements"]
           if "dq_join" in (s.get("stages") or {})]
    if not got:
        return None
    return 1000.0 * sum(got) / len(got)
