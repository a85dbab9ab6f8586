"""Ranking windows (kernels.window_rank as the DQ executor runs it,
dq/compute.py `_whole_input`): the statement thread's self time beneath
the DQ stage span that ranks the stage's whole input (`window` on its
`dispatch program=dq_stage` span): the input merged, the sort passes,
the run heads and scans, the ranks scattered back, the partitions read
back, the program after it, the output routed: `stages["dq_window"]`,
mean per statement, in ms. A program whose profiles have no such key has
nothing to read here."""


def read(run):
    got = [s["stages"]["dq_window"] for s in run["statements"]
           if "dq_window" in (s.get("stages") or {})]
    if not got:
        return None
    return 1000.0 * sum(got) / len(got)
