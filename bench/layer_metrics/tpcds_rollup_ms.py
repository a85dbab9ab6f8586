"""ROLLUP levels (kernels.rollup as the DQ executor runs it,
dq/compute.py `_whole_input`): the statement thread's self time beneath
the DQ stage span that rolls the merged group-by up (`rollup_levels` on
its `dispatch program=dq_stage` span): each level's rows read back, the
levels built and placed one after another, the program after them, the
output routed: `stages["dq_rollup"]`, mean per statement, in ms. A
program whose profiles have no such key (one without GROUP BY ROLLUP)
has nothing to read here."""


def read(run):
    got = [s["stages"]["dq_rollup"] for s in run["statements"]
           if "dq_rollup" in (s.get("stages") or {})]
    if not got:
        return None
    return 1000.0 * sum(got) / len(got)
