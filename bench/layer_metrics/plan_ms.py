"""Plan + executor choice (kqp/session.py, plan/executor.py): the self
time of the statement thread's planning spans (`plan`, `parse`,
`ssa.compile`, `snapshot`, `scan.prune`, `plan.signature`, `dq.lower`,
`dq.build`: `stages["plan"]` of the QueryProfile), mean per statement,
in ms."""


def read(run):
    got = [s["stages"]["plan"] for s in run["statements"]
           if "plan" in (s.get("stages") or {})]
    if not got:
        return None
    return 1000.0 * sum(got) / len(got)
