"""Scan + staging (engine/scan.py, dq/compute.py): what the dispatching
thread waited for the staging pipeline's next block (`scan.pull` spans:
`stages["pull"]`), mean per statement, in ms. Staging on the critical
path, where `host_stage_ms` is the producer's sum over its threads."""


def read(run):
    got = [s["stages"]["pull"] for s in run["statements"]
           if "pull" in (s.get("stages") or {})]
    if not got:
        return None
    return 1000.0 * sum(got) / len(got)
