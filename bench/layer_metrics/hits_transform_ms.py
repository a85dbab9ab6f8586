"""Kernels (ssa/kernels.py `group_ids_sorted`, `fused_group_reduce`'s
scatter tier, `scatter_first`, `sort_perm`) as the walk runs them over a
scan's concatenated output (plan/executor.py `_transform_node`): the
statement thread's self time beneath the `transform` span, the
Transform's program enqueued and waited for: `stages["transform"]`, mean
per statement, in ms. A program without the key (before PR 37), or a
statement whose aggregate was pushed into its scan, has nothing to read
here."""


def read(run):
    got = [s["stages"]["transform"] for s in run["statements"]
           if "transform" in (s.get("stages") or {})]
    if not got:
        return None
    return 1000.0 * sum(got) / len(got)
