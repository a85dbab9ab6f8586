"""Mesh (parallel/mesh_exec.py, parallel/dist.py): the statement
thread's self time in the mesh executor's own spans, those beneath the
`mesh` span and outside its per-shard `scan` spans: placing the shards'
blocks on the mesh, enqueueing the collective step, the wait for it and
the answer's copy out: `stages["mesh"]`, mean per statement, in ms. A
program without the key (before PR 31), or a statement that no mesh
executor answered, has nothing to read here."""


def read(run):
    got = [s["stages"]["mesh"] for s in run["statements"]
           if "mesh" in (s.get("stages") or {})]
    if not got:
        return None
    return 1000.0 * sum(got) / len(got)
