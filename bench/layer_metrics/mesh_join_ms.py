"""Mesh (parallel/mesh_exec.py `_local_lookup` / `_local_expand` over
ssa/join.py): the statement thread's self time beneath the `mesh.join`
spans: each device-local join enqueued (`dispatch program=mesh_lookup`,
`mesh_match`, `mesh_expand`) and the wait for an expanding join's
totals: `stages["mesh_join"]`, mean per statement, in ms. A program
without the key (before PR 35), or a statement that joined nothing over
the mesh, has nothing to read here."""


def read(run):
    got = [s["stages"]["mesh_join"] for s in run["statements"]
           if "mesh_join" in (s.get("stages") or {})]
    if not got:
        return None
    return 1000.0 * sum(got) / len(got)
