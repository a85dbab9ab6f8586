"""Mesh (parallel/mesh_exec.py `_repartition`, parallel/shuffle.py): the
statement thread's self time beneath the `mesh.shuffle` spans, one a
side of a join: every exchange over the chips enqueued
(`dispatch program=mesh_repartition`), the wait for its worst bucket
count and the wait that slices its output: `stages["mesh_shuffle"]`,
mean per statement, in ms. A program without the key (before PR 35), or
a statement that exchanged nothing over the mesh, has nothing to read
here."""


def read(run):
    got = [s["stages"]["mesh_shuffle"] for s in run["statements"]
           if "mesh_shuffle" in (s.get("stages") or {})]
    if not got:
        return None
    return 1000.0 * sum(got) / len(got)
