"""Scan + staging under the mesh walk (parallel/mesh_exec.py over
engine/scan.py): the statement thread's time in the shards' scans, which
it runs one after another: `stages["pull"] + stages["dispatch"] +
stages["device_wait"]` of the statements a mesh executor answered (those
with a `mesh` key, which holds the mesh's own spans apart), mean per
statement, in ms. A program without the key has nothing to read here."""


def read(run):
    got = [sum(s["stages"].get(k, 0.0)
               for k in ("pull", "dispatch", "device_wait"))
           for s in run["statements"]
           if "mesh" in (s.get("stages") or {})]
    if not got:
        return None
    return 1000.0 * sum(got) / len(got)
