"""Scan + staging under a join over the mesh (parallel/mesh_exec.py
`_scan` over engine/scan.py): the statement thread's time in the sides'
scans, which it runs one shard after another, each block compacted,
fetched, concatenated and placed back on the mesh: `stages["pull"] +
stages["dispatch"] + stages["device_wait"] + stages["fetch"]` of the
statements that exchanged rows over the mesh (those with a
`mesh_shuffle` key, which with `mesh_join` and `mesh` holds the mesh's
own spans apart), mean per statement, in ms. A program without the key
(before PR 35) has nothing to read here."""


def read(run):
    got = [sum(s["stages"].get(k, 0.0)
               for k in ("pull", "dispatch", "device_wait", "fetch"))
           for s in run["statements"]
           if "mesh_shuffle" in (s.get("stages") or {})]
    if not got:
        return None
    return 1000.0 * sum(got) / len(got)
