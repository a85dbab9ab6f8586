"""Scan + staging on the walk's path for an aggregate that does not push
down (engine/scan.py `run_stream`, blocks/block.py `concat_blocks`): the
statement thread's self time beneath the `host.concat` span, which turns
the scan's block outputs into the one block a Transform reads (every
block copied out of the device, numpy's concatenate, the result staged
back): `stages["concat"]`, mean per statement, in ms. A program without
the key (before PR 37), or a statement whose scan ended in a final
program, has nothing to read here."""


def read(run):
    got = [s["stages"]["concat"] for s in run["statements"]
           if "concat" in (s.get("stages") or {})]
    if not got:
        return None
    return 1000.0 * sum(got) / len(got)
