"""Front (kqp/session.py, obs/profile.py): the share of a statement's
server seconds that a named leaf span of its thread explains, 100 x (1 -
`stages["unattributed"]` / `server_s`), mean per statement, in %. A
refactor that drops a span shows here."""


def read(run):
    got = [1.0 - s["stages"]["unattributed"] / s["server_s"]
           for s in run["statements"]
           if "unattributed" in (s.get("stages") or {})
           and s.get("server_s")]
    if not got:
        return None
    return 100.0 * sum(got) / len(got)
