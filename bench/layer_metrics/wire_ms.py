"""Front (api/pgwire.py, kqp/session.py): what the wire adds to a
statement. Client-side latency minus the server's own seconds for the
same statement (its QueryProfile in the cluster's ring), mean per
statement, in ms."""


def read(run):
    pairs = [(s["client_s"], s["server_s"]) for s in run["statements"]
             if s.get("server_s") is not None]
    if not pairs:
        return None
    return 1000.0 * sum(c - v for c, v in pairs) / len(pairs)
