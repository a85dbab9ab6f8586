"""Device: what the statement thread spent blocked on the chip or its
link (`device.wait` and `device.get` spans: `stages["device_wait"]`),
mean per statement, in ms. The statement's `server_s` minus it bounds
the idle time that host code can cause."""


def read(run):
    got = [s["stages"]["device_wait"] for s in run["statements"]
           if "device_wait" in (s.get("stages") or {})]
    if not got:
        return None
    return 1000.0 * sum(got) / len(got)
