"""collective_exposed_pct: Device trace: time in collective operations during which no compute operation runs on that device, over the traced window, averaged over the chips."""


def read(ctx):
    t = ctx["trace"]
    if not t or not t.get("devices") or ctx["chips"] < 2 or not t.get("busy_s"):
        return None
    return 100.0 * t["collective_exposed_s"] / t["window_s"]
