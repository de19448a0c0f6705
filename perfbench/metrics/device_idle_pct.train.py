"""device_idle_pct.train: Device trace: 1 - (union of the device's operation intervals) / (traced window), averaged over the chips."""


def read(ctx):
    t = ctx["trace"]
    if not t or not t.get("devices") or not t.get("window_s"):
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
