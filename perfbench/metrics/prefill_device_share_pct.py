"""prefill_device_share_pct: Device trace: summed device time of the admission executables (bucketed prefill, the rows' write, the first-token sample) over the traced window's busy time. Every decoding slot waits while one runs."""


def read(ctx):
    from pb import xplane

    t = ctx["trace"]
    if not t or not t.get("devices") or not t.get("busy_s"):
        return None
    durs = xplane.module_durations(t["modules"], ctx["params"]["match"])
    if not durs:
        return None
    print(f"admissions on the device: {len(durs)} executables, {sum(durs):.3f} s of the traced window's "
          f"{t['busy_s']:.3f} s busy", flush=True)
    return 100.0 * sum(durs) / (t["busy_s"] * t["devices"])
