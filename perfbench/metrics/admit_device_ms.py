"""admit_device_ms: Device trace: device time of one admission executable (the bucketed prefill with its chunked scan, the expert matmuls at prompt width, the state and cache writes and the first-token sample), median over the admissions of the traced window. Every decoding slot waits while it runs."""


def read(ctx):
    import statistics

    from pb import xplane

    t = ctx["trace"]
    if not t or not t.get("devices"):
        return None
    durs = xplane.module_durations(t["modules"], ctx["params"]["match"])
    if not durs:
        return None
    print(f"admissions on the device: {len(durs)} in the traced window, {1000 * min(durs):.3f} to "
          f"{1000 * max(durs):.3f} ms each", flush=True)
    return 1000.0 * statistics.median(durs)
