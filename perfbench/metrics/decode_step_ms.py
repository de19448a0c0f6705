"""decode_step_ms: Device trace: device time of the decode-fold executable per token step (its duration over the fold depth), median over the traced window."""


def read(ctx):
    import statistics

    from pb import xplane

    t = ctx["trace"]
    if not t or not t.get("devices"):
        return None
    fold = int(ctx["mix"]["replica"].get("decode_fold", 1))
    durs = xplane.module_durations(t["modules"], ctx["params"]["match"])
    return 1000.0 * statistics.median(durs) / fold if durs else None
