"""loop_host_ms_per_fold: stats()['spans'], window difference: summed seconds of every span of the replica's loop thread except serve.loop.idle (no work) and those in which it is blocked on the device (serve.engine.harvest_wait, serve.engine.key_wait, serve.engine.admit_wait), over the decode folds dispatched."""


def read(ctx):
    from pb import spans

    w = spans.window(ctx)
    if w is None or w["folds"] <= 0:
        return None
    host = {
        name: row["s"] for name, row in w["segments"].items()
        if name.startswith(spans.LOOP_PREFIXES) and name not in spans.NOT_HOST_WORK
    }
    per_fold = {k: v / w["folds"] for k, v in host.items()}
    print(f"loop host time per fold over {w['folds']} folds: {spans.split(per_fold)}", flush=True)
    return 1000.0 * sum(host.values()) / w["folds"]
