"""rpc_busy_pct: stats()['spans'], window difference: summed seconds of serve.rpc.submit, serve.rpc.result and serve.rpc.stats (work and lock wait on the actor's RPC thread; a long poll's sleep is serve.rpc.result_wait and is left out) over the window's seconds."""


def read(ctx):
    from pb import spans

    w = spans.window(ctx)
    if w is None:
        return None
    busy = {k: w["segments"][k]["s"] for k in ("serve.rpc.submit", "serve.rpc.result", "serve.rpc.stats") if k in w["segments"]}
    print(f"RPC thread busy: {spans.split(busy)} in {w['seconds']:.2f} s", flush=True)
    return 100.0 * sum(busy.values()) / w["seconds"]
