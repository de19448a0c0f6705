"""result_rpcs_per_s: stats()['spans'], window difference: count of serve.rpc.result over the window's seconds."""


def read(ctx):
    from pb import spans

    w = spans.window(ctx)
    if w is None:
        return None
    n = w["segments"].get("serve.rpc.result", {"n": 0})["n"]
    print(f"result RPCs: {n} answered in {w['seconds']:.2f} s", flush=True)
    return n / w["seconds"]
