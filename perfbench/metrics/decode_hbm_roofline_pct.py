"""decode_hbm_roofline_pct: Bytes a decode step has to read (every block's weights and the head once, and the keys and values of the tokens that are live in the batch, from shapes and the window's slot lengths) over the HBM peak, over decode_step_ms."""


def read(ctx):
    import statistics

    from pb import xplane

    t = ctx["trace"]
    if not t or not t.get("devices") or ctx["peaks"] is None:
        return None
    fold = int(ctx["mix"]["replica"].get("decode_fold", 1))
    durs = xplane.module_durations(t["modules"], ctx["params"]["match"])
    if not durs:
        return None
    step_s = statistics.median(durs) / fold
    # live tokens in the batch, averaged over the window: each request holds its prompt from its
    # first token on and grows by one token a step until its last.
    sec = float(ctx["seconds"])
    live = 0.0
    for r in ctx["program"]["records"]:
        if not r["recv_s"]:
            continue
        a, b = max(0.0, r["recv_s"][0]), min(sec, r["recv_s"][-1])
        if b > a:
            live += (b - a) * (r["prompt_len"] + len(r["tokens"]) / 2.0)
    live /= sec
    need = ctx["costs"].decode_step_bytes(ctx["dims"], live)
    print(f"decode step: {1000 * step_s:.3f} ms on the device; {live:.0f} live KV tokens; {need / 1e9:.3f} GB to read", flush=True)
    return 100.0 * (need / ctx["peaks"]["hbm_bytes_per_s"]) / step_s
