"""sparse_decode_hbm_roofline_pct: Bytes a decode token step has to read (attention, dense, router and head weights once; experts_hit_per_step x one expert's bytes in each expert layer; the full layers' K/V of the live positions; the window layers' K/V of min(positions, window) a live request: the family's sparse_decode_step_bytes) over the HBM peak, over the decode-fold executable's device time per token step."""


def read(ctx):
    import statistics

    from pb import plug, xplane

    t = ctx["trace"]
    if not t or not t.get("devices") or ctx["peaks"] is None:
        return None
    fam = plug.family_of(ctx["dims"])
    hit = fam.experts_hit_per_step(ctx["program"]) if hasattr(fam, "experts_hit_per_step") else None
    durs = xplane.module_durations(t["modules"], ctx["params"]["match"])
    if hit is None or not durs:
        return None
    fold = int(ctx["mix"]["replica"].get("decode_fold", 1))
    step_s = statistics.median(durs) / fold
    # positions live in the batch, averaged over the window: a request holds its prompt from its first token on
    # and grows by a token a step until its last; a window layer reads at most `window` of them.
    sec, win = float(ctx["seconds"]), ctx["dims"]["window"]
    live = in_window = 0.0
    for r in ctx["program"]["records"]:
        if not r["recv_s"]:
            continue
        a, b = max(0.0, r["recv_s"][0]), min(sec, r["recv_s"][-1])
        if b > a:
            n = r["prompt_len"] + len(r["tokens"]) / 2.0
            live += (b - a) * n
            in_window += (b - a) * min(n, win)
    need = fam.sparse_decode_step_bytes(ctx["dims"], live / sec, in_window / sec, hit)
    print(f"sparse decode step: {1000 * step_s:.3f} ms on the device; {live / sec:.0f} live positions, "
          f"{in_window / sec:.0f} of them inside a window; {hit:.2f} experts hit a layer; {need / 1e9:.3f} GB to read", flush=True)
    return 100.0 * (need / ctx["peaks"]["hbm_bytes_per_s"]) / step_s
