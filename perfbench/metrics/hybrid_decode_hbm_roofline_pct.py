"""hybrid_decode_hbm_roofline_pct: Bytes a decode token step has to move (the weights outside the routed experts once; experts_hit_per_step x one expert's bytes in each expert layer; the state and conv tail of the LIVE requests read and written; the attention layers' K/V of the live positions: the family's hybrid_decode_step_bytes) over the HBM peak, over the decode-fold executable's device time per token step."""


def read(ctx):
    import statistics

    from pb import plug, xplane

    t = ctx["trace"]
    if not t or not t.get("devices") or ctx["peaks"] is None:
        return None
    fam = plug.family_of(ctx["dims"])
    if not hasattr(fam, "hybrid_decode_step_bytes"):
        return None
    hit, w = fam.experts_hit_per_step(ctx["program"]), fam.ssm_window(ctx["program"])
    durs = xplane.module_durations(t["modules"], ctx["params"]["match"])
    if hit is None or w is None or w["decode"]["slot_steps"] <= 0 or not durs:
        return None
    fold = int(ctx["mix"]["replica"].get("decode_fold", 1))
    step_s = statistics.median(durs) / fold
    # live requests a token step, by the program's own count; the positions they hold, from the client's
    # records: a request holds its prompt from its first token on and grows by a token a step until its last
    slots = int(ctx["mix"]["replica"]["num_slots"])
    live_slots = slots * w["decode"]["slot_steps_live"] / w["decode"]["slot_steps"]
    sec, positions = float(ctx["seconds"]), 0.0
    for r in ctx["program"]["records"]:
        if not r["recv_s"]:
            continue
        a, b = max(0.0, r["recv_s"][0]), min(sec, r["recv_s"][-1])
        if b > a:
            positions += (b - a) * (r["prompt_len"] + len(r["tokens"]) / 2.0)
    need = fam.hybrid_decode_step_bytes(ctx["dims"], live_slots, positions / sec, hit)
    print(f"hybrid decode step: {1000 * step_s:.3f} ms on the device; {live_slots:.1f} of {slots} slots live, "
          f"{positions / sec:.0f} live positions; {hit:.2f} experts hit a layer; {need / 1e9:.3f} GB to move", flush=True)
    return 100.0 * (need / ctx["peaks"]["hbm_bytes_per_s"]) / step_s
