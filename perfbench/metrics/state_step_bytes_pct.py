"""state_step_bytes_pct: of the bytes the window's decode token steps moved as the program runs them — every layer's weights and the head once a step, the recurrent state and conv tail of every slot-step ADVANCED read and written (stats()['ssm']: idle slots too), the K/V rows VISITED (stats()['attn']) — the share that is the state layers' own: their weights once a step, and the state and tail. By the family's byte functions (families/<model_type>.py: state_weight_bytes, state_bytes_per_slot, matmul_params, kv_bytes_per_token); how much of a step the mechanism is."""


def read(ctx):
    from pb import plug

    fam = plug.family_of(ctx["dims"])
    if not hasattr(fam, "state_weight_bytes") or not hasattr(fam, "ssm_window"):
        return None
    p, dims = ctx["program"], ctx["dims"]
    w = fam.ssm_window(p)
    a0, a1 = (p.get("stats0") or {}).get("attn"), (p.get("stats1") or {}).get("attn")
    if w is None or not a0 or not a1 or w["decode"]["slot_steps"] <= 0:
        return None
    slot_steps = w["decode"]["slot_steps"]
    steps = slot_steps / float(ctx["mix"]["replica"]["num_slots"])  # every slot is advanced in every token step
    rows = a1["rows_visited"] - a0["rows_visited"]  # summed over token steps and layers
    weights = 2.0 * fam.matmul_params(dims) * steps  # the state layers' own among them
    moved = 2.0 * fam.state_bytes_per_slot(dims) * slot_steps  # state and tail, read and written
    kv = rows * fam.kv_bytes_per_token(dims) / dims["layers"]
    state, total = fam.state_weight_bytes(dims) * steps + moved, weights + moved + kv
    print(f"state layers' bytes: {state / steps / 1e9:.3f} GB of the {total / steps / 1e9:.3f} GB a token step moved "
          f"({steps:.0f} steps; weights {weights / steps / 1e9:.3f}, K/V rows visited {kv / steps / 1e9:.3f})", flush=True)
    return 100.0 * state / total
