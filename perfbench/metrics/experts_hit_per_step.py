"""experts_hit_per_step: stats()['moe'], window difference: held experts that received at least one token, summed over expert layers and decode token steps, over (token steps x expert layers). What a decode step's expert bytes follow."""


def read(ctx):
    from pb import plug

    fam = plug.family_of(ctx["dims"])
    if not hasattr(fam, "moe_window"):
        return None
    w = fam.moe_window(ctx["program"])
    v = fam.experts_hit_per_step(ctx["program"])
    if v is None:
        return None
    d = w["decode"]
    print(f"held experts hit a step and expert layer: {v:.3f} of {ctx['dims']['experts_held'][1]} "
          f"({d['experts_hit']} hits over {d['token_steps']} token steps x {w['expert_layers']} expert layers; "
          f"{d['pairs_held'] / max(1, d['token_steps'] * w['expert_layers']):.2f} pairs a step and layer)", flush=True)
    return v
