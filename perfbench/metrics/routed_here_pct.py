"""routed_here_pct: stats()['moe'], window difference: (token, expert) pairs that landed on the experts this replica holds over all pairs its router chose, prefill and decode together. 100 x held / published experts if routing is even; it says that the router ran over all experts and how much expert work this share got."""


def read(ctx):
    from pb import plug

    fam = plug.family_of(ctx["dims"])
    w = fam.moe_window(ctx["program"]) if hasattr(fam, "moe_window") else None
    if w is None:
        return None
    routed = w["decode"]["pairs_routed"] + w["prefill"]["pairs_routed"]
    held = w["decode"]["pairs_held"] + w["prefill"]["pairs_held"]
    if routed <= 0:
        return None
    first, count = ctx["dims"]["experts_held"]
    print(f"pairs on held experts: {held} of {routed} routed in the window (decode {w['decode']['pairs_held']} of "
          f"{w['decode']['pairs_routed']}, prefill {w['prefill']['pairs_held']} of {w['prefill']['pairs_routed']}); "
          f"even routing over {ctx['dims']['experts']} experts would give {100.0 * count / ctx['dims']['experts']:.2f}%", flush=True)
    return 100.0 * held / routed
