"""state_live_pct: stats()['ssm'], window difference: of the slot-steps by which the decode folds advanced the state layers' state (every slot in every iteration: the state of all slots is read and written), the share that belonged to a live request. It says how much of a decode step's state traffic is work."""


def read(ctx):
    from pb import plug

    fam = plug.family_of(ctx["dims"])
    w = fam.ssm_window(ctx["program"]) if hasattr(fam, "ssm_window") else None
    if w is None or w["decode"]["slot_steps"] <= 0:
        return None
    d, p = w["decode"], w["prefill"]
    print(f"state layers: {d['slot_steps_live']} live of {d['slot_steps']} slot-steps advanced in the window's decode "
          f"folds; admissions scanned {p['rows_scanned']} rows, {p['rows_real']} of them prompt", flush=True)
    return 100.0 * d["slot_steps_live"] / d["slot_steps"]
