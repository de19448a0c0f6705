"""state_slots_visited_pct: stats()['ssm'], window difference: of the decode folds' slot-steps (every slot in every iteration), the share whose running state the state layers' update read and wrote: the live ones where the update walks the live slots (ops/ssm_step.py), all of them where one XLA pass moves every slot's state (so too a program that counts slot-steps but not yet the visited ones: it has no other update). It says how often the kernel engages; beside state_live_pct, whether a slot that is visited is a slot that is live."""


def read(ctx):
    from pb import plug

    fam = plug.family_of(ctx["dims"])
    w = fam.ssm_window(ctx["program"]) if hasattr(fam, "ssm_window") else None
    if w is None or w["decode"]["slot_steps"] <= 0:
        return None
    d = w["decode"]
    # a program from before the counter (PR 48) has one update, the XLA pass over every slot's state
    visited = d.get("slot_steps_visited", d["slot_steps"])
    print(f"state layers: the update visited {visited} of {d['slot_steps']} slot-steps in the window's decode folds, "
          f"{d['slot_steps_live']} of them live", flush=True)
    return 100.0 * visited / d["slot_steps"]
