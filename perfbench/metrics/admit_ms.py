"""admit_ms: stats()['spans'], window difference: seconds of serve.sched.admit (release, admit_many with its prefill dispatch, per-admission bookkeeping) plus the two waits on the device inside it (serve.engine.key_wait, the PRNG-key fetch, and serve.engine.admit_wait, the first-token sync), over the count of serve.sched.admit."""


def read(ctx):
    from pb import spans

    w = spans.window(ctx)
    row = None if w is None else w["segments"].get("serve.sched.admit")
    if not row or row["n"] <= 0:
        return None
    waits = {k: w["segments"][k]["s"] for k in spans.BLOCKED_IN_ADMIT if k in w["segments"]}
    whole = row["s"] + sum(waits.values())
    print(f"admissions: {row['n']} bursts in the window, {whole:.4f} s in serve.sched.admit, of it blocked on the "
          f"device: {spans.split(waits) or 'nothing named'}", flush=True)
    return 1000.0 * whole / row["n"]
