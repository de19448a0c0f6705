"""decode_behind_admit_pct: stats()['spans']['riders_s']['decoding'], window difference: of the request-seconds the decoding slots spent behind the loop thread's spans, the share behind an admission (serve.sched.admit self time, serve.engine.admit_wait, serve.sched.prefill_chunks); the whole split by span printed, serve.engine.key_wait (the fold in flight: the decoders' own work) by name."""


def read(ctx):
    from pb import waits

    b = waits.behind(ctx, "decoding")
    if b is None:
        return None
    own = b["by_span"].get("serve.engine.key_wait", 0.0)
    print(f"decoders behind admissions: {b['admission_s']:.3f} of {b['all_s']:.3f} request-seconds; behind "
          f"serve.engine.key_wait, the fold in flight that an admission waits out (their own work): {own:.3f}", flush=True)
    return 100.0 * b["admission_s"] / b["all_s"]
