"""ttft_in_prefill_pct: stats()['spans']['riders_s']['waiting'], window difference: of the request-seconds the requests without a first token spent behind the loop thread's spans, the share behind an admission (serve.sched.admit self time, serve.engine.admit_wait, serve.sched.prefill_chunks); the rest, printed by span, is waiting for the loop to reach the admission."""


def read(ctx):
    from pb import waits

    b = waits.behind(ctx, "waiting")
    if b is None:
        return None
    print(f"first tokens behind admissions: {b['admission_s']:.3f} of {b['all_s']:.3f} request-seconds; the other "
          f"{b['all_s'] - b['admission_s']:.3f} waited for the loop to reach the admission", flush=True)
    return 100.0 * b["admission_s"] / b["all_s"]
