"""queue_wait_exact_p95_ms: stats()['latency']['queue'] (rlt_serve_phase_seconds{phase="queue"}), window difference of the per-bucket counts, 95th percentile linear inside its bucket; the mean printed."""


def read(ctx):
    from pb import waits

    t = waits.tail(ctx, "queue")
    if t is None:
        return None
    print(f"queue wait at the replica: p95 {t['p_ms']:.3f} ms, mean {t['mean_ms']:.3f} ms over {t['n']} requests that "
          f"ended in the window", flush=True)
    return t["p_ms"]
