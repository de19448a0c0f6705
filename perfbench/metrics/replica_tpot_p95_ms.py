"""replica_tpot_p95_ms: stats()['latency']['tpot'] (rlt_serve_tpot_seconds), window difference of the per-bucket counts, 95th percentile linear inside its bucket; the mean printed, and the client's tpot_p95_ms of the same run beside it."""


def read(ctx):
    from pb import waits

    t = waits.tail(ctx, "tpot")
    if t is None:
        return None
    client = ctx["e2e"].get("tpot_p95_ms")
    print(f"time per output token at the replica: p95 {t['p_ms']:.3f} ms, mean {t['mean_ms']:.3f} ms over {t['n']} "
          f"requests that ended in the window; the client's p95 of this run: {client}", flush=True)
    return t["p_ms"]
