"""replica_ttft_p95_ms: stats()['latency']['ttft'] (rlt_serve_ttft_seconds), window difference of the per-bucket counts, 95th percentile linear inside its bucket; the mean printed, and the client's first-token tail of the same run less this as the front's share."""


def read(ctx):
    from pb import waits

    t = waits.tail(ctx, "ttft")
    if t is None:
        return None
    client = ctx["e2e"].get("ttft_p95_ms")
    front = "" if client is None else f"; the client's p95 of this run {client:.3f} ms, {client - t['p_ms']:+.3f} ms the front's"
    print(f"time to first token at the replica: p95 {t['p_ms']:.3f} ms, mean {t['mean_ms']:.3f} ms over {t['n']} first "
          f"tokens in the window{front}", flush=True)
    return t["p_ms"]
