"""client_rpc_ms: Benchmark span around ServeClient.submit (route plan, journal record and the submit RPC), median over the window's requests."""


def read(ctx):
    from pb import stats

    rpc = [r["rpc_s"] for r in ctx["program"]["records"] if r["counted"]]
    return 1000.0 * stats.percentile(rpc, 50) if rpc else None
