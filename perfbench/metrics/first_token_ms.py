"""first_token_ms: the ``percentile`` (the entry's parameter) over the requests due in the window of the first token seen by the client minus the time the request was due; a request that saw no token enters at the drain limit."""


def read(ctx):
    from pb import stats

    missing = float(ctx["seconds"]) + float(ctx["mix"].get("drain_s", 30.0))
    first = [(r["recv_s"][0] - r["due_s"]) if r["recv_s"] else missing
             for r in ctx["program"]["records"] if r["counted"]]
    return 1000.0 * stats.percentile(first, float(ctx["params"]["percentile"])) if first else None
