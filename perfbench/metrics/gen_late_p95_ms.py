"""gen_late_p95_ms: How late the generator ran: the moment submit was called minus the moment the request was due, 95th percentile over the window's requests."""


def read(ctx):
    from pb import stats

    late = [r["submit_s"] - r["due_s"] for r in ctx["program"]["records"] if r["counted"]]
    return 1000.0 * stats.percentile(late, 95) if late else None
