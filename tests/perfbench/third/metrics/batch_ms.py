"""batch_ms: host time of one scored batch, window seconds over batches."""


def read(ctx):
    p = ctx["program"]
    return 1000.0 * p["window_s"] / p["batches"] if p["batches"] else None
