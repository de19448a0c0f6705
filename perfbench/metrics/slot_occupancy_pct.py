"""slot_occupancy_pct: ServeMetrics: mean share of slots decoding per engine step, over the replica's last 512 steps, read at window end."""


def read(ctx):
    v = ctx["program"]["stats1"].get("occupancy")
    return None if v is None else 100.0 * v
