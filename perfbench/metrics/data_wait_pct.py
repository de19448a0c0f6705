"""data_wait_pct: TrainTelemetry.data_wait_s over the window, as a share of the loop's three host segments. With asynchronous dispatch this is also where device compute surfaces."""


def read(ctx):
    w = ctx["program"].get("window")
    if not w:
        return None
    total = w["data_wait_s"] + w["dispatch_s"] + w["drain_s"]
    return 100.0 * w["data_wait_s"] / total if total > 0 else None
