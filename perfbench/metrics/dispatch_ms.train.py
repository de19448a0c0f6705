"""dispatch_ms.train: TrainTelemetry.step_s per folded dispatch: host enqueue time of the compiled step, not device time."""


def read(ctx):
    w = ctx["program"].get("window")
    if not w or not w["chunks"]:
        return None
    return 1000.0 * w["dispatch_s"] / w["chunks"]
