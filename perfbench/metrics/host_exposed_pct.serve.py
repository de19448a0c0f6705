"""host_exposed_pct.serve: stats()['spans']['exposed_s'], window difference: seconds in which the loop had work and no device program was in flight, as the engine reckons it, over the window's seconds; the split by open span is printed."""


def read(ctx):
    from pb import spans

    w = spans.window(ctx)
    if w is None:
        return None
    total = sum(w["exposed_s"].values())
    print(f"exposed host time {total:.4f} s of {w['seconds']:.2f} s (loop had work for {w['work_s']:.2f} s): "
          f"{spans.split(w['exposed_s'])}", flush=True)
    return 100.0 * total / w["seconds"]
