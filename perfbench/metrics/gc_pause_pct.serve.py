"""gc_pause_pct.serve: stats()['spans']['gc'], window difference: summed pause seconds of the cyclic collector over all generations, over the window's seconds; the longest pause since the replica was built is printed."""


def read(ctx):
    from pb import spans

    w = spans.window(ctx)
    if w is None:
        return None
    total = sum(row["s"] for row in w["gc"].values())
    counts = ", ".join(f"gen {g}: {row['n']} in {1000 * row['s']:.2f} ms" for g, row in sorted(w["gc"].items()))
    print(f"collector pauses in the window: {counts}; longest since the replica was built {1000 * w['gc_max_s']:.2f} ms", flush=True)
    return 100.0 * total / w["seconds"]
