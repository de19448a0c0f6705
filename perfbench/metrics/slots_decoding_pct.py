"""slots_decoding_pct: stats()['spans']['riders_s']['decoding'], window difference: the decoding slots' request-seconds over num_slots x the window's seconds: the mean share of the slots that held a request between its first token and its end, exactly the window's."""


def read(ctx):
    from pb import waits

    r = waits.riders(ctx)
    if r is None:
        return None
    rode = sum(r["decoding"].values())
    print(f"slots decoding: {rode:.3f} request-seconds over {r['num_slots']} slots x {r['seconds']:.3f} s = "
          f"{rode / r['seconds']:.2f} slots in the mean; waiting for a first token: "
          f"{sum(r['waiting'].values()) / r['seconds']:.2f} requests in the mean", flush=True)
    return 100.0 * rode / (r["num_slots"] * r["seconds"])
