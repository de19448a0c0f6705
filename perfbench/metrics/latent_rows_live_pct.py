"""latent_rows_live_pct: stats()['attn'], window difference: of the latent layers' cache rows allocated to the slots, summed over the window's decode token steps and latent layers, the share that held a position of a live request. The XLA read multiplies every allocated row; this is the share of that read which is work."""


def read(ctx):
    p = ctx["program"]
    a0, a1 = (p.get("stats0") or {}).get("attn"), (p.get("stats1") or {}).get("attn")
    if not a0 or not a1:
        return None
    allocated = a1["rows_allocated"] - a0["rows_allocated"]
    if allocated <= 0:
        return None
    live = a1["rows_live"] - a0["rows_live"]
    print(f"latent rows: {live} live of {allocated} allocated (and read) over the window's decode token steps and "
          f"latent layers", flush=True)
    return 100.0 * live / allocated
