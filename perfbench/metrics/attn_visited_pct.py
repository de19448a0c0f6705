"""attn_visited_pct: stats()['attn'], window difference: cache rows the decode attention read over the rows allocated to the slots, summed over the window's decode token steps and attention layers. What the load does to the decode kernel's work: 100 on a read of all rows, the live share (rounded up to whole blocks) on the kernel that visits live rows only."""


def read(ctx):
    p = ctx["program"]
    a0, a1 = (p.get("stats0") or {}).get("attn"), (p.get("stats1") or {}).get("attn")
    if not a0 or not a1:
        return None
    allocated = a1["rows_allocated"] - a0["rows_allocated"]
    if allocated <= 0:
        return None
    visited, live = a1["rows_visited"] - a0["rows_visited"], a1["rows_live"] - a0["rows_live"]
    print(f"decode attention: {visited} rows visited and {live} live of {allocated} allocated over the window's "
          f"token steps and layers ({100.0 * live / allocated:.3f}% live)", flush=True)
    return 100.0 * visited / allocated
