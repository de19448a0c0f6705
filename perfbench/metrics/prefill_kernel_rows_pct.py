"""prefill_kernel_rows_pct: stats()['attn'], window difference: of the row·layers of attention the window's admissions prefilled (a prompt's bucket, over the attention layers of mixed layer kinds), the share the forward flash kernel read (ops/flash_attention.py, chosen by models/mixed.py:prefill_kernel): the full and latent kinds without a sink logit, on a TPU, from the crossing's rows up. It says how often the mechanism engages; the print carries the share of the padded causal squares' score tiles a read visited (the kernel skips query blocks past the prompt's end). A program without the counters read no row through the kernel: 0."""


def read(ctx):
    p = ctx["program"]
    a0, a1 = (p.get("stats0") or {}).get("attn"), (p.get("stats1") or {}).get("attn")
    if not a0 or not a1:
        return None
    rows, kernel, tiles, visited = (
        a1.get(key, 0) - a0.get(key, 0) for key in ("prefill_rows", "prefill_rows_kernel", "prefill_tiles", "prefill_tiles_visited"))
    if rows <= 0:
        return 0.0
    print(f"prefill attention: the flash kernel read {kernel} of {rows} row-layers the window's admissions prefilled; "
          f"{visited} of {tiles} score tiles of the padded causal squares visited"
          + (f" ({100.0 * visited / tiles:.3f}%)" if tiles > 0 else ""), flush=True)
    return 100.0 * kernel / rows
