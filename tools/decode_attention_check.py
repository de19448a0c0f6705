"""On-chip decode-attention check: does the kernel compile at a family's
rows, is it the XLA read's result, and what does a block size cost?

For each shape (the chat cell's Llama rows, GPT-2 medium's, the docqa
cell's latents and rotary keys, the full layers' K and V rows of the
mixedlen and shortchat cells' mixed configurations) and each occupancy
(``cell``: a quarter of the slots live at the cell's lengths; ``idle``:
none, which is the call's own cost; ``half``; ``full``: every slot at the
last row, where kernel and XLA read move the same bytes — the latent XLA
read moves the latents twice) runs ``ops.decode_attention`` over every
layer of a stacked bf16 cache at each candidate block and the XLA read on
the same inputs (``models/gpt.py:_attend_layer_cache``,
``models/mixed.py:_attend_latent_cache`` and ``_attend_cache``), and
records: compiled or refused with Mosaic's message, the max abs error
against the XLA read over the live slots, and the time of one layer's call.
In-process on the real chip; fails off-chip (interpret mode proves nothing
about Mosaic, and a CPU time is no device number). Prints one JSON line per
row and writes them all to ``--out``.
"""
import argparse
import json
import os
import sys
import time

SHAPES = {
    # name: layers, slots, rows, query heads, KV heads, head width
    "chat_8x64x2048_32q8kvx128": (8, 64, 2048, 32, 8, 128),
    "gpt2_medium_24x16x1024_16x64": (24, 16, 1024, 16, 16, 64),
    # a latent layer's: layers, slots, rows, query heads, latent width, rotary width
    "docqa_16x64x6656_32q_512+64": (16, 64, 6656, 32, 512, 64),
    # a mixed configuration's full kind: layers, slots, rows, query heads, KV heads, q·k head width, v head width
    "mixedlen_2x64x5120_64q4kv_192+128": (2, 64, 5120, 64, 4, 192, 128),
    "shortchat_1x128x2048_32q2kvx128": (1, 128, 2048, 32, 2, 128, 128),
}
#: the ``cell`` occupancy's lengths by shape: the prompts' lognormal median and sigma, their least and
#: largest (None: half the cache) and how far into its answer a request may be; chat lengths by default
CHAT_LENGTHS = (192, 0.6, 32, None, 96)
CELL_LENGTHS = {
    "docqa_16x64x6656_32q_512+64": (2048, 0.7, 512, 6144, 256),
    "mixedlen_2x64x5120_64q4kv_192+128": (1024, 0.9, 128, 4096, 384),
    "shortchat_1x128x2048_32q2kvx128": (192, 0.9, 32, 1024, 512),
}


def occupancy(name: str, B: int, S: int, rng, lengths=CHAT_LENGTHS):
    """(pos, live) of one token step."""
    import numpy as np

    median, sigma, low, high, answer = lengths
    live = np.zeros((B,), bool)
    pos = rng.integers(0, S, size=(B,))
    if name == "cell":
        live[rng.permutation(B)[: B // 4]] = True
        prompt = np.clip(np.exp(rng.normal(np.log(median), sigma, size=(B,))), low, high or S // 2)
        pos = np.minimum(prompt + rng.integers(0, answer, size=(B,)), S - 1).astype(np.int64)
    elif name == "half":
        live[rng.permutation(B)[: B // 2]] = True
    elif name == "full":
        live[:] = True
        pos[:] = S - 1
    return pos.astype(np.int32), live


def main() -> int:
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    p = argparse.ArgumentParser()
    p.add_argument("--shapes", default=",".join(SHAPES))
    p.add_argument("--blocks", default="128,256,512,1024")
    p.add_argument("--occupancies", default="cell,idle,half,full")
    p.add_argument("--calls", type=int, default=20)
    p.add_argument("--layers", type=int, default=0, help="time this many of a shape's layers, evenly spaced (0: all; "
                   "a program holds one Mosaic compile a layer)")
    p.add_argument(
        "--out", default=os.path.join(here, "chiprun_out", "decode_attention_check.json")
    )
    args = p.parse_args()

    sys.path.insert(0, here)  # run as `python tools/decode_attention_check.py`
    from ray_lightning_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"decode_attention_check: needs a TPU, found {dev.platform}", file=sys.stderr)
        return 2
    from ray_lightning_tpu.models.gpt import GPTConfig, _attend_layer_cache
    from ray_lightning_tpu.ops.decode_attention import decode_attention

    xla_cfg = GPTConfig(attn_impl="reference")  # the selection's way to the XLA read

    def timed(fn, *a):
        out = jax.block_until_ready(fn(*a))
        jax.block_until_ready(fn(*a))
        t = time.perf_counter()
        for _ in range(args.calls):
            last = fn(*a)
        jax.block_until_ready(last)
        return out, (time.perf_counter() - t) / args.calls

    def filled(shape, seed):
        """A bf16 normal array filled a layer at a time where it lies (the
        docqa shape's latents are 7 GB: no second copy fits beside them)."""
        put = jax.jit(
            lambda buf, li: buf.at[li].set(
                jax.random.normal(jax.random.fold_in(jax.random.PRNGKey(seed), li), shape[1:], jnp.bfloat16)),
            donate_argnums=0,
        )
        buf = jnp.zeros(shape, jnp.bfloat16)
        for li in range(shape[0]):
            buf = put(buf, li)
        return buf

    def rows_kernel(q, kc, vc, li, pos, live, block):
        return decode_attention(q, kc, vc, li, pos, live, block=block)

    def dense(L, B, S, H, Hkv, hd):
        """(queries, caches, the XLA read a layer, the kernel a layer by block)"""
        q = jax.random.normal(jax.random.PRNGKey(hd), (B, H, hd), jnp.bfloat16)
        caches = (filled((L, B, S, Hkv * hd), 1), filled((L, B, S, Hkv * hd), 2))
        xla = lambda q, kc, vc, li, pos, live: _attend_layer_cache(xla_cfg, q[:, None], kc, vc, li, pos[:, None])[:, 0]
        return (q,), caches, xla, rows_kernel

    def latent(L, B, S, H, rank, rope):
        from ray_lightning_tpu.models.mixed import _attend_latent_cache
        from ray_lightning_tpu.ops.decode_attention import latent_decode_attention

        cfg = GPTConfig(attn_impl="reference", qk_head_dim=rank // 4 + rope)  # 192: the scale is all it gives
        qs = (
            jax.random.normal(jax.random.PRNGKey(3), (B, H, rank), jnp.bfloat16),
            jax.random.normal(jax.random.PRNGKey(4), (B, H, rope), jnp.bfloat16),
        )
        # the latents are RMS-normed rows (norm sqrt(rank)); the rotary keys as the cache keeps them, handed to
        # the kernel with the positions minor as models/mixed.py hands them: no copy where the chip keeps them so
        caches = (filled((L, B, S, rank), 1), filled((L, B, S, rope), 2))
        xla = lambda ql, qr, cc, rc, li, pos, live: _attend_latent_cache(
            cfg, ql, qr, {"latent": cc}, {"latent": rc}, li, pos).astype(jnp.float32)
        kern = lambda ql, qr, cc, rc, li, pos, live, block: latent_decode_attention(
            ql, qr, cc, jnp.swapaxes(rc, 2, 3), li, pos, live, scale=(rank // 4 + rope) ** -0.5, block=block)
        return qs, caches, xla, kern

    def mixed(L, B, S, H, G, dk, dv):
        """A full kind's K and V rows, of their own widths, against the read
        ``models/mixed.py:_attention_part`` took before the kernel."""
        from ray_lightning_tpu.models.mixed import _attend_cache

        q = jax.random.normal(jax.random.PRNGKey(dk), (B, H, dk), jnp.bfloat16)
        caches = (filled((L, B, S, G * dk), 1), filled((L, B, S, G * dv), 2))
        xla = lambda q, kc, vc, li, pos, live: _attend_cache(
            q.reshape(B, 1, G, H // G, dk), kc[li], vc[li], pos, None, 0, False)[:, 0].astype(jnp.float32)
        return (q,), caches, xla, rows_kernel

    rows = []
    for shape_name in args.shapes.split(","):
        L, B, S, H = SHAPES[shape_name][:4]
        build = mixed if len(SHAPES[shape_name]) == 7 else latent if "+" in shape_name else dense
        qs, caches, xla_layer, kern_layer = build(*SHAPES[shape_name])
        layers = sorted({round(i * (L - 1) / max(1, args.layers - 1)) for i in range(args.layers)}) or list(range(L))
        # a program of one or two short calls is timed by the host's dispatch (about 0.23 ms a program: the mixedlen
        # shape's two idle calls read 120 us each): at least eight calls a program, pass after pass over the layers
        passes = -(-8 // len(layers))

        def program(layer, qs, caches, pos, live, *more):
            """``passes`` passes over the layers; a pass's queries wait for every call of the pass before (their
            results times zero), so that the compiler neither merges equal calls nor drops one as unread."""
            for _ in range(passes):
                out = jnp.stack([layer(*qs, *caches, li, pos, live, *more) for li in layers])
                qs = tuple(q + (out[..., :1].sum(0) * 0).astype(q.dtype) for q in qs)
            return out

        @jax.jit
        def xla(qs, caches, pos, live):
            return program(xla_layer, qs, caches, pos, live)

        for occ in args.occupancies.split(","):
            pos_np, live_np = occupancy(
                occ, B, S, np.random.default_rng(len(occ)), CELL_LENGTHS.get(shape_name, CHAT_LENGTHS))
            pos, live = jnp.asarray(pos_np), jnp.asarray(live_np)
            want, t_xla = timed(xla, qs, caches, pos, live)
            base = {
                "shape": shape_name, "occupancy": occ, "live_slots": int(live_np.sum()),
                "live_rows": int((pos_np[live_np] + 1).sum()), "device": dev.device_kind,
            }
            rows.append(dict(base, form="xla", us_per_layer=t_xla / (passes * len(layers)) * 1e6))
            print(json.dumps(rows[-1]), flush=True)
            for block in (int(b) for b in args.blocks.split(",")):
                if S % block:
                    continue
                row = dict(base, form="kernel", block=block)

                @jax.jit
                def kern(qs, caches, pos, live, block=block):
                    return program(kern_layer, qs, caches, pos, live, block)

                try:
                    got, t = timed(kern, qs, caches, pos, live)
                except Exception as exc:  # noqa: BLE001 - the refusal IS the record
                    row["status"] = "refused"
                    row["error"] = f"{type(exc).__name__}: {exc}"[:2000]
                else:
                    row["status"] = "compiled"
                    row["us_per_layer"] = t / (passes * len(layers)) * 1e6
                    if live_np.any():
                        err = jnp.abs(got - want)[:, live_np]
                        row["max_abs_err"] = float(err.max())
                        row["ref_abs_max"] = float(jnp.abs(want[:, live_np]).max())
                    row["finite"] = bool(jnp.isfinite(got).all())
                rows.append(row)
                print(json.dumps(row), flush=True)
        del qs, caches
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(rows, f, indent=1)
    return 0 if all(r.get("status", "compiled") == "compiled" for r in rows) else 1


if __name__ == "__main__":
    sys.exit(main())
