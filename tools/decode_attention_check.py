"""On-chip decode-attention check: does the kernel compile at a family's
rows, is it the XLA read's result, and what does a block size cost?

For each shape (the chat cell's Llama rows, GPT-2 medium's) and each
occupancy (``cell``: a quarter of the slots live at chat lengths; ``idle``:
none, which is the call's own cost; ``half``; ``full``: every slot at the
last row, where kernel and XLA read move the same bytes) runs
``ops.decode_attention`` over every layer of a stacked bf16 cache of rows at
each candidate block and ``models/gpt.py:_attend_layer_cache``'s XLA rows
read on the same inputs, and records: compiled or refused with Mosaic's
message, the max abs error against the XLA read over the live slots, and
the time of one layer's call. In-process on the real chip; fails off-chip
(interpret mode proves nothing about Mosaic, and a CPU time is no device
number). Prints one JSON line per row and writes them all to ``--out``.
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
}


def occupancy(name: str, B: int, S: int, rng):
    """(pos, live) of one token step."""
    import numpy as np

    live = np.zeros((B,), bool)
    pos = rng.integers(0, S, size=(B,))
    if name == "cell":
        live[rng.permutation(B)[: B // 4]] = True
        prompt = np.clip(np.exp(rng.normal(np.log(192), 0.6, size=(B,))), 32, S // 2)
        pos = np.minimum(prompt + rng.integers(0, 96, size=(B,)), S - 1).astype(np.int64)
    elif name == "half":
        live[rng.permutation(B)[: B // 2]] = True
    elif name == "full":
        live[:] = True
        pos[:] = S - 1
    return pos.astype(np.int32), live


def main() -> int:
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    p = argparse.ArgumentParser()
    p.add_argument("--blocks", default="128,256,512,1024")
    p.add_argument("--occupancies", default="cell,idle,half,full")
    p.add_argument("--calls", type=int, default=20)
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

    rows = []
    for shape_name, (L, B, S, H, Hkv, hd) in SHAPES.items():
        ks = jax.random.split(jax.random.PRNGKey(L * S + hd), 3)
        q = jax.random.normal(ks[0], (B, H, hd), jnp.bfloat16)
        kc = jax.random.normal(ks[1], (L, B, S, Hkv * hd), jnp.bfloat16)
        vc = jax.random.normal(ks[2], (L, B, S, Hkv * hd), jnp.bfloat16)

        @jax.jit
        def xla(q, kc, vc, pos, live):
            return jnp.stack([
                _attend_layer_cache(xla_cfg, q[:, None], kc, vc, li, pos[:, None])[:, 0]
                for li in range(L)
            ])

        for occ in args.occupancies.split(","):
            pos_np, live_np = occupancy(occ, B, S, np.random.default_rng(len(occ)))
            pos, live = jnp.asarray(pos_np), jnp.asarray(live_np)
            want, t_xla = timed(xla, q, kc, vc, pos, live)
            base = {
                "shape": shape_name, "occupancy": occ, "live_slots": int(live_np.sum()),
                "live_rows": int((pos_np[live_np] + 1).sum()), "device": dev.device_kind,
            }
            rows.append(dict(base, form="xla", us_per_layer=t_xla / L * 1e6))
            print(json.dumps(rows[-1]), flush=True)
            for block in (int(b) for b in args.blocks.split(",")):
                if S % block:
                    continue
                row = dict(base, form="kernel", block=block)

                @jax.jit
                def kern(q, kc, vc, pos, live, block=block):
                    return jnp.stack([
                        decode_attention(q, kc, vc, li, pos, live, block=block) for li in range(L)
                    ])

                try:
                    got, t = timed(kern, q, kc, vc, pos, live)
                except Exception as exc:  # noqa: BLE001 - the refusal IS the record
                    row["status"] = "refused"
                    row["error"] = f"{type(exc).__name__}: {exc}"[:2000]
                else:
                    row["status"] = "compiled"
                    row["us_per_layer"] = t / L * 1e6
                    if live_np.any():
                        err = jnp.abs(got - want)[:, live_np]
                        row["max_abs_err"] = float(err.max())
                        row["ref_abs_max"] = float(jnp.abs(want[:, live_np]).max())
                    row["finite"] = bool(jnp.isfinite(got).all())
                rows.append(row)
                print(json.dumps(row), flush=True)
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(rows, f, indent=1)
    return 0 if all(r.get("status", "compiled") == "compiled" for r in rows) else 1


if __name__ == "__main__":
    sys.exit(main())
