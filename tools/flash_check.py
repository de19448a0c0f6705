"""On-chip flash-attention check: does each shape compile, and is it right?

For each (S, D) — and one windowed+sinks shape — runs the Pallas kernels
(``ops.flash_attention``) forward and backward in bf16 against
``attention_reference`` on the same inputs and records, per shape:
compiled, or refused with Mosaic's message, and the max abs error of the
output and of dq/dk/dv. In-process on the real chip; fails off-chip (the
kernels would run in interpret mode and prove nothing about Mosaic).
Prints one JSON line per shape and writes them all to ``--out``.
"""
import argparse
import json
import os
import sys


def check_shape(S: int, D: int, window: int, sinks: int) -> dict:
    import jax
    import jax.numpy as jnp

    from ray_lightning_tpu.ops import attention_reference, flash_attention

    B, H = 1, 2  # the reference holds (B, H, S, S) fp32 scores
    row = {"B": B, "S": S, "H": H, "D": D, "window": window, "sinks": sinks}
    kq, kk, kv, kd = jax.random.split(jax.random.PRNGKey(S + D), 4)
    q, k, v, do = (
        jax.random.normal(key, (B, S, H, D), jnp.bfloat16)
        for key in (kq, kk, kv, kd)
    )
    kw = dict(causal=True, window=window, sinks=sinks)

    def fwd_bwd(attn):
        def f(q, k, v):
            out, vjp = jax.vjp(lambda q, k, v: attn(q, k, v, **kw), q, k, v)
            return (out,) + vjp(do)

        return jax.jit(f)

    try:
        got = jax.block_until_ready(fwd_bwd(flash_attention)(q, k, v))
    except Exception as exc:  # noqa: BLE001 - the refusal IS the record
        row["status"] = "refused"
        row["error"] = f"{type(exc).__name__}: {exc}"[:2000]
        return row
    want = fwd_bwd(attention_reference)(q, k, v)
    row["status"] = "compiled"
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        err = jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))
        row[f"max_abs_err_{name}"] = float(err.max())
        row[f"ref_abs_max_{name}"] = float(jnp.abs(b.astype(jnp.float32)).max())
    return row


def main() -> int:
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    p = argparse.ArgumentParser()
    p.add_argument("--seqs", default="1024,4096,8192")
    p.add_argument("--head-dims", default="64,128")
    p.add_argument("--window", type=int, default=1024)
    p.add_argument("--sinks", type=int, default=4)
    p.add_argument(
        "--out", default=os.path.join(here, "chiprun_out", "flash_check.json")
    )
    args = p.parse_args()

    sys.path.insert(0, here)  # run as `python tools/flash_check.py`
    from ray_lightning_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"flash_check: needs a TPU, found {dev.platform}", file=sys.stderr)
        return 2
    seqs = [int(s) for s in args.seqs.split(",")]
    shapes = [(S, int(D), 0, 0) for D in args.head_dims.split(",") for S in seqs]
    shapes.append((seqs[len(seqs) // 2], 64, args.window, args.sinks))
    rows = []
    for shape in shapes:
        rows.append(check_shape(*shape))
        print(json.dumps(rows[-1]), flush=True)
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"device_kind": dev.device_kind, "rows": rows}, f, indent=1)
    return 0 if all(r["status"] == "compiled" for r in rows) else 1


if __name__ == "__main__":
    sys.exit(main())
