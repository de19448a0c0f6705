"""On-chip flash vs reference attention timing at long context.

In-process on the real chip (op-level diagnosis). Measures forward and
forward+backward wall time for the Pallas flash kernel vs the dense
reference at growing S, plus the sliding-window variant. Prints one
JSON line per config.
"""
import argparse
import json
import statistics
import time


def bench_one(fn, args, iters=20, warmup=3):
    import jax

    out = fn(*args)
    jax.block_until_ready(out)
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return statistics.median(times)


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--heads", type=int, default=8)
    p.add_argument("--head-dim", type=int, default=64)
    p.add_argument("--seqs", default="512,1024,2048,4096,8192")
    p.add_argument("--window", type=int, default=1024)
    args = p.parse_args()

    from ray_lightning_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()
    import jax
    import jax.numpy as jnp

    from ray_lightning_tpu.ops import attention_reference, flash_attention

    B, H, hd = args.batch, args.heads, args.head_dim
    for S in (int(s) for s in args.seqs.split(",")):
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(k1, (B, S, H, hd), jnp.bfloat16)
        k = jax.random.normal(k2, (B, S, H, hd), jnp.bfloat16)
        v = jax.random.normal(k3, (B, S, H, hd), jnp.bfloat16)

        def grad_wall(attn):
            f = jax.jit(
                jax.grad(lambda q, k, v: attn(q, k, v).astype(jnp.float32).sum())
            )
            return bench_one(f, (q, k, v))

        row = {"S": S, "B": B, "H": H, "hd": hd}
        flash = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True))
        row["flash_fwd_ms"] = round(1e3 * bench_one(flash, (q, k, v)), 2)
        row["flash_bwd_ms"] = round(
            1e3 * grad_wall(lambda q, k, v: flash_attention(q, k, v, causal=True)),
            2,
        )
        win = jax.jit(
            lambda q, k, v: flash_attention(
                q, k, v, causal=True, window=args.window
            )
        )
        row[f"flash_w{args.window}_fwd_ms"] = round(
            1e3 * bench_one(win, (q, k, v)), 2
        )
        row[f"flash_w{args.window}_bwd_ms"] = round(
            1e3
            * grad_wall(
                lambda q, k, v: flash_attention(
                    q, k, v, causal=True, window=args.window
                )
            ),
            2,
        )
        if S <= 4096:  # dense (S, S) scores get expensive fast
            try:
                ref = jax.jit(
                    lambda q, k, v: attention_reference(q, k, v, causal=True)
                )
                row["ref_fwd_ms"] = round(1e3 * bench_one(ref, (q, k, v)), 2)
                row["ref_bwd_ms"] = round(
                    1e3
                    * grad_wall(
                        lambda q, k, v: attention_reference(q, k, v, causal=True)
                    ),
                    2,
                )
            except Exception as exc:  # noqa: BLE001 - OOM at large S
                row["ref_error"] = f"{type(exc).__name__}"
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
