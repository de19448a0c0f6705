"""On-chip flash-attention check: does each shape compile, and is it right?

For each (S, D) — and one windowed+sinks shape — runs the Pallas kernels
(``ops.flash_attention``) forward and backward in bf16 against
``attention_reference`` on the same inputs and records, per shape:
compiled, or refused with Mosaic's message, and the max abs error of the
output and of dq/dk/dv. Then the forward-only shapes of a mixed layer's
prefill (``models/mixed.py:prefill_kernel``): q·k 192 / v 128 with a KV
head a query head and with 64 heads on 4, 128 / 128 at 20 on 4, each with
every row real and with ``true_len`` inside the bucket, against
``attention_reference`` on the real rows. ``--time`` adds to those rows the
kernel's time beside the blocked XLA read's (``_attend_rows_full``) — where
the crossing ``models/mixed.py:_KERNEL_ROWS`` is read — and ``--rows-of
<configuration .json>`` times the configuration's layers over a prompt
(``mixed_rows``: an admission but for its cache write and sample) under
both reads, bucket by bucket. In-process on the real chip; fails off-chip
(the kernels would run in interpret mode and prove nothing about Mosaic).
Prints one JSON line per shape and writes them all to ``--out``.
"""
import argparse
import json
import os
import statistics
import sys
import time

#: (query heads, KV heads, q·k width, v width) of the forward-only shapes:
#: the docqa cell's latent layers, the mixedlen cell's full layers, the
#: burstchat cell's.
FORWARD_HEADS = ((32, 32, 192, 128), (64, 4, 192, 128), (20, 4, 128, 128))


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


def _median_us(fn, *args, runs: int = 10) -> float:
    import jax

    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(runs):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    return 1e6 * statistics.median(times)


def check_forward(S: int, H: int, Hkv: int, dqk: int, dv: int, true_len: int, timed: bool) -> dict:
    """The forward-only kernel at one shape: q (1, S, H, dqk) on Hkv KV
    heads, v of width dv, the first ``true_len`` rows real (0: all)."""
    import jax
    import jax.numpy as jnp

    from ray_lightning_tpu.models.mixed import _attend_rows_full
    from ray_lightning_tpu.ops import attention_reference, flash_attention
    from ray_lightning_tpu.ops.flash_attention import _default_block

    R = H // Hkv
    row = {"forward_only": True, "S": S, "H": H, "Hkv": Hkv, "qk": dqk, "v": dv, "true_len": true_len}
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(S + H + true_len), 3)
    q = jax.random.normal(kq, (1, S, H, dqk), jnp.bfloat16)
    k = jax.random.normal(kk, (1, S, Hkv, dqk), jnp.bfloat16)
    v = jax.random.normal(kv, (1, S, Hkv, dv), jnp.bfloat16)
    real = jnp.asarray(true_len or S, jnp.int32)
    kernel = jax.jit(lambda q, k, v, n: flash_attention(q, k, v, true_len=n if true_len else None))
    try:
        got = jax.block_until_ready(kernel(q, k, v, real))
    except Exception as exc:  # noqa: BLE001 - the refusal IS the record
        row["status"] = "refused"
        row["error"] = f"{type(exc).__name__}: {exc}"[:2000]
        return row
    row["status"] = "compiled"
    # the reference a KV head at a time: (1, R, S, S) float32 scores are what it holds
    group = jax.jit(lambda q, k, v: attention_reference(
        q, jnp.repeat(k, R, axis=2), jnp.repeat(v, R, axis=2), causal=True))
    n, err, top = int(real), 0.0, 0.0
    for g in range(Hkv):
        want = group(q[:, :, g * R:(g + 1) * R], k[:, :, g:g + 1], v[:, :, g:g + 1]).astype(jnp.float32)[:, :n]
        err = max(err, float(jnp.abs(got[:, :n, g * R:(g + 1) * R].astype(jnp.float32) - want).max()))
        top = max(top, float(jnp.abs(want).max()))
    row["max_abs_err_out"], row["ref_abs_max_out"] = err, top
    tile = min(_default_block(S), S)
    row["rows_zeroed"] = int((jnp.abs(got[:, -(-n // tile) * tile:].astype(jnp.float32)).sum(axis=(0, 2, 3)) == 0).sum())
    if timed:
        xla = jax.jit(lambda q, k, v: _attend_rows_full(q.reshape(1, S, Hkv, R, dqk), k, v, None))
        row["kernel_us"] = _median_us(kernel, q, k, v, real)
        row["xla_us"] = _median_us(xla, q, k, v)
    return row


def time_rows(config_path: str, buckets) -> list:
    """A configuration's layers over a prompt (``mixed_rows``) under both
    reads, a bucket at a time: the blocked XLA read, the kernel with every
    row real, and the kernel with the bucket's shortest prompt."""
    import jax
    import jax.numpy as jnp

    from ray_lightning_tpu.models import mixed
    from ray_lightning_tpu.models.gpt import GPTConfig, init_gpt_params

    with open(config_path) as f:
        spec = json.load(f)
    cfg = GPTConfig(**spec["program_config"])
    dt = jnp.dtype(spec.get("weights_dtype", cfg.compute_dtype))
    params = jax.jit(lambda key: jax.tree_util.tree_map(
        lambda w: w.astype(dt) if w.ndim > 1 else w, init_gpt_params(key, cfg)))(jax.random.PRNGKey(0))
    rows = []
    for pb in buckets:
        tokens = jax.random.randint(jax.random.PRNGKey(pb), (1, pb), 0, cfg.vocab_size, jnp.int32)
        row = {"rows_of": os.path.basename(config_path), "bucket": pb}
        for name, prefill in (("xla", False), ("kernel", True)):
            fn = jax.jit(lambda p, t, n, prefill=prefill: mixed.mixed_rows(p, cfg, t, true_len=n, prefill=prefill)[0])
            t0 = time.perf_counter()
            jax.block_until_ready(fn(params, tokens, jnp.asarray(pb, jnp.int32)))
            row[name + "_first_call_s"] = time.perf_counter() - t0  # the compile, and one run
            row[name + "_us"] = _median_us(fn, params, tokens, jnp.asarray(pb, jnp.int32), runs=5)
            if prefill:
                row["kernel_shortest_prompt_us"] = _median_us(fn, params, tokens, jnp.asarray(pb // 2 + 1, jnp.int32), runs=5)
        row["kernel_reads"] = {kind: mixed.prefill_kernel(cfg, kind, pb) for kind in ("full", "latent") if mixed.count_kind(cfg, kind)}
        rows.append(row)
        print(json.dumps(row), flush=True)
    return rows


def main() -> int:
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    p = argparse.ArgumentParser()
    p.add_argument("--seqs", default="1024,4096,8192")
    p.add_argument("--head-dims", default="64,128")
    p.add_argument("--window", type=int, default=1024)
    p.add_argument("--sinks", type=int, default=4)
    p.add_argument("--forward-seqs", default="256,512,1024,2048,4096,6144")
    p.add_argument("--time", action="store_true", help="time the forward-only kernel beside the blocked XLA read")
    p.add_argument("--rows-of", default=None, help="a configuration's .json: time its layers over a prompt under both reads")
    p.add_argument("--buckets", default="2048,4096,6144")
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
    seqs = [int(s) for s in args.seqs.split(",") if s]
    shapes = [(S, int(D), 0, 0) for D in args.head_dims.split(",") for S in seqs]
    if seqs:
        shapes.append((seqs[len(seqs) // 2], 64, args.window, args.sinks))
    rows = []
    for shape in shapes:
        rows.append(check_shape(*shape))
        print(json.dumps(rows[-1]), flush=True)
    for heads in FORWARD_HEADS:
        for S in (int(s) for s in args.forward_seqs.split(",") if s):
            for true_len in (0, S // 2 + 1):
                rows.append(check_forward(S, *heads, true_len, args.time))
                print(json.dumps(rows[-1]), flush=True)
    ok = all(r["status"] == "compiled" for r in rows)
    if args.rows_of:
        rows += time_rows(args.rows_of, [int(b) for b in args.buckets.split(",")])
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"device_kind": dev.device_kind, "rows": rows}, f, indent=1)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
