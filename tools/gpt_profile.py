"""On-chip GPT step profile: trace a few steps, print top device ops.

Runs the flagship config in-process on the real chip (no actor fabric —
this is an op-level diagnosis, not a throughput measurement), captures a
jax.profiler trace, then aggregates device-track event durations from
the perfetto JSON so the hot ops are visible without TensorBoard.
"""
import argparse
import glob
import gzip
import json
import os
from collections import defaultdict


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--seq", type=int, default=512)
    p.add_argument("--batch", type=int, default=16)
    p.add_argument("--chunk", type=int, default=0)
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--outdir", default="/tmp/gpt_trace")
    args = p.parse_args()

    from ray_lightning_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from ray_lightning_tpu.models import GPTConfig
    from ray_lightning_tpu.models.gpt import (
        chunked_lm_loss,
        gpt_forward,
        init_gpt_params,
        lm_loss,
    )

    cfg = GPTConfig.gpt2_small(
        max_seq=args.seq, remat=False, loss_chunk=args.chunk
    )
    params = init_gpt_params(jax.random.PRNGKey(0), cfg)
    opt = optax.adamw(1e-4)
    opt_state = opt.init(params)
    toks = jnp.asarray(
        np.random.default_rng(0).integers(
            0, cfg.vocab_size, (args.batch, args.seq + 1)
        ),
        jnp.int32,
    )

    def loss_fn(p, t):
        if args.chunk:
            hidden = gpt_forward(p, t[:, :-1], cfg, return_hidden=True)
            return chunked_lm_loss(hidden, p["wte"], t[:, 1:], args.chunk)[0]
        return lm_loss(gpt_forward(p, t[:, :-1], cfg), t[:, 1:])[0]

    @jax.jit
    def step(p, s, t):
        loss, grads = jax.value_and_grad(loss_fn)(p, t)
        updates, s = opt.update(grads, s, p)
        return optax.apply_updates(p, updates), s, loss

    # Warmup/compile outside the trace.
    params, opt_state, loss = step(params, opt_state, toks)
    jax.block_until_ready(loss)

    import shutil
    import time

    from ray_lightning_tpu.obs import profiling as obs_profiling

    shutil.rmtree(args.outdir, ignore_errors=True)
    t0 = time.time()
    # obs.profiling.trace == jax.profiler.trace + the process-wide
    # one-capture lock shared with the on-demand profile() RPCs.
    with obs_profiling.trace(args.outdir):
        for _ in range(args.steps):
            params, opt_state, loss = step(params, opt_state, toks)
        jax.block_until_ready(loss)
    wall = time.time() - t0
    tok_s = args.steps * args.batch * args.seq / wall
    print(
        json.dumps(
            {
                "batch": args.batch,
                "chunk": args.chunk,
                "steps": args.steps,
                "wall_s": round(wall, 2),
                "tokens_per_sec": round(tok_s, 1),
            }
        )
    )

    traces = glob.glob(
        os.path.join(args.outdir, "**", "*.trace.json.gz"), recursive=True
    )
    if not traces:
        print("no trace file found under", args.outdir)
        return
    with gzip.open(sorted(traces)[-1], "rt") as f:
        data = json.load(f)
    events = data.get("traceEvents", [])
    # Device-track complete events: aggregate wall duration by op name.
    pid_names = {
        e["pid"]: e["args"].get("name", "")
        for e in events
        if e.get("ph") == "M" and e.get("name") == "process_name"
        and isinstance(e.get("args"), dict)
    }
    device_pids = {
        pid for pid, name in pid_names.items()
        if "TPU" in name or "/device:" in name
    }
    totals: dict = defaultdict(float)
    for e in events:
        if e.get("ph") == "X" and e.get("pid") in device_pids:
            totals[e.get("name", "?")] += e.get("dur", 0.0)
    top = sorted(totals.items(), key=lambda kv: -kv[1])[:25]
    grand = sum(totals.values()) or 1.0
    print(f"device tracks: {[pid_names[p] for p in device_pids]}")
    for name, dur in top:
        print(f"{dur / 1e3:9.2f} ms  {100 * dur / grand:5.1f}%  {name[:90]}")


if __name__ == "__main__":
    main()
