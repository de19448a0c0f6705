"""chip_smoke.py — fit -> checkpoint -> serve, once, on the TPU.

The quickest proof that the system still starts on the chip: GPT-2 124M
(12 x 768, 12 heads, vocab 50,257, seq 1024, bf16, Pallas flash attention)
takes eight optimizer steps through ``Trainer.fit`` under
``RayShardedStrategy`` on every chip of the host, writes its state-stream
checkpoint (weights only, 0.5 GB), and — after the fit worker has exited —
one serving replica loads that file and answers seven requests through
``ServeClient``.
Weights are random (seed 0); the corpus is the seeded synthetic one.

This process never initialises a JAX backend: a chip belongs to one
process at a time, and here that is the fit worker, then the replica.

Any failed phase, or no TPU, is a non-zero exit and no result line. On
success the last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.

``--cpu-dry-run`` walks the same control flow at a toy size on the CPU, to
debug the script before spending chip time. It says so in its output,
prints no ``ok`` result, and is the only way this script runs off-chip.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import signal
import sys
import time
from typing import Any, Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))
FOLD = 4  # optimizer steps per dispatch
STEPS = 8  # two dispatches of the folded step
WATCHDOG_S = 1100  # the driver's limit is 1200 s, compilation included


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)
    print(f"  ok: {what}", flush=True)


def cache_entries(path: str) -> int:
    """Files in the compile-cache directory (0 when it does not exist)."""
    try:
        return sum(1 for e in os.scandir(path) if e.is_file())
    except FileNotFoundError:
        return 0


def host_limits() -> str:
    """What this host lets a process write: the checkpoint is the largest
    single file of the run. Printed up front and again on failure."""
    import resource
    import shutil

    soft, _ = resource.getrlimit(resource.RLIMIT_FSIZE)
    fsize = "unlimited" if soft == resource.RLIM_INFINITY else f"{soft} bytes"
    return f"max file size={fsize}, free disk under {HERE}={shutil.disk_usage(HERE).free} bytes"


def make_fit_report():
    """The callback that reports from INSIDE the fit worker. Built lazily:
    importing the trainer imports jax, which a directory holding nothing
    but this script does not need to get as far as."""
    from ray_lightning_tpu.trainer.callbacks import Callback

    class FitReport(Callback):
        def __init__(self) -> None:
            self.report: Dict[str, Any] = {"losses": []}

        def on_fit_start(self, trainer: Any, module: Any) -> None:
            import jax
            import numpy as np

            from ray_lightning_tpu.utils.native import native_available

            devs = jax.devices()
            p = trainer.params
            self.report.update(
                platform=devs[0].platform,
                device_kind=devs[0].device_kind,
                device_count=len(devs),
                native_data_path=bool(native_available()),
                # Small leaves kept whole: the driver compares the weights
                # it recovers against these.
                init_leaves={
                    "lnf_g": np.asarray(jax.device_get(p["lnf_g"])),
                    "ln1_b": np.asarray(jax.device_get(p["blocks"]["ln1_b"])),
                    "wpe": np.asarray(jax.device_get(p["wpe"])),
                },
            )

        def on_train_batch_end(
            self, trainer: Any, module: Any, logs: Dict[str, float], batch_idx: int
        ) -> None:
            self.report["losses"].append(float(logs["loss"]))

        def on_fit_end(self, trainer: Any, module: Any) -> None:
            import re

            import jax
            import numpy as np

            from ray_lightning_tpu.obs.jaxmon import compile_stats

            r = self.report
            # Before the re-compile below adds to it.
            r["compile"] = compile_stats().snapshot()
            r["peak_bytes_in_use"] = [
                int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                for d in jax.local_devices()
            ]
            st = trainer.strategy
            n_dev = len(jax.devices())
            global_batch = module.batch_size * n_dev
            seq = module.config.max_seq
            # How the work is spread: the batch, and the optimizer state.
            batch = st.make_global_batch(
                (np.zeros((global_batch, seq + 1), np.int32),)
            )[0]
            r["batch_shards"] = {
                "n": len(batch.addressable_shards),
                "devices": len({s.device for s in batch.addressable_shards}),
                "shape": list(batch.addressable_shards[0].data.shape),
            }
            leaves = jax.tree_util.tree_leaves(trainer.opt_state)
            big = max(leaves, key=lambda x: x.size)
            r["opt_state"] = {
                "largest_leaf": list(big.shape),
                "largest_leaf_shard": list(big.addressable_shards[0].data.shape),
                "sharded_leaves": sum(
                    1
                    for x in leaves
                    if x.addressable_shards[0].data.size < x.size
                ),
                "leaves": len(leaves),
            }
            # The train step, compiled again from the same inputs (a
            # persistent-cache hit): what the partitioner made of it.
            spec = lambda x: jax.ShapeDtypeStruct(  # noqa: E731
                x.shape, x.dtype, sharding=x.sharding
            )
            step = st.compile_train_step(
                module, trainer._tx, fold_steps=FOLD, fold_stacked=True
            )
            compiled = step.lower(
                jax.tree_util.tree_map(spec, trainer.params),
                jax.tree_util.tree_map(spec, trainer.opt_state),
                (
                    jax.ShapeDtypeStruct(
                        (FOLD, global_batch, seq + 1),
                        np.int32,
                        sharding=st.stacked_batch_sharding(),
                    ),
                ),
                trainer._rng,
                0,
            ).compile()
            calls = [
                ln
                for ln in compiled.as_text().splitlines()
                if 'custom_call_target="tpu_custom_call"' in ln
            ]
            r["mosaic_calls"] = len(calls)
            # Result shapes of the kernels, e.g. bf16[96,1024,64]: the
            # leading dim is (batch rows on this device) x heads.
            r["mosaic_shapes"] = sorted(
                {
                    m
                    for ln in calls
                    for m in re.findall(
                        r"\w+\[[\d,]+\]", ln.split(" custom-call(")[0]
                    )
                }
            )

        def state_dict(self) -> Dict[str, Any]:
            return self.report

        def load_state_dict(self, state: Dict[str, Any]) -> None:
            self.report = dict(state)

    return FitReport()


def run_fit(chips: int, cfg: Any, batch: int, out: str, dry: bool) -> Dict[str, Any]:
    import numpy as np

    from ray_lightning_tpu.models.gpt import GPTLM
    from ray_lightning_tpu.strategies import RayShardedStrategy
    from ray_lightning_tpu.trainer import ModelCheckpoint, Trainer
    from ray_lightning_tpu.utils.flops import PEAK_BF16_FLOPS

    fit_report = make_fit_report()
    # Weights only: what the replica loads. With Adam's moments the file
    # is 1.5 GB at this size, which the check machine refuses to write.
    ckpt_cb = ModelCheckpoint(
        dirpath=os.path.join(out, "checkpoints"), save_weights_only=True
    )
    module = GPTLM(
        config=cfg,
        batch_size=batch,
        n_train=batch * chips * STEPS,  # one epoch == STEPS steps
        warmup_steps=2,
    )
    trainer = Trainer(
        max_epochs=1,
        strategy=RayShardedStrategy(num_workers=chips, use_tpu=not dry),
        steps_per_execution=FOLD,
        log_every_n_steps=FOLD,
        callbacks=[ckpt_cb, fit_report],
        # No eval batches: the val hook still fires at epoch end, which
        # is where ModelCheckpoint writes.
        limit_val_batches=0,
        num_sanity_val_steps=0,
        enable_model_summary=False,
        ship_optimizer_state=False,
        default_root_dir=out,
        seed=0,
    )
    t0 = time.time()
    trainer.fit(module)
    r = fit_report.report
    r["fit_wall_s"] = round(time.time() - t0, 1)
    init_leaves = r.pop("init_leaves")
    compile_s = r["compile"].get("backend_compile", {}).get("total_s", 0.0)
    print(
        f"fit: platform={r['platform']} device_kind={r['device_kind']} "
        f"devices={r['device_count']} steps={trainer.global_step} "
        f"losses(after each dispatch)={r['losses']} "
        f"compile_s(set-up)={compile_s} wall_s={r['fit_wall_s']} "
        f"peak_bytes_in_use={r['peak_bytes_in_use']} "
        f"native_data_path={r['native_data_path']}",
        flush=True,
    )
    print(
        f"fit: mosaic_calls={r['mosaic_calls']} shapes={r['mosaic_shapes']} "
        f"batch_shards={r['batch_shards']} opt_state={r['opt_state']}",
        flush=True,
    )
    check(trainer.global_step == STEPS, f"{STEPS} optimizer steps ran")
    check(
        len(r["losses"]) == STEPS // FOLD and bool(np.all(np.isfinite(r["losses"]))),
        "losses are finite, one drained per folded dispatch",
    )
    check(r["device_count"] == chips, f"the worker saw all {chips} chip(s)")
    p = module.params
    recovered = {"lnf_g": p["lnf_g"], "ln1_b": p["blocks"]["ln1_b"], "wpe": p["wpe"]}
    check(
        all(bool(np.all(np.isfinite(v))) for v in recovered.values())
        and all(not np.array_equal(recovered[k], init_leaves[k]) for k in recovered),
        "recovered weights are finite and differ from init",
    )
    if not dry:
        check(r["platform"] == "tpu", "fit ran on platform tpu")
        check(
            r["device_kind"] in PEAK_BF16_FLOPS,
            f"device_kind {r['device_kind']!r} is in utils/flops.py",
        )
        check(
            r["mosaic_calls"] >= 3,
            "compiled train step holds the Mosaic flash kernels (fwd, dkv, dq)",
        )
        rows = batch * cfg.n_head
        check(
            all(s.startswith(f"bf16[{rows},") for s in r["mosaic_shapes"] if "bf16" in s),
            f"flash kernels carry the per-device batch ({batch} x {cfg.n_head} "
            f"heads = {rows} rows), not the global one",
        )
    if chips > 1:
        check(
            r["batch_shards"]["n"] == chips
            and r["batch_shards"]["devices"] == chips
            and r["batch_shards"]["shape"][0] == batch,
            f"batch has {chips} addressable shards on {chips} devices",
        )
        check(r["opt_state"]["sharded_leaves"] > 0, "optimizer state is sharded")
        peaks = r["peak_bytes_in_use"]
        if not dry:  # the CPU backend reports no memory stats
            check(
                min(peaks) > 0 and max(peaks) <= 1.25 * min(peaks),
                "all devices report comparable peak memory",
            )
    ckpt = ckpt_cb.best_model_path
    check(bool(ckpt) and os.path.isfile(ckpt), f"fit wrote its checkpoint: {ckpt}")
    r["ckpt_path"] = ckpt
    r["ckpt_bytes"] = os.path.getsize(ckpt)
    print(f"checkpoint: {ckpt} ({r['ckpt_bytes']} bytes)", flush=True)
    return r


def run_serve(chips: int, cfg: Any, ckpt: str, dry: bool) -> Dict[str, Any]:
    import numpy as np

    from ray_lightning_tpu import fabric
    from ray_lightning_tpu.serve import start_replicas

    check(
        fabric.available_resources().get("TPU", 0) == fabric.cluster_resources().get("TPU", 0),
        "the fit worker has exited and returned its chips",
    )
    env: Dict[str, str] = {}
    if dry:
        env = {
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": f"--xla_force_host_platform_device_count={chips}",
        }
    t0 = time.time()
    client = start_replicas(
        1,
        num_tpus_per_replica=0 if dry else chips,
        env=env,
        init_timeout=900.0,
        ckpt_path=ckpt,
        model_config=dataclasses.asdict(cfg),
        mesh=f"{chips}x1" if chips > 1 else None,
    )
    try:
        start_s = round(time.time() - t0, 1)
        rng = np.random.default_rng(0)
        seq = cfg.max_seq
        # Mixed prompt lengths: several prefill buckets. Request 1 fills
        # its bucket exactly, so the solo run it is held against below
        # also takes the flash kernel (a 20-token prompt would not tile).
        lengths = [5, 32, seq // 20, seq // 10, seq // 4, seq // 2 + 7, 12]
        n_new = 16
        prompts = [
            [int(t) for t in rng.integers(0, cfg.vocab_size, size=n)] for n in lengths
        ]
        sampled = dict(temperature=0.8, top_k=50, top_p=0.95)
        outs: List[List[int]] = [[] for _ in prompts]
        # 0-2: blocking, one at a time (0, 1 greedy; 2 sampled).
        outs[0] = client.generate(prompts[0], max_new_tokens=n_new)
        outs[1] = client.generate(prompts[1], max_new_tokens=n_new)
        outs[2] = client.generate(prompts[2], max_new_tokens=n_new, seed=2, **sampled)
        # 3-6: submitted together (they share decode folds), then streamed.
        handles = [
            client.submit(prompts[3], max_new_tokens=n_new),
            client.submit(prompts[4], max_new_tokens=n_new, seed=4, **sampled),
            client.submit(prompts[5], max_new_tokens=n_new),
            client.submit(prompts[6], max_new_tokens=n_new, seed=6, **sampled),
        ]
        for i, h in enumerate(handles, start=3):
            outs[i] = list(client.stream_handle(h, timeout_s=300.0))
        stats = client.stats()[0]
        dev = stats["device"]
        print(
            f"serve: replica device={dev} mesh={stats['mesh']} start_s={start_s} "
            f"compiled_count={stats['compiled_count']} "
            f"compiles_at_init={stats['compiles_at_init']} "
            f"compile_s_at_init(set-up)={stats['compile_s_at_init']} "
            f"compiles_since_init={stats['compiles_since_init']} "
            f"prefill_buckets={stats['prefill_buckets']} paged={stats['paged']}",
            flush=True,
        )
        check(
            all(len(o) == n_new for o in outs),
            f"{len(outs)} requests (prompts {lengths}) each returned {n_new} tokens",
        )
        check(
            all(0 <= t < cfg.vocab_size for o in outs for t in o),
            "every served token is in the vocabulary",
        )
        check(dev["count"] == chips, f"the replica saw all {chips} chip(s)")
        check(
            stats["compiles_at_init"] > 0,
            "the compile listener is live (it counted the engine's warm-up)",
        )
        check(
            stats["compiles_since_init"] == 0,
            "compiles_since_init == 0 over the requests",
        )
        # Last: this compiles in the replica, and would move the counter.
        dc = client.device_check(0, prompts[1], n_new)
        agree = next(
            (i for i, (a, b) in enumerate(zip(outs[1], dc["solo_tokens"])) if a != b),
            n_new,
        )
        print(
            f"serve: greedy request 1 agrees with solo gpt_generate in the "
            f"replica for {agree}/{n_new} tokens (not gated); "
            f"prefill_mosaic_calls={dc['prefill_mosaic_calls']}",
            flush=True,
        )
        if not dry:
            check(dev["platform"] == "tpu", "the replica reports platform tpu")
            check(
                all(n >= 1 for n in dc["prefill_mosaic_calls"].values()),
                "every prefill bucket holds the Mosaic flash kernel",
            )
        return {
            "device": dev,
            "mesh": stats["mesh"],
            "start_s": start_s,
            "requests": len(outs),
            "prompt_lengths": lengths,
            "compiled_count": stats["compiled_count"],
            "compiles_at_init": stats["compiles_at_init"],
            "compile_s_at_init": stats["compile_s_at_init"],
            "compiles_since_init": stats["compiles_since_init"],
            "prefill_mosaic_calls": dc["prefill_mosaic_calls"],
            "greedy_agreement": [agree, n_new],
            "memory": stats["memory"],
        }
    finally:
        client.shutdown()


def main(argv: Any = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--cpu-dry-run",
        action="store_true",
        help="toy-size control-flow check on the CPU; NOT a chip result",
    )
    args = ap.parse_args(argv)
    dry = args.cpu_dry_run

    if not os.path.isdir(os.path.join(HERE, "ray_lightning_tpu")):
        print(
            "chip_smoke: run me from a checkout: no ray_lightning_tpu/ beside "
            "this script",
            file=sys.stderr,
        )
        return 2
    from ray_lightning_tpu import fabric
    from ray_lightning_tpu.utils.compile_cache import place_compile_cache

    def on_alarm(signum: int, frame: Any) -> None:
        raise SmokeFailure(f"watchdog: not done after {WATCHDOG_S} s")

    signal.signal(signal.SIGALRM, on_alarm)
    signal.alarm(WATCHDOG_S)
    t_start = time.time()
    limits = host_limits()
    print(f"host: {limits}", flush=True)
    try:
        if dry:
            print(
                "CPU DRY RUN: toy sizes on the CPU backend. This is NOT a chip "
                "result.",
                flush=True,
            )
            chips = 2
            fabric.init(num_cpus=8, num_tpus=0)
        else:
            # Counts chips in a child process; raises if the probe cannot
            # ask the device.
            fabric.init()
            chips = int(fabric.cluster_resources().get("TPU", 0))
            if chips < 1:
                print(
                    "chip_smoke: no TPU: the fabric found no chips on this host "
                    f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}). "
                    "This script runs on the chip only.",
                    file=sys.stderr,
                )
                return 2
        # Before any worker is spawned: they inherit the variable.
        cache_dir = place_compile_cache()
        cache_before = cache_entries(cache_dir)
        print(f"compile cache: {cache_dir} entries={cache_before}", flush=True)
        from ray_lightning_tpu.models.gpt import GPTConfig

        if dry:
            cfg = GPTConfig(
                vocab_size=512, n_layer=2, n_head=4, d_model=64, max_seq=128,
                loss_chunk=32,
            )
            batch = 2
        else:
            cfg = GPTConfig.gpt2_small(loss_chunk=128)
            batch = 8
        out = os.path.join(HERE, ".chip_smoke_out", "dry" if dry else "chip")
        print(
            f"config: n_layer={cfg.n_layer} d_model={cfg.d_model} "
            f"n_head={cfg.n_head} vocab={cfg.vocab_size} seq={cfg.max_seq} "
            f"dtype={cfg.compute_dtype} attn={cfg.attn_impl} "
            f"loss_chunk={cfg.loss_chunk} per_chip_batch={batch} chips={chips}",
            flush=True,
        )
        fit = run_fit(chips, cfg, batch, out, dry)
        serve = run_serve(chips, cfg, fit["ckpt_path"], dry)
    except Exception as exc:  # noqa: BLE001 - any failed phase is a failed smoke
        import traceback

        traceback.print_exc()
        print(
            f"chip_smoke: FAILED: {type(exc).__name__}: {exc} (host: {limits})",
            file=sys.stderr,
        )
        return 1
    finally:
        signal.alarm(0)
        fabric.shutdown()
    import jax._src.xla_bridge as xb

    if xb.backends_are_initialized():
        print("chip_smoke: FAILED: this process initialised a JAX backend", file=sys.stderr)
        return 1
    cache_after = cache_entries(cache_dir)
    print(f"compile cache: {cache_dir} entries={cache_after} (was {cache_before})")
    device = {
        "platform": fit["platform"],
        "kind": fit["device_kind"],
        "count": fit["device_count"],
    }
    report = {
        "device": device,
        "dry_run": dry,
        "wall_s": round(time.time() - t_start, 1),
        "compile_cache": {"dir": cache_dir, "before": cache_before, "after": cache_after},
        "fit": fit,
        "serve": serve,
    }
    os.makedirs(os.path.join(HERE, "chiprun_out"), exist_ok=True)
    name = "chip_smoke_dry.json" if dry else f"chip_smoke_{device['count']}chip.json"
    with open(os.path.join(HERE, "chiprun_out", name), "w") as f:
        json.dump(report, f, indent=1, default=str)
    print(f"wall_s={report['wall_s']}", flush=True)
    if dry:
        print(json.dumps({"dry_run": True, "passed": True, "device": device}))
    else:
        print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
