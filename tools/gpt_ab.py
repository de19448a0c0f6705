"""On-chip A/B: GPT-2 124M tokens/s across (batch, loss_chunk) configs.

Each config gets a fresh worker process (fresh XLA runtime), mirroring
bench_gpt's methodology. Prints one JSON line per config.
"""
import argparse
import json
import statistics
import sys
import time


def run_config(batch: int, chunk: int, seq: int, epochs: int, fold: int = 1) -> dict:
    from ray_lightning_tpu.models import GPTConfig
    from ray_lightning_tpu.models.gpt import GPTLM
    from ray_lightning_tpu.strategies import RayShardedStrategy
    from ray_lightning_tpu.trainer import Trainer, TPUStatsCallback

    cfg = GPTConfig.gpt2_small(max_seq=seq, remat=False, loss_chunk=chunk)
    module = GPTLM(config=cfg, batch_size=batch, n_train=batch * 16)
    stats = TPUStatsCallback(verbose=False)
    trainer = Trainer(
        max_epochs=epochs,
        enable_checkpointing=False,
        callbacks=[stats],
        seed=0,
        log_every_n_steps=10**9,
        num_sanity_val_steps=0,
        check_val_every_n_epoch=10**9,
        steps_per_execution=fold,
        strategy=RayShardedStrategy(num_workers=1, use_tpu=True),
    )
    t0 = time.time()
    trainer.fit(module)
    steps_per_epoch = trainer.global_step // epochs
    rates = [steps_per_epoch / t for t in stats.epoch_times[1:]]
    sps = statistics.median(rates)
    return {
        "batch": batch,
        "loss_chunk": chunk,
        "fold": fold,
        "steps_per_sec": round(sps, 3),
        "tokens_per_sec": round(sps * batch * seq, 1),
        "wall_s": round(time.time() - t0, 1),
    }


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--seq", type=int, default=512)
    p.add_argument("--epochs", type=int, default=3)
    p.add_argument(
        "--configs",
        default="16:0,16:128,32:128,48:128,32:128:4,48:128:4",
        help="comma-separated batch:loss_chunk[:fold] specs",
    )
    args = p.parse_args()

    from ray_lightning_tpu import fabric
    from ray_lightning_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()
    fabric.init(num_cpus=8.0)
    for spec in args.configs.split(","):
        parts = [int(v) for v in spec.split(":")]
        b, c = parts[0], parts[1]
        fold = parts[2] if len(parts) > 2 else 1
        try:
            out = run_config(b, c, args.seq, args.epochs, fold=fold)
        except Exception as exc:  # noqa: BLE001 - record OOMs, keep sweeping
            out = {"batch": b, "loss_chunk": c, "fold": fold,
                   "error": f"{type(exc).__name__}: {str(exc)[:300]}"}
        print(json.dumps(out), flush=True)
    fabric.shutdown()


if __name__ == "__main__":
    main()
