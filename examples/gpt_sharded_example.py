"""GPT-2 training example: ZeRO-sharded data parallelism + generation.

The flagship-model analog of the reference's example set
(/root/reference/ray_lightning/examples/ray_ddp_sharded_example.py trains a
transformer under the FairScale-sharded strategy): trains a GPT on the
synthetic LM corpus under ``RayShardedStrategy`` (GSPMD-sharded optimizer
state), reports epoch wall time and device memory via ``TPUStatsCallback``,
then greedily generates from the fitted weights with the KV-cache decoder.

Smoke-test CI mode mirrors the reference's ``--smoke-test`` convention.
"""
import argparse

from ray_lightning_tpu import fabric
from ray_lightning_tpu.models import GPTConfig
from ray_lightning_tpu.models.gpt import GPTLM
from ray_lightning_tpu.strategies import RayShardedStrategy
from ray_lightning_tpu.trainer import Trainer, TPUStatsCallback


def train_gpt(
    num_workers: int = 2,
    num_epochs: int = 2,
    use_tpu: bool = False,
    smoke_test: bool = False,
    modern: bool = False,
    from_hf: str = None,
) -> Trainer:
    """``modern=True`` enables the Mistral-style variant: RoPE positions,
    grouped-query attention (12 -> 4 kv heads: a 3x smaller decode cache;
    MQA in smoke mode), and a sliding attention window — same
    trainer/strategy surface, one config change. ``from_hf`` fine-tunes a
    local Hugging Face GPT-2 checkpoint instead of training from scratch
    (weights imported via :func:`load_hf_gpt2`)."""
    if from_hf:
        if modern:
            raise SystemExit(
                "--from-hf imports a stock GPT-2 (learned positions, MHA); "
                "it cannot be combined with --modern"
            )
        from ray_lightning_tpu.models import load_hf_gpt2

        params, cfg = load_hf_gpt2(from_hf)
        module = GPTLM(config=cfg, batch_size=4 if smoke_test else 16,
                       n_train=64 if smoke_test else 2048, lr=1e-4)
    elif smoke_test:
        extra = dict(pos_embed="rope", n_kv_head=1, attn_window=16) if modern else {}
        cfg = GPTConfig(
            vocab_size=64, n_layer=2, n_head=2, d_model=32, max_seq=32,
            attn_impl="reference", **extra,
        )
        module = GPTLM(config=cfg, batch_size=4, n_train=64, lr=3e-3,
                       warmup_steps=5)
    else:
        extra = dict(pos_embed="rope", n_kv_head=4, attn_window=256) if modern else {}
        cfg = GPTConfig.gpt2_small(max_seq=512, **extra)
        module = GPTLM(config=cfg, batch_size=16, n_train=2048)
    stats = TPUStatsCallback()
    trainer = Trainer(
        max_epochs=num_epochs,
        callbacks=[stats],
        strategy=RayShardedStrategy(num_workers=num_workers, use_tpu=use_tpu),
        enable_checkpointing=False,
        precision="bf16" if use_tpu else "fp32",
        seed=0,
        log_grad_norm=True,
    )
    ckpt_path = None
    if from_hf:
        # fit() always initializes from the module's init_params; imported
        # weights enter through the resume path (params-only checkpoint).
        import tempfile

        from ray_lightning_tpu.utils import to_state_stream

        f = tempfile.NamedTemporaryFile(suffix=".ckpt", delete=False)
        f.write(to_state_stream({"params": params}))
        f.close()
        ckpt_path = f.name
    trainer.fit(module, ckpt_path=ckpt_path)
    print("val loss:", trainer.callback_metrics.get("val_loss"))

    # KV-cached greedy generation from the recovered rank-0 weights — run
    # inside a worker actor so the DRIVER never binds the accelerator (the
    # same discipline the launcher keeps during training).
    import numpy as np

    from ray_lightning_tpu.launchers.utils import TrainWorker

    params = module.params
    prompt = np.asarray([[1, 12, 3]], np.int32)

    def decode():
        from ray_lightning_tpu.models.gpt import gpt_generate

        return np.asarray(
            gpt_generate(params, cfg, prompt, max_new_tokens=8)
        )

    env = {} if use_tpu else {"JAX_PLATFORMS": "cpu"}
    resources = {"TPU": 1.0} if use_tpu else {}
    actor = (
        fabric.remote(TrainWorker)
        .options(num_cpus=1, resources=resources, env=env)
        .remote()
    )
    try:
        out = fabric.get(actor.execute.remote(decode), timeout=900)
    finally:
        fabric.kill(actor)
    print("generated:", out[0].tolist())
    return trainer


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--num-workers", type=int, default=2)
    parser.add_argument("--num-epochs", type=int, default=2)
    parser.add_argument("--use-tpu", action="store_true", default=False)
    parser.add_argument("--smoke-test", action="store_true")
    parser.add_argument(
        "--modern", action="store_true",
        help="RoPE + grouped-query attention + sliding window variant",
    )
    parser.add_argument(
        "--from-hf", type=str, default=None, metavar="PATH",
        help="fine-tune a LOCAL Hugging Face GPT-2 checkpoint directory "
        "instead of training from scratch (load_hf_gpt2 bridge)",
    )
    parser.add_argument(
        "--address", type=str, default=None,
        help="fabric head address (host:port) for client mode — start one "
        "with `python -m ray_lightning_tpu.fabric.server`",
    )
    parser.add_argument("--num-cpus", type=int, default=None)
    args = parser.parse_args()

    num_cpus = args.num_cpus
    if num_cpus is None and args.smoke_test:
        num_cpus = 8
    fabric.init(address=args.address, num_cpus=num_cpus)
    train_gpt(
        num_workers=args.num_workers,
        num_epochs=1 if args.smoke_test else args.num_epochs,
        use_tpu=args.use_tpu,
        smoke_test=args.smoke_test,
        modern=args.modern,
        from_hf=args.from_hf,
    )
    fabric.shutdown()


if __name__ == "__main__":
    main()
