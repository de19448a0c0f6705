"""BERT masked-LM pretraining example (data-parallel strategy).

The encoder-side sibling of ``gpt_sharded_example.py``: pretrains a
bidirectional encoder with dynamic BERT masking (80/10/10) under
``RayTPUStrategy``, then demonstrates ``fill_mask`` — masking a held-out
sequence and measuring how many tokens the encoder recovers. The
reference carries no encoder example (its examples stop at MNIST-level
classifiers); this one exists because a model zoo is part of the
TPU-native framework's surface.

Doubles as an integration smoke test (run with ``--smoke-test``), the
role the reference's examples play in CI
(/root/reference/.github/workflows/test.yaml:95-107).
"""
import argparse

import numpy as np

from ray_lightning_tpu import fabric
from ray_lightning_tpu.models import BERTConfig, BERTEncoder
from ray_lightning_tpu.models.gpt import make_fake_text
from ray_lightning_tpu.strategies import RayTPUStrategy
from ray_lightning_tpu.trainer import Trainer


def train_bert(
    num_workers: int = 2,
    num_epochs: int = 4,
    use_tpu: bool = False,
    smoke: bool = False,
) -> BERTEncoder:
    cfg = BERTConfig(
        vocab_size=128,
        n_layer=2 if smoke else 4,
        n_head=4,
        d_model=64 if smoke else 256,
        max_seq=32 if smoke else 128,
        attn_impl="reference" if smoke else "flash",
        loss_chunk=16,
        compute_dtype="float32" if smoke else "bfloat16",
    )
    module = BERTEncoder(
        config=cfg,
        batch_size=8 if smoke else 32,
        n_train=64 if smoke else 2048,
        lr=1e-3,
    )
    trainer = Trainer(
        max_epochs=num_epochs,
        strategy=RayTPUStrategy(num_workers=num_workers, use_tpu=use_tpu),
        enable_checkpointing=False,
        seed=0,
        num_sanity_val_steps=0,
    )
    trainer.fit(module)
    print(
        "final loss:",
        float(trainer.callback_metrics.get("loss", float("nan"))),
        flush=True,
    )
    return module


def demo_fill_mask(
    module: BERTEncoder, use_tpu: bool, mask_frac: float = 0.15
) -> float:
    """Mask a held-out sequence and report the recovery rate.

    Runs inside a worker actor (the gpt_sharded_example.py pattern): the
    driver never initializes a jax backend — workers own the chips, and
    on CPU the actor env pins the platform."""
    from ray_lightning_tpu.launchers.utils import TrainWorker

    cfg = module.config
    params = module.params
    clean = np.asarray(
        make_fake_text(4, seq_len=cfg.max_seq - 1, vocab=cfg.mask_id, seed=99)
        .arrays[0],
        np.int32,
    )[:, : cfg.max_seq]
    g = np.random.default_rng(0)
    sel = g.random(clean.shape) < mask_frac
    masked = np.where(sel, cfg.mask_id, clean)

    def fill():
        m = BERTEncoder(config=cfg)
        m.params = params
        return np.asarray(m.fill_mask(masked))

    env = {} if use_tpu else {"JAX_PLATFORMS": "cpu"}
    resources = {"TPU": 1.0} if use_tpu else {}
    actor = (
        fabric.remote(TrainWorker)
        .options(num_cpus=1, resources=resources, env=env)
        .remote()
    )
    try:
        filled = fabric.get(actor.execute.remote(fill), timeout=600.0)
    finally:
        fabric.kill(actor)
    recovered = float((filled[sel] == clean[sel]).mean())
    print(
        f"fill_mask recovered {recovered:.1%} of {int(sel.sum())} masked tokens",
        flush=True,
    )
    return recovered


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--num-workers", type=int, default=2)
    parser.add_argument("--num-epochs", type=int, default=4)
    parser.add_argument("--use-tpu", action="store_true")
    parser.add_argument("--smoke-test", action="store_true")
    parser.add_argument(
        "--address", default=None,
        help="fabric head address (host:port) for client mode — start one "
        "with `python -m ray_lightning_tpu.fabric.server`",
    )
    args = parser.parse_args()

    # Smoke tests over-provision logical CPUs so worker bundles always
    # fit tiny CI hosts (the ray_ddp_example.py convention).
    fabric.init(
        address=args.address, num_cpus=8 if args.smoke_test else None
    )
    module = train_bert(
        num_workers=args.num_workers,
        num_epochs=2 if args.smoke_test else args.num_epochs,
        use_tpu=args.use_tpu,
        smoke=args.smoke_test,
    )
    demo_fill_mask(module, use_tpu=args.use_tpu)
    fabric.shutdown()


if __name__ == "__main__":
    main()
