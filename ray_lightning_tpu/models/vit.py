"""Vision Transformer — the attention-on-images model family.

Beyond-parity: the reference's model zoo stops at MNIST MLPs and example
CIFAR models (SURVEY.md §2 row 12); this adds the standard ViT
classifier, built TPU-first:

- **Patchify as reshape + one matmul** (no conv, no gather): images fold
  to ``(B, N, ps*ps*C)`` with pure reshapes/transposes and hit the MXU as
  a single large projection.
- **Stacked blocks under ``lax.scan``** (compile once per depth, like
  ``models/gpt.py``) with parameters carrying a leading ``layers`` dim —
  the same layout the pipeline axis shards.
- **Non-causal flash attention** (``ops/flash_attention.py``) for the
  within-chip blocks; reference attention as fallback.
- **Logical axes** (``param_logical_axes``) so ``GSPMDStrategy`` shards
  heads/mlp over "model" and embeddings over "fsdp" with the same t5x
  rules as the GPT family.
- uint8 NHWC batches normalized on device (4x less H2D than f32).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import optax

from ray_lightning_tpu.models.layers import _layernorm
from ray_lightning_tpu.models.resnet import ImageClassifierModule
from ray_lightning_tpu.trainer.data import ArrayDataset


@dataclasses.dataclass(frozen=True)
class ViTConfig:
    image_size: int = 32
    patch_size: int = 4
    channels: int = 3
    num_classes: int = 10
    n_layer: int = 6
    n_head: int = 4
    d_model: int = 128
    d_ff: int = 512
    compute_dtype: str = "float32"
    attn_impl: str = "flash"  # "flash" | "reference"
    dropout: float = 0.0  # reserved; ViT-S/16-style configs train without

    def __post_init__(self) -> None:
        if self.image_size % self.patch_size:
            raise ValueError(
                f"image_size {self.image_size} not divisible by patch_size "
                f"{self.patch_size}"
            )
        if self.d_model % self.n_head:
            raise ValueError(
                f"d_model {self.d_model} not divisible by n_head {self.n_head}"
            )

    @property
    def n_patches(self) -> int:
        return (self.image_size // self.patch_size) ** 2

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_head


def vit_logical_axes(cfg: ViTConfig) -> Dict[str, Any]:
    """Same t5x-style vocabulary as ``gpt_logical_axes``: heads/mlp ->
    "model", embed -> "fsdp", layers -> "pp"/replicated."""
    return {
        "patch_w": (None, "embed"),
        "patch_b": (None,),
        "cls": (None,),
        "pos": (None, "embed"),
        "blocks": {
            "ln1_g": ("layers", None),
            "ln1_b": ("layers", None),
            "wqkv": ("layers", "embed", None, "heads", "kv"),
            "bqkv": ("layers", None, "heads", "kv"),
            "wo": ("layers", "heads", "kv", "embed"),
            "bo": ("layers", None),
            "ln2_g": ("layers", None),
            "ln2_b": ("layers", None),
            "wi": ("layers", "embed", "mlp"),
            "bi": ("layers", "mlp"),
            "wo2": ("layers", "mlp", "embed"),
            "bo2": ("layers", None),
        },
        "head_ln_g": (None,),
        "head_ln_b": (None,),
        "head_w": ("embed", None),
        "head_b": (None,),
    }


def init_vit_params(rng: jax.Array, cfg: ViTConfig) -> Dict[str, Any]:
    L, D, F = cfg.n_layer, cfg.d_model, cfg.d_ff
    H, hd = cfg.n_head, cfg.head_dim
    patch_dim = cfg.patch_size * cfg.patch_size * cfg.channels
    ks = jax.random.split(rng, 8)

    def norm(key, shape, scale):
        return (jax.random.normal(key, shape) * scale).astype(jnp.float32)

    return {
        "patch_w": norm(ks[0], (patch_dim, D), patch_dim**-0.5),
        "patch_b": jnp.zeros((D,)),
        "cls": norm(ks[1], (D,), 0.02),
        "pos": norm(ks[2], (cfg.n_patches + 1, D), 0.02),
        "blocks": {
            "ln1_g": jnp.ones((L, D)),
            "ln1_b": jnp.zeros((L, D)),
            "wqkv": norm(ks[3], (L, D, 3, H, hd), D**-0.5),
            "bqkv": jnp.zeros((L, 3, H, hd)),
            "wo": norm(ks[4], (L, H, hd, D), (H * hd) ** -0.5),
            "bo": jnp.zeros((L, D)),
            "ln2_g": jnp.ones((L, D)),
            "ln2_b": jnp.zeros((L, D)),
            "wi": norm(ks[5], (L, D, F), D**-0.5),
            "bi": jnp.zeros((L, F)),
            "wo2": norm(ks[6], (L, F, D), F**-0.5),
            "bo2": jnp.zeros((L, D)),
        },
        "head_ln_g": jnp.ones((D,)),
        "head_ln_b": jnp.zeros((D,)),
        "head_w": norm(ks[7], (D, cfg.num_classes), D**-0.5),
        "head_b": jnp.zeros((cfg.num_classes,)),
    }


def patchify(images: jax.Array, cfg: ViTConfig) -> jax.Array:
    """(B, H, W, C) -> (B, N, ps*ps*C) with pure reshapes/transposes."""
    B = images.shape[0]
    ps, n_side = cfg.patch_size, cfg.image_size // cfg.patch_size
    x = images.reshape(B, n_side, ps, n_side, ps, cfg.channels)
    x = x.transpose(0, 1, 3, 2, 4, 5)  # (B, nh, nw, ps, ps, C)
    return x.reshape(B, n_side * n_side, ps * ps * cfg.channels)


def vit_forward(
    params: Dict[str, Any], images: jax.Array, cfg: ViTConfig
) -> jax.Array:
    """(B, H, W, C) float images -> (B, num_classes) logits."""
    cdt = jnp.dtype(cfg.compute_dtype)
    B = images.shape[0]
    x = patchify(images.astype(cdt), cfg) @ params["patch_w"].astype(cdt)
    x = x + params["patch_b"].astype(cdt)
    cls = jnp.broadcast_to(params["cls"].astype(cdt), (B, 1, cfg.d_model))
    x = jnp.concatenate([cls, x], axis=1) + params["pos"].astype(cdt)

    def attend(q, k, v):
        if cfg.attn_impl == "flash":
            from ray_lightning_tpu.ops import flash_attention

            return flash_attention(q, k, v, causal=False)
        from ray_lightning_tpu.ops import attention_reference

        return attention_reference(q, k, v, causal=False)

    H, hd = cfg.n_head, cfg.head_dim

    def block(h: jax.Array, lp: Dict[str, jax.Array]):
        a = _layernorm(h, lp["ln1_g"], lp["ln1_b"])
        qkv = (
            jnp.einsum("bsd,dthk->bsthk", a, lp["wqkv"].astype(cdt))
            + lp["bqkv"].astype(cdt)
        )
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]  # (B, S, H, hd)
        o = attend(q, k, v)
        h = h + jnp.einsum("bshk,hkd->bsd", o, lp["wo"].astype(cdt)) + lp[
            "bo"
        ].astype(cdt)
        m = _layernorm(h, lp["ln2_g"], lp["ln2_b"])
        m = jax.nn.gelu(
            jnp.einsum("bsd,df->bsf", m, lp["wi"].astype(cdt))
            + lp["bi"].astype(cdt)
        )
        h = h + jnp.einsum("bsf,fd->bsd", m, lp["wo2"].astype(cdt)) + lp[
            "bo2"
        ].astype(cdt)
        return h, None

    x, _ = jax.lax.scan(block, x, params["blocks"])
    x = _layernorm(x[:, 0], params["head_ln_g"], params["head_ln_b"])
    return (
        x.astype(jnp.float32) @ params["head_w"] + params["head_b"]
    )


class ViTClassifier(ImageClassifierModule):
    """ViT image classifier TPUModule: the shared image-classifier surface
    (``ImageClassifierModule`` in models/resnet.py — normalization, steps,
    fake-CIFAR loaders sized to ``config.image_size``) over the functional
    ViT forward."""

    def __init__(
        self,
        config: Optional[ViTConfig] = None,
        lr: float = 1e-3,
        batch_size: int = 32,
        n_train: int = 512,
        warmup_steps: int = 0,
        dataset: Optional[ArrayDataset] = None,
        **cfg_kwargs: Any,
    ) -> None:
        super().__init__()
        if config is None:
            config = ViTConfig(**cfg_kwargs)
        elif cfg_kwargs:
            config = dataclasses.replace(config, **cfg_kwargs)
        self.config = config
        self.num_classes = config.num_classes
        self.image_size = config.image_size
        self.lr = lr
        self.batch_size = batch_size
        self.n_train = n_train
        self.warmup_steps = warmup_steps
        self._dataset = dataset

    def param_logical_axes(self) -> Dict[str, Any]:
        return vit_logical_axes(self.config)

    # -- model -----------------------------------------------------------
    def init_params(self, rng: jax.Array, batch: Any) -> Any:
        del batch
        return init_vit_params(rng, self.config)

    def _forward(self, params: Any, x: jax.Array) -> jax.Array:
        return vit_forward(params, x, self.config)

    def configure_optimizers(self):
        if self.warmup_steps:
            sched = optax.warmup_cosine_decay_schedule(
                0.0, self.lr, self.warmup_steps, max(self.warmup_steps * 10, 100)
            )
            return {"optimizer": optax.adamw(sched), "lr_schedule": sched}
        return optax.adamw(self.lr)
