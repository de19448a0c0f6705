"""BERT-style bidirectional encoder with masked-LM pretraining.

The reference tops out at example-level models (see models/gpt.py's
module docstring); the TPU-native framework carries a model zoo that
exercises every compute path at model level. The encoder is the
non-causal counterpart of the GPT family: same stacked-``lax.scan``
blocks, same logical-axis TP sharding, same Pallas flash attention —
but with ``causal=False`` (full bidirectional mixing) and a masked-LM
objective instead of next-token prediction.

Design notes (TPU-first):
- Pre-LN blocks (like the GPT family): one compiled block body scanned
  over stacked per-layer leaves; gelu MLP.
- Dynamic BERT masking (80/10/10) happens INSIDE the jitted training
  step from the step rng — no host-side mask materialization, and every
  epoch re-masks for free.
- The MLM loss reuses :func:`~ray_lightning_tpu.models.gpt.chunked_lm_loss`
  with negative targets as ignore labels — unmasked positions simply
  never enter the loss, and fp32 logits only ever materialize at
  ``(B, chunk, V)`` when ``loss_chunk > 0``.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from ray_lightning_tpu.models.gpt import chunked_lm_loss, make_fake_text
from ray_lightning_tpu.models.layers import _layernorm, _lm_head
from ray_lightning_tpu.trainer.data import DataLoader, Dataset
from ray_lightning_tpu.trainer.module import TPUModule


@dataclass(frozen=True)
class BERTConfig:
    vocab_size: int = 256
    n_layer: int = 2
    n_head: int = 4
    d_model: int = 128
    d_ff: int = 0  # 0 -> 4 * d_model
    max_seq: int = 128
    compute_dtype: str = "float32"  # "bfloat16" for TPU runs
    remat: bool = False
    attn_impl: str = "flash"  # "flash" | "reference"
    # Masked-LM objective: fraction of positions selected per sequence,
    # split 80% [MASK] / 10% random token / 10% kept (BERT's recipe).
    mask_prob: float = 0.15
    # [MASK] id; the default reserves the last vocab row.
    mask_token_id: int = -1
    # S-chunk size for the fused MLM head + CE (see GPTConfig.loss_chunk).
    loss_chunk: int = 0
    init_std: float = 0.02

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_head

    @property
    def ff_dim(self) -> int:
        return self.d_ff or 4 * self.d_model

    @property
    def mask_id(self) -> int:
        return self.mask_token_id if self.mask_token_id >= 0 else self.vocab_size - 1


def init_bert_params(rng: jax.Array, cfg: BERTConfig) -> Dict[str, Any]:
    """Parameter pytree with stacked per-layer leaves (leading dim L)."""
    L, D, H, hd, F = (
        cfg.n_layer,
        cfg.d_model,
        cfg.n_head,
        cfg.head_dim,
        cfg.ff_dim,
    )
    std = cfg.init_std
    res_std = std / np.sqrt(2.0 * L)
    keys = jax.random.split(rng, 7)

    def norm(key, shape, s):
        return (jax.random.normal(key, shape) * s).astype(jnp.float32)

    return {
        "wte": norm(keys[0], (cfg.vocab_size, D), std),
        "wpe": norm(keys[1], (cfg.max_seq, D), std),
        "blocks": {
            "ln1_g": jnp.ones((L, D)),
            "ln1_b": jnp.zeros((L, D)),
            "wqkv": norm(keys[2], (L, D, 3, H, hd), std),
            "bqkv": jnp.zeros((L, 3, H, hd)),
            "wo": norm(keys[3], (L, H, hd, D), res_std),
            "bo": jnp.zeros((L, D)),
            "ln2_g": jnp.ones((L, D)),
            "ln2_b": jnp.zeros((L, D)),
            "wi": norm(keys[4], (L, D, F), std),
            "bi": jnp.zeros((L, F)),
            "wo2": norm(keys[5], (L, F, D), res_std),
            "bo2": jnp.zeros((L, D)),
        },
        "lnf_g": jnp.ones((D,)),
        "lnf_b": jnp.zeros((D,)),
        # MLM transform before the tied decoder (BERT's extra dense+LN).
        "mlm_w": norm(keys[6], (D, D), std),
        "mlm_b": jnp.zeros((D,)),
        "mlm_ln_g": jnp.ones((D,)),
        "mlm_ln_b": jnp.zeros((D,)),
    }


def bert_logical_axes(cfg: BERTConfig) -> Dict[str, Any]:
    """Logical axis names per parameter (same rule set as the GPT family:
    embed->fsdp, heads/mlp/vocab->model)."""
    return {
        "wte": ("vocab", "embed"),
        "wpe": (None, "embed"),
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
        "lnf_g": (None,),
        "lnf_b": (None,),
        "mlm_w": ("embed", None),
        "mlm_b": (None,),
        "mlm_ln_g": (None,),
        "mlm_ln_b": (None,),
    }


def bert_forward(
    params: Dict[str, Any],
    tokens: jax.Array,
    cfg: BERTConfig,
    return_hidden: bool = False,
) -> jax.Array:
    """tokens (B, S) int32 -> MLM logits (B, S, V).

    Bidirectional: every position attends to every position
    (``causal=False`` through the same Pallas kernel the GPT family
    uses). ``return_hidden`` returns the post-MLM-transform hidden
    states (B, S, D) for :func:`chunked_lm_loss`.
    """
    from ray_lightning_tpu.ops import attention_reference, flash_attention

    cdt = jnp.dtype(cfg.compute_dtype)
    B, S = tokens.shape
    x = (params["wte"][tokens] + params["wpe"][:S]).astype(cdt)

    def attend(q, k, v):
        if cfg.attn_impl == "reference":
            return attention_reference(q, k, v, causal=False)
        return flash_attention(q, k, v, causal=False)

    def block(h, lp):
        a = _layernorm(h, lp["ln1_g"], lp["ln1_b"])
        qkv = (
            jnp.einsum("bsd,dthk->bsthk", a, lp["wqkv"].astype(cdt))
            + lp["bqkv"].astype(cdt)
        )
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        o = attend(q, k, v)
        h = h + jnp.einsum("bshk,hkd->bsd", o, lp["wo"].astype(cdt)) + lp[
            "bo"
        ].astype(cdt)
        m = _layernorm(h, lp["ln2_g"], lp["ln2_b"])
        m = jax.nn.gelu(
            jnp.einsum("bsd,df->bsf", m, lp["wi"].astype(cdt))
            + lp["bi"].astype(cdt)
        )
        m = jnp.einsum("bsf,fd->bsd", m, lp["wo2"].astype(cdt)) + lp[
            "bo2"
        ].astype(cdt)
        return h + m, None

    body = jax.checkpoint(block) if cfg.remat else block
    x, _ = jax.lax.scan(body, x, params["blocks"])
    x = _layernorm(x, params["lnf_g"], params["lnf_b"])
    # MLM transform: dense + gelu + LN, then the tied decoder.
    x = jax.nn.gelu(
        jnp.einsum("bsd,de->bse", x, params["mlm_w"].astype(cdt))
        + params["mlm_b"].astype(cdt)
    )
    x = _layernorm(x, params["mlm_ln_g"], params["mlm_ln_b"])
    if return_hidden:
        return x
    return _lm_head(x, params["wte"])


def apply_mlm_masking(
    rng: jax.Array, tokens: jax.Array, cfg: BERTConfig
) -> Tuple[jax.Array, jax.Array]:
    """BERT dynamic masking: (inputs, targets) from clean tokens.

    ``mask_prob`` of positions are selected; of those 80% become
    ``[MASK]``, 10% a uniform random token, 10% stay. Targets carry the
    ORIGINAL id at selected positions and -1 (ignore) elsewhere —
    exactly the contract :func:`chunked_lm_loss` averages over. Runs
    traced (inside jit) so every step re-masks from its own rng.
    """
    r_sel, r_split, r_rand = jax.random.split(rng, 3)
    sel = jax.random.uniform(r_sel, tokens.shape) < cfg.mask_prob
    u = jax.random.uniform(r_split, tokens.shape)
    # The 10% branch replaces with a REAL vocabulary token: draw from the
    # vocab minus [MASK] by sampling vocab_size-1 values and shifting the
    # ones at/above mask_id up by one (uniform over every non-mask id,
    # wherever mask_token_id sits).
    rand_toks = jax.random.randint(
        r_rand, tokens.shape, 0, cfg.vocab_size - 1, dtype=tokens.dtype
    )
    rand_toks = jnp.where(rand_toks >= cfg.mask_id, rand_toks + 1, rand_toks)
    masked = jnp.where(
        u < 0.8,
        jnp.asarray(cfg.mask_id, tokens.dtype),
        jnp.where(u < 0.9, rand_toks, tokens),
    )
    inputs = jnp.where(sel, masked, tokens)
    targets = jnp.where(sel, tokens, jnp.asarray(-1, tokens.dtype))
    return inputs, targets


def masked_lm_loss(
    logits: jax.Array, targets: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """Mean CE + accuracy over positions with ``targets >= 0`` (dense
    counterpart of the chunked path; equality asserted in tests)."""
    valid = targets >= 0
    safe = jnp.clip(targets, 0)
    ce = optax.softmax_cross_entropy_with_integer_labels(logits, safe)
    n = jnp.maximum(jnp.sum(valid.astype(jnp.float32)), 1.0)
    loss = jnp.sum(jnp.where(valid, ce, 0.0)) / n
    hit = (jnp.argmax(logits, -1) == targets) & valid
    return loss, jnp.sum(hit.astype(jnp.float32)) / n


class BERTEncoder(TPUModule):
    """Masked-LM pretraining module over the synthetic token corpus.

    The affine-recurrence corpus (:func:`make_fake_text`) is ideal for
    MLM: a masked token is recoverable from either neighbor, so loss
    drops far below ln(V) once the encoder uses both directions.
    """

    def __init__(
        self,
        config: Optional[BERTConfig | Dict[str, Any]] = None,
        lr: float = 3e-4,
        warmup_steps: int = 20,
        batch_size: int = 8,
        n_train: int = 256,
        dataset: Optional[Dataset] = None,
        weight_decay: float = 0.01,
    ) -> None:
        super().__init__()
        if isinstance(config, dict):
            config = BERTConfig(**config)
        self.config = config or BERTConfig()
        self.lr = lr
        self.warmup_steps = warmup_steps
        self.batch_size = batch_size
        self.n_train = n_train
        self._dataset = dataset
        self.weight_decay = weight_decay

    def param_logical_axes(self) -> Dict[str, Any]:
        return bert_logical_axes(self.config)

    def init_params(self, rng: jax.Array, batch: Any) -> Any:
        return init_bert_params(rng, self.config)

    def _loss(self, params: Any, batch: Any, rng: jax.Array) -> Any:
        toks = batch[0] if isinstance(batch, (tuple, list)) else batch
        toks = toks[:, : self.config.max_seq]
        inputs, targets = apply_mlm_masking(rng, toks, self.config)
        if self.config.loss_chunk > 0:
            hidden = bert_forward(params, inputs, self.config, return_hidden=True)
            return chunked_lm_loss(
                hidden, params["wte"], targets, self.config.loss_chunk
            )
        return masked_lm_loss(bert_forward(params, inputs, self.config), targets)

    def training_step(self, params, batch, rng):
        loss, acc = self._loss(params, batch, rng)
        return loss, {"loss": loss, "mlm_acc": acc}

    def validation_step(self, params, batch):
        # Deterministic eval masking: a fixed key, so val_loss is
        # comparable across epochs (train re-masks every step).
        loss, acc = self._loss(params, batch, jax.random.PRNGKey(0))
        return {"val_loss": loss, "val_accuracy": acc}

    def configure_optimizers(self):
        sched = optax.warmup_cosine_decay_schedule(
            0.0, self.lr, self.warmup_steps, max(self.warmup_steps + 1, 10_000)
        )
        return {
            "optimizer": optax.adamw(sched, weight_decay=self.weight_decay),
            "lr_schedule": sched,
        }

    def fill_mask(self, tokens: Any) -> jax.Array:
        """Argmax prediction at every ``[MASK]`` position; all other
        positions pass through unchanged. tokens (B, S) int with
        ``mask_id`` at the positions to fill."""
        if self.params is None:
            raise RuntimeError("no parameters: fit first or set module.params")
        toks = jnp.asarray(tokens, jnp.int32)
        # Fitted params arrive as host numpy (gather_state); device-ify
        # once (the gpt_generate pattern, models/gpt.py).
        params = jax.tree_util.tree_map(jnp.asarray, self.params)
        logits = bert_forward(params, toks, self.config)
        # Never "fill" with [MASK] itself: its wte row has a logit too,
        # and an undertrained model may rank it first.
        logits = logits.at[..., self.config.mask_id].set(-jnp.inf)
        pred = jnp.argmax(logits, -1).astype(toks.dtype)
        return jnp.where(toks == self.config.mask_id, pred, toks)

    def _data(self) -> Dataset:
        if self._dataset is None:
            # Reserve the [MASK] row: corpus tokens stay below mask_id.
            self._dataset = make_fake_text(
                self.n_train,
                seq_len=self.config.max_seq - 1,
                vocab=self.config.mask_id,
            )
        return self._dataset

    def train_dataloader(self) -> DataLoader:
        return DataLoader(self._data(), batch_size=self.batch_size, shuffle=True)

    def val_dataloader(self) -> DataLoader:
        # Held-out corpus (same recurrence, different seed — the GPTLM
        # convention) so val_loss carries a generalization signal.
        return DataLoader(
            make_fake_text(
                64,
                seq_len=self.config.max_seq - 1,
                vocab=self.config.mask_id,
                seed=7,
            ),
            batch_size=self.batch_size,
        )
