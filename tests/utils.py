"""Shared test helpers, mirroring the reference's tests/utils.py harness
(get_trainer/train_test/load_test/predict_test — /root/reference/
ray_lightning/tests/utils.py:213-272)."""
from __future__ import annotations

from typing import Any, Optional

import numpy as np

from ray_lightning_tpu.trainer import Trainer


def get_trainer(
    strategy: Any = None,
    max_epochs: int = 1,
    callbacks: Optional[list] = None,
    seed: int = 42,
    **kwargs: Any,
) -> Trainer:
    return Trainer(
        max_epochs=max_epochs,
        strategy=strategy,
        callbacks=callbacks,
        enable_checkpointing=kwargs.pop("enable_checkpointing", False),
        seed=seed,
        **kwargs,
    )


def flat_norm(params: Any) -> float:
    import jax

    leaves = jax.tree_util.tree_leaves(params)
    return float(sum(np.linalg.norm(np.asarray(l)) for l in leaves))


def train_test(trainer: Trainer, module: Any) -> None:
    """Fit and assert training moved the weights (reference
    train_test asserts weight-norm delta > 0.1, tests/utils.py:236-245)."""
    import jax

    before = None
    if module.params is not None:
        before = flat_norm(module.params)
    trainer.fit(module)
    assert trainer.state["status"] == "finished"
    after = flat_norm(module.params)
    if before is not None:
        assert abs(after - before) > 1e-3
    assert np.isfinite(after)


def predict_test(trainer: Trainer, module: Any, min_acc: float = 0.5) -> None:
    """Fit then check accuracy >= bound (reference tests/utils.py:256-272)."""
    trainer.fit(module)
    acc = trainer.callback_metrics.get("ptl/val_accuracy")
    assert acc is not None and acc >= min_acc, f"accuracy {acc} < {min_acc}"


def budget_freeze_requests(rng: Any, max_seq: int = 64) -> tuple:
    """``(first, late)`` request lists ``[(prompt, max_new_tokens)]`` of
    the serve tests' ``budget_freeze`` traffic (vocabulary 97): three
    prompts 3, 14 and 9 rows deep that share the first folds, the deepest
    with a budget that ends inside a fold; then a short prompt for the
    slot that one leaves, and one that decodes to the cache's last row."""

    def prompt(n: int) -> list:
        return rng.integers(0, 97, size=n).tolist()

    first = [(prompt(3), 30), (prompt(14), 3), (prompt(9), 11)]
    late = [(prompt(4), 8), (prompt(16), max_seq - 16)]
    return first, late


def force_decode_kernel(monkeypatch: Any) -> None:
    """Tell the one selection function (``models/gpt.py:_decode_rows_block``)
    "tpu": the decode kernel is then the read wherever its other conditions
    hold, and off the chip it runs interpreted. No option does this."""
    import functools

    from ray_lightning_tpu.models import gpt as G

    monkeypatch.setattr(
        G, "_decode_rows_block", functools.partial(G._decode_rows_block, backend="tpu")
    )


def force_prefill_kernel(monkeypatch: Any, rows: int = 0) -> None:
    """Tell the prefill's selection function (``models/mixed.py:prefill_kernel``)
    "tpu", and take the kernel from ``rows`` rows up: the forward flash kernel
    is then the read wherever its other conditions hold, and off the chip it
    runs interpreted. No option does this."""
    import functools

    from ray_lightning_tpu.models import mixed

    monkeypatch.setattr(mixed, "prefill_kernel", functools.partial(mixed.prefill_kernel, backend="tpu"))
    monkeypatch.setattr(mixed, "_KERNEL_ROWS", rows)


def mixed_program_hashes(cfg: Any) -> dict:
    """sha256 of the jaxpr texts of a mixed configuration's two serving
    programs, traced at toy sizes (three slots of 32 rows, a fold of two,
    a bucket of eight): ``{"fold": ..., "admission": ...}`` —
    ``gpt_decode_fold`` over both caches and an admission as
    ``serve/engine.py:admit_impl`` makes it (the rows' pass, both halves
    written into the slot, the head on the last real row). Two trees that
    give one text run one program."""
    import hashlib

    import jax
    import jax.numpy as jnp

    from ray_lightning_tpu.models.gpt import _rmsnorm, gpt_decode_fold, init_gpt_params
    from ray_lightning_tpu.models.mixed import empty_caches, mixed_logits, mixed_rows, write_prefill_rows

    slots, rows, bucket, fold = 3, 32, 8, 2
    params = jax.eval_shape(lambda: init_gpt_params(jax.random.PRNGKey(0), cfg))
    k_cache, v_cache = jax.eval_shape(lambda: empty_caches(cfg, slots, rows, jnp.float32))
    i32, f32 = jax.ShapeDtypeStruct((slots,), jnp.int32), jax.ShapeDtypeStruct((slots,), jnp.float32)

    def step(params, k_cache, v_cache, cur, pos, temps, top_ks, top_ps, keys, active, remaining, eos):
        return gpt_decode_fold(params, cfg, cur, pos, keys, temps, top_ks, top_ps, active, remaining, eos,
                               k_cache, v_cache, fold=fold)

    def admit(params, k_cache, v_cache, prompt, last_idx, slot):
        h, pf_k, pf_v, counts = mixed_rows(params, cfg, prompt, true_len=last_idx + 1)
        k_cache, v_cache = write_prefill_rows(k_cache, v_cache, pf_k, pf_v, slot, last_idx + 1)
        h_last = jax.lax.dynamic_slice_in_dim(h, last_idx, 1, axis=1)
        logits = mixed_logits(_rmsnorm(h_last, params["lnf_g"], cfg.norm_eps)[:, 0], params, cfg)
        return k_cache, v_cache, jnp.argmax(logits, -1), counts

    scalar = jax.ShapeDtypeStruct((), jnp.int32)
    texts = {
        "fold": jax.make_jaxpr(step)(
            params, k_cache, v_cache, i32, i32, f32, i32, f32, jax.ShapeDtypeStruct((slots, 2), jnp.uint32),
            jax.ShapeDtypeStruct((slots,), jnp.bool_), i32, i32),
        "admission": jax.make_jaxpr(admit)(
            params, k_cache, v_cache, jax.ShapeDtypeStruct((1, bucket), jnp.int32), scalar, scalar),
    }
    return {k: hashlib.sha256(str(v).encode()).hexdigest() for k, v in texts.items()}
