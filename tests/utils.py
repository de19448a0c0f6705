"""Shared test helpers, mirroring the reference's tests/utils.py harness
(get_trainer/train_test/load_test/predict_test — /root/reference/
ray_lightning/tests/utils.py:213-272)."""
from __future__ import annotations

import functools
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
    """Tell the one selection function (``models/layers.py:decode_rows_block``)
    "tpu": the decode kernel is then the read wherever its other conditions
    hold, and off the chip it runs interpreted. No option does this."""
    import functools

    from ray_lightning_tpu.models import layers

    monkeypatch.setattr(
        layers, "decode_rows_block", functools.partial(layers.decode_rows_block, backend="tpu")
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

    from ray_lightning_tpu.models.gpt import gpt_decode_fold, init_gpt_params
    from ray_lightning_tpu.models.layers import _rmsnorm
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


#: The uniform configurations whose programs ``uniform_program_hashes`` runs:
#: ``name -> (GPTConfig fields, the tree is an engine's)``. GPT-2's parts
#: (learned positions, LayerNorm, the fused QKV, GELU, the tied head),
#: Mistral's (rotary, RMSNorm, grouped KV heads, SwiGLU, an untied head) on
#: the stored tree and on ``engine_weights``' tree (whose fold and verify
#: read a cache of rows, as the single-device engine keeps it), the same
#: under a window with sinks, and a uniform layer of routed experts.
_MISTRAL = dict(
    n_head=4, n_kv_head=2, pos_embed="rope", norm_impl="rmsnorm", mlp_variant="swiglu",
    tie_word_embeddings=False, compute_dtype="bfloat16", attn_impl="flash",
)
UNIFORM_CASES = {
    "gpt2": (dict(n_head=4, attn_impl="reference"), False),
    "mistral": (_MISTRAL, False),
    "mistral_engine": (_MISTRAL, True),
    "mistral_window": (dict(_MISTRAL, attn_window=4, attn_sinks=1), False),
    "mistral_window_engine": (dict(_MISTRAL, attn_window=4, attn_sinks=1), True),
    "moe": (dict(n_head=2, pos_embed="rope", norm_impl="rmsnorm", mlp_variant="swiglu", n_experts=4,
                 moe_top_k=2, attn_impl="reference"), False),
}
UNIFORM_MODES = ("forward_grad", "prefill", "prefill_chunk", "decode_fold", "decode_verify", "decode_step_paged")
#: Every (case, mode) that runs somewhere: training never takes an engine's tree.
UNIFORM_PROGRAMS = [
    (case, mode) for case, (_, engine) in UNIFORM_CASES.items() for mode in UNIFORM_MODES
    if not (engine and mode == "forward_grad")
]


@functools.lru_cache(maxsize=None)
def _uniform_inputs(case: str) -> tuple:
    """``(cfg, params, tokens (3, 17), caches)`` of a case, from fixed keys;
    ``caches`` by name, a K and a V each."""
    import jax
    import jax.numpy as jnp

    from ray_lightning_tpu.models import gpt as G

    fields, engine = UNIFORM_CASES[case]
    cfg = G.GPTConfig(vocab_size=61, n_layer=2, d_model=32, d_ff=48, max_seq=16, **fields)
    L, B, S, Hkv, hd = cfg.n_layer, 3, cfg.max_seq, cfg.kv_head, cfg.head_dim
    lead = {"slot": ((L, 1, S), False), "slots": ((L, B, S), engine), "pages": ((L, 1 + B * 4, S // 4), False)}

    # one draw from the one key, cut up on the host (a draw a leaf is a compile a leaf): weights
    # and biases 0.05 wide, gains about one, the caches whatever a last tenant left
    shapes = jax.eval_shape(lambda: G.init_gpt_params(jax.random.PRNGKey(0), cfg))
    noise = np.asarray(jax.random.normal(jax.random.PRNGKey(49), (1 << 17,), jnp.float32))
    taken = [0]

    def take(shape, scale=1.0, base=0.0):
        n = int(np.prod(shape))
        taken[0] += n
        assert taken[0] <= noise.size
        return jnp.asarray(base + scale * noise[taken[0] - n:taken[0]].reshape(shape))

    paths, tree = jax.tree_util.tree_flatten_with_path(shapes)
    params = jax.tree_util.tree_unflatten(tree, [
        take(a.shape, 0.1, 1.0) if jax.tree_util.keystr(path).endswith("_g']") else take(a.shape, 0.05)
        for path, a in paths
    ])
    caches = {
        name: tuple(
            take(shape + ((Hkv * hd,) if rows else (Hkv, hd))).astype(jnp.dtype(cfg.compute_dtype)) for _ in range(2))
        for name, (shape, rows) in lead.items()
    }
    toks = jax.random.randint(jax.random.PRNGKey(50), (B, S + 1), 0, cfg.vocab_size, jnp.int32)
    return cfg, G.engine_weights(params, cfg) if engine else params, toks, caches


def uniform_program_hashes(case: str, mode: str) -> str:
    """sha256 of the bytes that one mode of ``models/gpt.py`` puts out for a
    uniform configuration of :data:`UNIFORM_CASES` at toy sizes (two layers
    of width 32, three slots of 16 rows), every input drawn from one fixed
    key: the loss and every gradient of ``gpt_forward``'s training loss;
    hidden states or logits and both caches of ``gpt_prefill``,
    ``gpt_prefill_chunk``, ``gpt_decode_fold`` (with its tokens and slot
    state), ``gpt_decode_verify`` and ``gpt_decode_step_paged``. Each leaf's
    shape and dtype go into the hash before its bytes. Two trees that give
    one hash compute, on this backend, the same numbers to the bit."""
    import hashlib

    import jax
    import jax.numpy as jnp

    from ray_lightning_tpu.models import gpt as G

    cfg, params, toks, caches = _uniform_inputs(case)
    B = toks.shape[0]
    pos, cur = jnp.asarray([3, 9, 12], jnp.int32), toks[:, 0]
    if mode == "forward_grad":
        module = G.GPTLM(config=cfg)
        out = jax.jit(jax.value_and_grad(lambda p: module.training_step(p, (toks,), None)[0]))(params)
    elif mode == "prefill":
        out = jax.jit(lambda p: G.gpt_prefill(p, cfg, toks[:2, :8]))(params)
    elif mode == "prefill_chunk":
        out = jax.jit(lambda p, k, v: G.gpt_prefill_chunk(p, cfg, toks[:1, :8], k, v, jnp.int32(5), jnp.int32(6)))(
            params, *caches["slot"])
    elif mode == "decode_fold":
        keys = jax.random.split(jax.random.PRNGKey(50), B)
        out = jax.jit(lambda p, k, v: G.gpt_decode_fold(
            p, cfg, cur, pos, keys, jnp.asarray([0.0, 0.8, 0.0]), jnp.asarray([0, 5, 0], jnp.int32),
            jnp.asarray([1.0, 0.9, 1.0]), jnp.asarray([True, True, False]), jnp.asarray([9, 2, 0], jnp.int32),
            jnp.full((B,), -1, jnp.int32), k, v, fold=3))(params, *caches["slots"])
    elif mode == "decode_verify":
        out = jax.jit(lambda p, k, v: G.gpt_decode_verify(p, cfg, toks[:, :4], pos, k, v))(params, *caches["slots"])
    elif mode == "decode_step_paged":
        table = 1 + jnp.arange(B * 4, dtype=jnp.int32).reshape(B, 4)
        out = jax.jit(lambda p, k, v: G.gpt_decode_step_paged(p, cfg, cur, pos, k, v, table, 4))(
            params, *caches["pages"])
    else:
        raise ValueError(f"unknown mode {mode!r}: one of {UNIFORM_MODES}")
    digest = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(out):
        a = np.asarray(leaf)
        digest.update(f"{a.shape}{a.dtype}".encode())
        digest.update(a.tobytes())
    return digest.hexdigest()
