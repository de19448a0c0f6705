"""Each plain reference against the system at a tiny size on the CPU; the
same comparison fails when the system side, or the reference put in its
place (the control), computes in a lower precision."""
import numpy as np
import pytest

from pb import reference, traffic, weights
from pb.spec import Spec

model_dims = Spec().dims  # the family is found by the configuration's model_type

GPT2 = {"model_type": "gpt2", "n_embd": 64, "n_layer": 2, "n_head": 4, "n_positions": 64, "vocab_size": 211}
MISTRAL = {
    "model_type": "mistral", "hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 4, "num_key_value_heads": 2,
    "intermediate_size": 96, "vocab_size": 211, "max_position_embeddings": 128, "sliding_window": 16,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
}
OPT = {"lr": 3e-4, "warmup_steps": 2, "weight_decay": 0.01, "decay_steps": 10000}


def _program_cfg(family, dtype):
    from ray_lightning_tpu.models.gpt import GPTConfig

    if family == "gpt2":
        return GPTConfig(vocab_size=211, n_layer=2, n_head=4, d_model=64, max_seq=64,
                         compute_dtype=dtype, attn_impl="reference")
    return GPTConfig.llama(vocab_size=211, n_layer=2, n_head=4, n_kv_head=2, d_model=64, d_ff=96, max_seq=64,
                           compute_dtype=dtype, attn_impl="reference", attn_window=16)


@pytest.mark.parametrize("family,cfg", [("gpt2", GPT2), ("mistral", MISTRAL)])
def test_forward_agrees_and_a_lower_precision_does_not(family, cfg):
    import jax.numpy as jnp

    from ray_lightning_tpu.models.gpt import gpt_forward

    dims = model_dims(cfg)
    params = weights.make_params(2**31 + 11, dims, 64, "float32")
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 211, (2, 64)), jnp.int32)
    ref = reference.logits_of(params, toks, dims)
    rel = lambda a: float(jnp.linalg.norm(a - ref) / jnp.linalg.norm(ref))  # noqa: E731
    # float32 on both sides: rounding only. The tolerance is float32's: 1e-5.
    assert rel(gpt_forward(params, toks, _program_cfg(family, "float32"))) < 1e-5
    # the system in bfloat16 is three orders worse, and so is the control in float8
    assert rel(gpt_forward(params, toks, _program_cfg(family, "bfloat16"))) > 1e-3
    assert rel(reference.logits_of(params, toks, dims, lowp=True)) > 1e-2


def test_decode_through_the_cache_agrees_with_the_full_forward():
    import jax.numpy as jnp

    from ray_lightning_tpu.models.gpt import gpt_generate

    dims = model_dims(MISTRAL)
    params = weights.make_params(5, dims, 64, "float32")
    prompt = np.random.default_rng(1).integers(0, 211, (1, 24)).astype(np.int32)
    out = np.asarray(gpt_generate(params, _program_cfg("mistral", "float32"), jnp.asarray(prompt), 12))[0]
    res = reference.serve_reference(
        params, [{"prompt": out[:24].tolist(), "tokens": out[24:].tolist()}], dims, pad_to=64, control=True)
    assert res["tokens_compared"] == 12
    assert res["widest_gap"] <= 1e-5  # greedy tokens of the program are the reference's first choice
    # the serve check catches a token that is not the model's: shift every id by one
    bad = reference.serve_reference(
        params, [{"prompt": out[:24].tolist(), "tokens": ((out[24:] + 1) % 211).tolist()}], dims, pad_to=64)
    assert bad["widest_gap"] > 0.1


def test_adamw_steps_agree_with_optax_and_the_control_fails():
    import jax
    import optax

    dims = model_dims(GPT2)
    params = weights.make_params(9, dims, 64, "float32")
    rows = traffic.fake_text(16, 64, 211, seed=9).reshape(4, 4, 65)
    ref = reference.train_reference(params, rows, dims, OPT, micro=2)
    # the same four steps by optax, the library the program uses
    sched = optax.warmup_cosine_decay_schedule(0.0, OPT["lr"], OPT["warmup_steps"], 10000)
    tx = optax.adamw(sched, weight_decay=OPT["weight_decay"])
    p, st, losses = params, tx.init(params), []
    for k in range(4):
        loss, g = jax.value_and_grad(reference.lm_loss)(p, rows[k], dims)
        up, st = tx.update(g, st, p)
        p = optax.apply_updates(p, up)
        losses.append(float(loss))
    assert np.allclose(losses, ref["losses"], rtol=0, atol=2e-6)
    from families import gpt2

    prog = {
        "mu_norms": reference.leaf_norms(st[0].mu),
        "delta_norms": reference.view_norms(jax.tree_util.tree_map(lambda a, b: a - b, p, params), gpt2.SPLIT),
    }
    # fused leaves are compared part by part, and the key bias, whose gradient is zero by the
    # mathematics, is the one leaf found to be noise
    assert {"blocks/bqkv.q", "blocks/bqkv.k", "blocks/bqkv.v", "blocks/wqkv.k"} <= set(ref["delta_norms"])
    assert reference.noise_leaves(ref["mu_view_norms"]) == ["blocks/bqkv.k"]
    assert reference.norm_gap(prog["mu_norms"], ref["mu_norms"])[0] < 1e-4
    assert reference.norm_gap(prog["delta_norms"], ref["delta_norms"])[0] < 1e-4
    # a step that returns its state unchanged: the change's norm is zero, the gap one
    frozen = {k: 0.0 for k in ref["delta_norms"]}
    assert reference.norm_gap(frozen, ref["delta_norms"])[0] == pytest.approx(1.0)
    # an optimizer at 0.7 of the stated learning rate: every leaf's change is 0.7 as long
    slow = {k: 0.7 * v for k, v in ref["delta_norms"].items()}
    assert reference.norm_gap(slow, ref["delta_norms"])[0] == pytest.approx(0.3)
    # the control: the reference in float8 in the program's place moves the gradient norms
    ctl = reference.train_reference(params, rows, dims, OPT, lowp=True, micro=2)
    assert reference.norm_gap(ctl["mu_norms"], ref["mu_norms"])[0] > 30 * reference.norm_gap(
        prog["mu_norms"], ref["mu_norms"])[0]


def test_lr_schedule_is_optaxs():
    import optax

    sched = optax.warmup_cosine_decay_schedule(0.0, 3e-4, 2, 10000)
    for c in (0, 1, 2, 3, 50, 9999, 20000):
        assert reference.lr_at(c, OPT) == pytest.approx(float(sched(c)), rel=1e-5, abs=1e-10)


def test_norm_gap_measures_small_leaves_against_the_median_leaf():
    ref = {"a": 1.0, "b": 2.0, "tiny": 1e-9}
    assert reference.norm_gap({"a": 1.0, "b": 2.0, "tiny": 2e-9}, ref)[0] == pytest.approx(1e-9)
    gap, name = reference.norm_gap({"a": 1.1, "b": 2.0, "tiny": 1e-9}, ref)
    assert name == "a" and gap == pytest.approx(0.1)
    assert reference.norm_gap({"a": 1.0}, ref)[0] == float("inf")


def test_noise_leaves_and_parts():
    import jax.numpy as jnp

    assert reference.noise_leaves({"a": 1.0, "b": 2.0, "c": 3.0, "k": 1e-6, "z": 0.0}) == ["k", "z"]
    assert reference.noise_leaves({"a": 1.0, "b": 0.5}) == []
    x = jnp.arange(24, dtype=jnp.float32).reshape(2, 3, 4)
    parts = reference.part_norms("w", x, {"w": (1, ("q", "k", "v"))})
    assert set(parts) == {"w.q", "w.k", "w.v"}
    assert parts["w.k"] == pytest.approx(float(jnp.linalg.norm(x[:, 1])))
    assert sum(v * v for v in parts.values()) == pytest.approx(float(jnp.sum(x * x)))
    assert reference.part_norms("w", x, {}) == {"w": pytest.approx(float(jnp.linalg.norm(x)))}
    # a leaf that is left out cannot be the worst
    ref = {"a": 1.0, "b": 2.0, "k": 1.0}
    assert reference.norm_gap({"a": 1.0, "b": 2.0, "k": 1.5}, ref)[1] == "k"
    assert reference.norm_gap({"a": 1.0, "b": 2.0, "k": 1.5}, ref, skip=("k",))[0] == 0.0


def test_seeded_weights_are_reproducible_and_fill_every_leaf():
    import jax

    dims = model_dims(MISTRAL)
    a = weights.make_params(2**31 + 3, dims, 64, "bfloat16")
    b = weights.make_params(2**31 + 3, dims, 64, "bfloat16")
    c = weights.make_params(3, dims, 64, "bfloat16")
    la, lb, lc = (jax.tree_util.tree_leaves(t) for t in (a, b, c))
    assert all(str(x.dtype) == "bfloat16" for x in la)
    assert all(bool((x == y).all()) for x, y in zip(la, lb))
    assert not all(bool((x == y).all()) for x, y in zip(la, lc))
    assert set(a) == {"wte", "lm_head", "lnf_g", "lnf_b", "blocks"} and "wkv" in a["blocks"]
