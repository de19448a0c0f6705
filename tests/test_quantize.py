"""Weight-only int8 quantization: roundtrip bounds, path equality (the
quantized forward/decode must equal dequantize-then-compute EXACTLY),
and end-to-end decode on GPT-2 and Llama variants."""
import dataclasses

import numpy as np
import pytest

from ray_lightning_tpu.models.gpt import (
    GPTConfig,
    gpt_forward,
    gpt_generate,
    init_gpt_params,
)
from ray_lightning_tpu.utils.quantize import (
    dequantize_params,
    is_quantized,
    quantize_params_int8,
    quantize_tensor,
)
from tests.test_gpt import TINY


def test_quantize_tensor_roundtrip_bound():
    """Symmetric per-channel int8: |w - dequant(q)| <= s/2 everywhere,
    and all-zero channels stay zero."""
    import jax
    import jax.numpy as jnp

    w = np.array(
        jax.random.normal(jax.random.PRNGKey(0), (32, 3, 8)) * 0.05
    )
    w[:, 1, 2] = 0.0  # a dead output channel
    node = quantize_tensor(jnp.asarray(w), (0,))
    assert node["q"].dtype == jnp.int8
    deq = np.asarray(node["q"], np.float32) * np.asarray(node["s"])
    err = np.abs(deq - w)
    bound = np.asarray(node["s"]) / 2 + 1e-8
    assert (err <= bound).all()
    assert (deq[:, 1, 2] == 0).all()


def _tree_keys(d, prefix=""):
    for k, v in d.items():
        if is_quantized(v):
            yield prefix + k
        elif isinstance(v, dict):
            yield from _tree_keys(v, prefix + k + ".")


@pytest.mark.parametrize(
    "cfg",
    [
        TINY,
        dataclasses.replace(
            GPTConfig.llama(
                vocab_size=64, n_layer=2, n_head=4, n_kv_head=2,
                d_model=32, d_ff=48, max_seq=32,
            ),
            attn_impl="reference",
        ),
    ],
    ids=["gpt2-tied", "llama-gqa-untied"],
)
def test_quantized_path_equals_dequantized_oracle(cfg):
    """The in-graph dequant path must produce EXACTLY what running the
    dequantized fp32 tree produces — quantization error lives in the
    weights, never in the consuming code path."""
    import jax

    params = init_gpt_params(jax.random.PRNGKey(0), cfg)
    qparams = quantize_params_int8(params)
    quantized = set(_tree_keys(qparams))
    assert "wte" in quantized and "blocks.wo2" in quantized
    if not cfg.tie_word_embeddings:
        assert "lm_head" in quantized

    toks = np.asarray(
        jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)
    )
    oracle = gpt_forward(dequantize_params(qparams), toks, cfg)
    out = gpt_forward(qparams, toks, cfg)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(oracle), atol=1e-6
    )
    # And the error vs the ORIGINAL weights is small but nonzero (the
    # quantization is real).
    ref = np.asarray(gpt_forward(params, toks, cfg))
    rel = np.abs(np.asarray(out) - ref).max() / np.abs(ref).max()
    assert 0 < rel < 0.05, rel


@pytest.mark.parametrize(
    "cfg",
    [
        TINY,
        dataclasses.replace(
            GPTConfig.llama(
                vocab_size=64, n_layer=2, n_head=4, n_kv_head=2,
                d_model=32, d_ff=48, max_seq=32,
            ),
            attn_impl="reference",
        ),
    ],
    ids=["gpt2-fused", "llama-gqa"],
)
@pytest.mark.parametrize("weights", ["stored", "engine"])
def test_quantized_decode_matches_quantized_forward(cfg, weights):
    """Greedy decode from the quantized tree (prefill + cached scan)
    agrees with argmax over the quantized parallel forward — the decode
    consumers (embedding gather, fused AND grouped qkv, wo/mlp/head
    dequants) all line up. ``engine``: the decode runs on the tree an
    engine makes of the quantized one (``engine_weights``: int8 nodes
    re-form with their scales; a fused gelu tree comes back as it is)."""
    import jax
    import jax.numpy as jnp

    from ray_lightning_tpu.models.gpt import engine_weights

    params = quantize_params_int8(init_gpt_params(jax.random.PRNGKey(3), cfg))
    held = params
    if weights == "engine":
        held = engine_weights(params, cfg)
        if cfg.mlp_variant == "swiglu":
            gate, wq = held["blocks"]["wi_gate"], held["blocks"]["wq"]
            assert is_quantized(gate) and is_quantized(wq) and "wi" not in held["blocks"]
            assert gate["q"].shape == (2, 32, 48) and gate["s"].shape == (2, 1, 48)
            assert wq["q"].shape == (2, 32, 32) and wq["s"].shape == (2, 1, 32)
        else:
            assert held["blocks"].keys() == params["blocks"].keys()
    prompt = np.asarray([[5, 2, 7, 1]], np.int32)
    out = np.asarray(
        gpt_generate(held, cfg, jnp.asarray(prompt), max_new_tokens=6)
    )
    assert out.shape == (1, 10)
    for p in range(3, 9):
        logits = gpt_forward(params, out[:, : p + 1], cfg)
        np.testing.assert_array_equal(
            np.argmax(np.asarray(logits[:, -1]), -1), out[:, p + 1]
        )


def test_quantized_chunked_loss_and_zigzag_embedding():
    """The fused chunked head accepts a quantized table, and the
    sequence-parallel (zigzag) embedding path gathers int8 rows."""
    import jax
    import jax.numpy as jnp

    from ray_lightning_tpu.models.gpt import chunked_lm_loss, lm_loss
    from tests.test_gpt import make_inprocess

    params = quantize_params_int8(init_gpt_params(jax.random.PRNGKey(0), TINY))
    toks = np.asarray(
        jax.random.randint(jax.random.PRNGKey(1), (2, 17), 0, TINY.vocab_size)
    )
    hidden = gpt_forward(params, toks[:, :-1], TINY, return_hidden=True)
    loss_c, acc_c = chunked_lm_loss(
        hidden, params["wte"], jnp.asarray(toks[:, 1:]), 4
    )
    logits = gpt_forward(params, toks[:, :-1], TINY)
    loss_d, acc_d = lm_loss(logits, jnp.asarray(toks[:, 1:]))
    np.testing.assert_allclose(float(loss_c), float(loss_d), rtol=2e-4)

    cfg = dataclasses.replace(TINY, seq_impl="zigzag")
    strategy = make_inprocess({"data": 2, "seq": 4}, sequence_parallel=True)
    module_dense = gpt_forward(
        params, toks[:, :-1], cfg, mesh=strategy.mesh, seq_axis="seq"
    )
    np.testing.assert_allclose(
        np.asarray(module_dense), np.asarray(logits), atol=1e-4
    )


def test_quantize_moe_keeps_experts_fp32():
    import jax

    cfg = dataclasses.replace(TINY, n_experts=4, d_ff=32)
    params = init_gpt_params(jax.random.PRNGKey(0), cfg)
    qparams = quantize_params_int8(params)
    assert not is_quantized(qparams["blocks"]["wi"])
    assert not is_quantized(qparams["blocks"]["router"])
    assert is_quantized(qparams["blocks"]["wqkv"])
    toks = np.zeros((2, 8), np.int32)
    out = gpt_forward(qparams, toks, cfg)
    assert np.isfinite(np.asarray(out)).all()
