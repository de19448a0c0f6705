"""A configuration with latent attention (``GPTConfig.layer_types`` kind
"latent", models/mixed.py:_latent_part): how it is described, the rotation
of neighbouring pairs, the modes that refuse it by name with the kind's
own reason, and what the replica says about it. The comparison with the
plain reference is ``tests/perfbench/test_deepseek_v3.py``."""
import time

import numpy as np
import pytest
from test_mixed_layers import _call, _engine, _int8, _mesh2

from ray_lightning_tpu.models import layers
from ray_lightning_tpu.models.gpt import GPTConfig, init_gpt_params

LATENT = dict(
    vocab_size=96, n_layer=3, n_head=4, d_model=32, d_ff=64, d_ff_expert=16, d_ff_shared=32, max_seq=64,
    qk_head_dim=12, v_head_dim=8, rope_dim=4, rope_interleave=True, kv_lora_rank=16, rope_theta=1e6,
    pos_embed="rope", norm_impl="rmsnorm", norm_eps=1e-6, mlp_variant="swiglu", tie_word_embeddings=False,
    layer_types=[["latent", "dense"], ["latent", "experts"], ["latent", "experts"]],
    n_experts=16, moe_top_k=3, moe_scoring="sigmoid", moe_routed_scale=2.448, experts_held=[4, 8],
)


@pytest.fixture(scope="module")
def cfg():
    return GPTConfig(**LATENT)


@pytest.fixture(scope="module")
def params(cfg):
    import jax

    return init_gpt_params(jax.random.PRNGKey(0), cfg)


def test_the_kind_is_described_by_two_fields_and_the_tree_follows(cfg, params):
    from ray_lightning_tpu.models.mixed import ATTN_KINDS, KV_KINDS, empty_caches, mixed_param_shapes

    assert "latent" in ATTN_KINDS and "latent" not in KV_KINDS and cfg.mixed
    assert hash(cfg) == hash(GPTConfig(**LATENT))
    shapes = mixed_param_shapes(cfg)["blocks"]
    # q at the whole q·k head; the stream -> [latent 16; rotary key 4]; the latent -> each head's [keys 8; values 8]
    assert shapes["lat_wq"] == (3, 32, 4, 12) and shapes["lat_wkv_a"] == (3, 32, 20) and shapes["lat_kv_g"] == (3, 16)
    assert shapes["lat_wkv_b"] == (3, 4, 16, 16) and shapes["lat_wo"] == (3, 4, 8, 32)
    assert not any(k.startswith(("full_", "swa_")) for k in shapes)
    assert {k: tuple(v.shape) for k, v in params["blocks"].items()} == shapes
    k, v = empty_caches(cfg, 5, 64, np.float32)
    # 16 + 4 values a position and layer, where K and V of 4 heads would be 4 x (12 + 8)
    assert k["latent"].shape == (3, 5, 64, 16) and v["latent"].shape == (3, 5, 64, 4)


@pytest.mark.parametrize("change,says", [
    (dict(kv_lora_rank=0), "latent layers need kv_lora_rank >= 1"),
    (dict(rope_dim=12), "0 < rope_dim < qk_head_dim"),
    (dict(rope_dim=0), "0 < rope_dim < qk_head_dim"),
    (dict(pos_embed="none"), "needs state layers"),
    (dict(attn_sink_logit=["latent"]), "attn_sink_logit names 'latent'"),
    (dict(layer_types=[["full", "dense"], ["full", "experts"], ["full", "experts"]]), "kv_lora_rank describes latent layers"),
    (dict(layer_types=[["latent", "dense"], ["latents", "experts"], ["latent", "experts"]]), "layer_types entry"),
])
def test_a_latent_configuration_that_cannot_run_says_what_is_wrong(change, says):
    with pytest.raises(ValueError, match=says):
        GPTConfig(**dict(LATENT, **change)).validate_variants()


def test_the_latent_and_the_pairing_need_layer_types():
    for field in (dict(kv_lora_rank=16), dict(rope_interleave=True)):
        with pytest.raises(ValueError, match="need layer_types"):
            GPTConfig(pos_embed="rope", **field).validate_variants()


def test_neighbouring_pairs_turn_as_written_and_come_out_half_split():
    """``_rope(interleave=True)`` against the rule written out: pair i is
    dims (2i, 2i + 1), turned by ``pos * theta^(-2i / d)``; what comes out
    is every pair's first member, then every second, and the dims past the
    rotary width pass."""
    import jax
    import jax.numpy as jnp

    from ray_lightning_tpu.models.layers import _rope, _rope_tables

    x = jax.random.normal(jax.random.PRNGKey(2), (2, 9, 3, 10), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(9)[None], (2, 9))
    got = np.asarray(_rope(x, _rope_tables(pos, 1e4, 8), interleave=True))
    want = np.array(x)
    for i in range(4):
        ang = np.arange(9)[None, :, None] * 1e4 ** (-2 * i / 8)
        a, b = np.asarray(x[..., 2 * i]), np.asarray(x[..., 2 * i + 1])
        want[..., i], want[..., 4 + i] = a * np.cos(ang) - b * np.sin(ang), a * np.sin(ang) + b * np.cos(ang)
    assert np.abs(got - want).max() < 1e-5
    # half-split pairs are another rotation, not this one in another order
    assert np.abs(np.sort(np.asarray(_rope(x, _rope_tables(pos, 1e4, 8))), -1) - np.sort(got, -1)).max() > 1e-2


# -- the modes that refuse, with the kind's own reason ---------------------------------------
@pytest.mark.parametrize("name,kw", [
    ("paged KV cache", dict(kv_pages=16, kv_page=16)),
    ("prefix pool", dict(prefix_blocks=4)),
    ("KV store", dict(kvstore_dir="/nonexistent")),
    ("chunked prefill", dict(prefill_chunk=16)),
    ("piggybacked prefill chunks", dict(piggyback_chunks=1)),
    ("speculative decoding", dict(spec="ngram")),
    ("serve mesh of more than one device", dict(mesh=_mesh2)),
    ("int8 weights", dict(params=_int8)),
])
def test_the_engine_refuses_each_mode_with_the_latent_rows_reason(params, cfg, name, kw):
    kw = {k: (v() if k == "mesh" else v) for k, v in kw.items()}
    p = kw.pop("params", lambda x: x)(params)
    with pytest.raises(ValueError, match=f"{name}.*does not run.*a latent layer keeps one row of kv_lora_rank"):
        _engine(p, cfg, **kw)


@pytest.mark.parametrize("fn_name,says", [
    ("gpt_prefill_chunk", "chunked prefill"), ("_piggyback_prefill", "piggybacked prefill chunks"),
    ("gpt_decode_verify", "speculative decoding"), ("gpt_decode_step_paged", "paged KV cache"),
    ("gpt_decode_verify_paged", "paged KV cache"), ("gpt_prefill_chunk_paged", "paged KV cache"),
    ("gpt_decode_fold_spec", "speculative decoding"), ("gpt_decode_fold paged", "paged KV cache"),
    ("gpt_generate", "gpt_generate"), ("gpt_logical_axes", "sharded parameter tree"),
])
def test_each_restatement_of_the_block_refuses_a_latent_layer_by_name(params, cfg, fn_name, says):
    with pytest.raises(ValueError, match=f"{says}.*no K / V pair: the page, pool, export and wire formats"):
        _call(fn_name, params, cfg)()


def test_a_configuration_without_latent_layers_is_refused_as_before():
    from ray_lightning_tpu.models.mixed import refuse_mixed

    plain = GPTConfig(**dict(LATENT, kv_lora_rank=0, layer_types=[["full", "dense"], ["full", "experts"], ["full", "experts"]]))
    with pytest.raises(ValueError) as e:
        refuse_mixed(plain, "chunked prefill (prefill_chunk)")
    assert "latent" not in str(e.value) and "decode fold only" in str(e.value)


# -- what the replica says -----------------------------------------------------------
def test_the_replica_serves_it_and_reports_the_latent_rows(params, monkeypatch):
    from ray_lightning_tpu.obs import registry
    from ray_lightning_tpu.serve.server import ServeReplica

    own = registry.MetricsRegistry()  # see tests/test_state_layers.py: exact totals are read out of it
    monkeypatch.setattr(registry, "get_registry", lambda: own)
    rep = ServeReplica(params=params, model_config=dict(LATENT), num_slots=3, max_seq=64,
                       prefill_buckets=[4, 16], decode_fold=4, watchdog=False)
    try:
        rng = np.random.default_rng(1)
        sizes = (10, 3, 12, 2)
        rids = [rep.submit(rng.integers(0, 96, size=n).tolist(), max_new_tokens=20) for n in sizes]
        deadline = time.monotonic() + 120
        for rid in rids:
            while not rep.result(rid, wait_s=0.2)["done"]:
                assert time.monotonic() < deadline, "request did not finish"
        st = rep.stats()
        attn, moe, cache = st["attn"], st["moe"], st["cache"]
        assert moe["expert_layers"] == 2 and moe["decode"]["pairs_routed"] == 4 * 19 * 2 * 3
        # each of a request's 19 decode steps saw its prompt and what was generated before, in each of 3 layers
        assert attn["rows_live"] == 3 * sum(n + j for n in sizes for j in range(1, 20))
        assert attn["rows_visited"] == attn["rows_allocated"] and attn["rows_allocated"] % (3 * 3 * 64 * 4) == 0
        assert cache == {"latent": {"layers": 3, "rows_per_slot": 64, "bytes": 3 * 3 * 64 * (16 + 4) * 4, "row_layout": True}}
        assert st["memory"]["kv_cache"]["bytes"] == cache["latent"]["bytes"]
        assert st["compiles_since_init"] == 0
        text = rep.metrics_text()
        assert f'rlt_serve_attn_rows_live_total {attn["rows_live"]}\n' in text
        assert f'rlt_serve_attn_rows_allocated_total {attn["rows_allocated"]}\n' in text
        assert f'rlt_serve_kv_bytes{{kind="latent"}} {cache["latent"]["bytes"]}\n' in text
    finally:
        rep.stop()


# -- the decode kernel under the latent layers (ops/decode_attention.py) ---------------------------------
#: LATENT at widths Mosaic takes: latents of 128, rotary keys of 64 (half a lane tile), three blocks of 128 positions a slot
KERNEL = dict(LATENT, kv_lora_rank=128, rope_dim=64, qk_head_dim=72, max_seq=384)


def test_a_decode_step_through_the_kernel_gives_the_xla_steps_logits(monkeypatch):
    """The whole token step (projections, rotary, the two cache writes, the
    read, the experts): logits of the live slots and both caches, to the
    tolerance of the GPT twin (tests/test_decode_attention.py)."""
    import jax
    import jax.numpy as jnp

    from ray_lightning_tpu.models import gpt as G
    from ray_lightning_tpu.models.mixed import empty_caches
    from tests.utils import force_decode_kernel

    cfg = GPTConfig(**dict(KERNEL, compute_dtype="float32"))
    params = init_gpt_params(jax.random.PRNGKey(1), cfg)
    B = 3
    ks = jax.random.split(jax.random.PRNGKey(2), 2)
    kc, vc = empty_caches(cfg, B, 384, jnp.float32)  # three blocks of 128: 256 does not divide it
    kc = {"latent": 0.3 * jax.random.normal(ks[0], kc["latent"].shape, jnp.float32)}
    vc = {"latent": 0.3 * jax.random.normal(ks[1], vc["latent"].shape, jnp.float32)}
    cur = jnp.asarray([5, 17, 44], jnp.int32)
    pos = jnp.asarray([128, 383, 9], jnp.int32)  # a block's first row, the cache's last, and a slot that is not live
    active = jnp.asarray([True, True, False])
    want = G.gpt_decode_step(params, cfg, cur, pos, kc, vc, active)
    assert layers.decode_rows_block(cfg, 1, kc, vc, "latent") == 0
    force_decode_kernel(monkeypatch)
    assert layers.decode_rows_block(cfg, 1, kc, vc, "latent") == 128
    got = G.gpt_decode_step(params, cfg, cur, pos, kc, vc, active)
    np.testing.assert_allclose(np.asarray(got[0])[:2], np.asarray(want[0])[:2], atol=2e-4, rtol=0)
    for a, b in zip(got[1:], want[1:]):
        # layer 0's write is the same; the later layers' differ by the read's rounding, in the live slots' positions
        np.testing.assert_allclose(np.asarray(a["latent"])[:, :2], np.asarray(b["latent"])[:, :2], atol=2e-4, rtol=0)


@pytest.mark.parametrize("read", ["xla", "kernel"])
def test_the_replicas_attn_counter_says_which_read_the_fold_takes(read, monkeypatch):
    """``stats()["attn"]``: under the kernel ``rows_visited`` is the blocks
    up to each live slot's position, under the XLA read every allocated
    row. The engine asks the selection the fold asks."""
    import jax

    from ray_lightning_tpu.obs import registry
    from ray_lightning_tpu.serve.server import ServeReplica
    from tests.utils import force_decode_kernel

    own = registry.MetricsRegistry()
    monkeypatch.setattr(registry, "get_registry", lambda: own)
    if read == "kernel":
        force_decode_kernel(monkeypatch)
    params = init_gpt_params(jax.random.PRNGKey(0), GPTConfig(**KERNEL))
    rep = ServeReplica(params=params, model_config=dict(KERNEL), num_slots=3, max_seq=384,
                       prefill_buckets=[16, 128], decode_fold=4, watchdog=False)
    try:
        rng = np.random.default_rng(1)
        sizes = (10, 120, 3)  # the second crosses the first block's end while it decodes
        rids = [rep.submit(rng.integers(0, 96, size=n).tolist(), max_new_tokens=20) for n in sizes]
        deadline = time.monotonic() + 240
        for rid in rids:
            while not rep.result(rid, wait_s=0.2)["done"]:
                assert time.monotonic() < deadline, "request did not finish"
        attn = rep.stats()["attn"]
        steps = [n + j for n in sizes for j in range(1, 20)]  # rows 0 .. pos each decode step's query saw
        assert attn["rows_live"] == 3 * sum(steps)
        assert attn["rows_allocated"] % (3 * 3 * 384 * 4) == 0 and attn["rows_allocated"] > 0
        if read == "kernel":
            assert attn["rows_visited"] == 3 * sum(-(-rows // 128) * 128 for rows in steps)
            assert attn["rows_live"] < attn["rows_visited"] < attn["rows_allocated"]
        else:
            assert attn["rows_visited"] == attn["rows_allocated"]
        assert rep.stats()["compiles_since_init"] == 0
    finally:
        rep.stop()
