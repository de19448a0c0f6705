"""A configuration with state layers and layers that are one part alone
(``GPTConfig.layer_types`` with "ssm" and None, models/ssm.py): how it is
described, the pieces of the state layer against a position-by-position
evaluation, the expert layer at another width than the residual's, the
modes that refuse it by name with the state's own reason, and what the
replica says about it. The comparison with the plain reference is
``tests/perfbench/test_nemotron_h.py``."""
import time

import numpy as np
import pytest

from ray_lightning_tpu.models.gpt import GPTConfig, init_gpt_params

STATE = dict(
    vocab_size=96, n_layer=5, n_head=4, n_kv_head=2, d_model=32, d_ff_expert=24, max_seq=64,
    pos_embed="none", norm_impl="rmsnorm", mlp_variant="relu2", tie_word_embeddings=False,
    layer_types=[["ssm", None], [None, "experts"], ["ssm", None], ["full", None], [None, "experts"]],
    ssm_heads=8, ssm_head_dim=4, ssm_groups=2, ssm_state=16, ssm_conv=4, ssm_chunk=8,
    n_experts=16, moe_top_k=3, moe_scoring="sigmoid", experts_held=[4, 8],
    moe_latent_dim=16, d_ff_shared=48, moe_routed_scale=2.5,
)


@pytest.fixture(scope="module")
def cfg():
    return GPTConfig(**STATE)


@pytest.fixture(scope="module")
def params(cfg):
    import jax

    return init_gpt_params(jax.random.PRNGKey(0), cfg)


def test_a_layer_may_be_one_part_alone_and_the_tree_follows(cfg, params):
    from ray_lightning_tpu.models.mixed import layer_specs, mixed_param_shapes

    assert cfg.layer_types[0] == ("ssm", None) and cfg.layer_types[1] == (None, "experts") and cfg.mixed
    assert hash(cfg) == hash(GPTConfig(**STATE))
    specs = layer_specs(cfg)
    assert [(s.mixer_index, s.mlp_index, s.norm1_index, s.norm2_index) for s in specs] == [
        (0, 0, 0, 0), (0, 0, 1, 0), (1, 0, 1, 1), (0, 0, 2, 1), (0, 1, 3, 1)]
    shapes = mixed_param_shapes(cfg)["blocks"]
    assert shapes["ln1_g"] == (3, 32) and shapes["ln2_g"] == (2, 32)  # one norm a part
    assert shapes["ssm_wx"] == (2, 32, 32 + 2 * 2 * 16) and shapes["ssm_conv_w"] == (2, 4, 96)
    assert shapes["moe_wi"] == (2, 8, 1, 16, 24) and shapes["moe_wo2"] == (2, 8, 24, 16)  # in the latent, no gate
    assert shapes["moe_shared_wi"] == (2, 1, 32, 48) and shapes["moe_latent_up"] == (2, 16, 32)
    assert {k: tuple(v.shape) for k, v in params["blocks"].items()} == shapes
    assert "dense_wi" not in shapes and "swa_wq" not in shapes


@pytest.mark.parametrize("change,says", [
    (dict(layer_types=[["ssm", None], [None, None], ["ssm", None], ["full", None], [None, "experts"]]), "layer_types entry"),
    (dict(layer_types=[["conv", None], [None, "experts"], ["ssm", None], ["full", None], [None, "experts"]]), "layer_types entry"),
    (dict(ssm_heads=7), "divisible by ssm_groups"),
    (dict(ssm_state=0), "state layers need"),
    (dict(ssm_conv=1), "ssm_conv >= 2"),
    (dict(pos_embed="learned"), "rotary or no positions"),
    (dict(mlp_variant="gelu"), "SwiGLU or relu2"),
    (dict(moe_latent_dim=-1), "moe_latent_dim"),
    (dict(layer_types=[["full", None]] * 5, n_experts=0, moe_latent_dim=0, d_ff_shared=0, moe_routed_scale=1.0,
          experts_held=[]), "pos_embed='none' needs state layers"),
    (dict(layer_types=[["ssm", None]] * 5, n_experts=0, experts_held=[]), "describe expert layers"),
])
def test_a_configuration_that_cannot_run_says_what_is_wrong(change, says):
    with pytest.raises(ValueError, match=says):
        GPTConfig(**dict(STATE, **change)).validate_variants()


def test_relu2_and_no_positions_need_layer_types():
    with pytest.raises(ValueError, match="relu2"):
        GPTConfig(mlp_variant="relu2").validate_variants()


# -- the state layer's two evaluations -------------------------------------------------
def _layer(params, i=0):
    return {k[len("ssm_"):]: v[i] for k, v in params["blocks"].items() if k.startswith("ssm_")}


def test_rows_in_chunks_are_the_steps_one_at_a_time_and_padding_leaves_the_state(cfg, params):
    """``ssm_rows`` over 21 rows (two whole chunks of 8 and a part) against
    ``ssm_step`` fed the same rows one by one from an empty state: the
    outputs, the state and the conv tail agree; right-padded to 32 rows
    with ``valid`` marking 21, state and tail are those after row 21."""
    import jax
    import jax.numpy as jnp

    from ray_lightning_tpu.models import ssm

    lp = _layer(params)
    # leaves that the program's own initialisation leaves at one and zero, moved off them
    lp = dict(lp, A_log=lp["A_log"] + 0.3, dt_bias=lp["dt_bias"] - 0.2, D=lp["D"] * 0.7,
              conv_w=lp["conv_w"] * 30.0, conv_b=lp["conv_b"] + 0.1)
    u = jax.random.normal(jax.random.PRNGKey(1), (2, 21, 32), jnp.float32)
    out, state, tail = ssm.ssm_rows(u, lp, cfg)
    s, t = ssm.empty_state(cfg, 2, jnp.float32)
    outs = []
    for i in range(21):
        o, s, t = ssm.ssm_step(u[:, i:i + 1], lp, cfg, s, t)
        outs.append(o)
    one_by_one = jnp.concatenate(outs, axis=1)
    assert float(jnp.abs(out - one_by_one).max()) < 1e-5 * float(jnp.abs(one_by_one).max())
    assert float(jnp.abs(state - s).max()) < 1e-5 * float(jnp.abs(s).max())
    assert float(jnp.abs(tail - t).max()) == 0.0
    padded = jnp.concatenate([u, jax.random.normal(jax.random.PRNGKey(2), (2, 11, 32), jnp.float32)], axis=1)
    valid = jnp.arange(32)[None, :] < jnp.asarray([21, 21])[:, None]
    out_p, state_p, tail_p = ssm.ssm_rows(padded, lp, cfg, valid)
    assert float(jnp.abs(out_p[:, :21] - out).max()) < 1e-6
    assert float(jnp.abs(state_p - state).max()) < 1e-6 * float(jnp.abs(state).max())
    assert float(jnp.abs(tail_p - tail).max()) < 1e-6  # a matmul over 32 rows and over 21: another blocking
    blind = ssm.ssm_rows(padded, lp, cfg)  # what the padding would do if it were taken for prompt
    assert float(jnp.abs(blind[1] - state).max()) > 1e-3 * float(jnp.abs(state).max())


def test_a_prompt_shorter_than_the_conv_leaves_zeros_in_the_tail(cfg, params):
    import jax
    import jax.numpy as jnp

    from ray_lightning_tpu.models import ssm

    u = jax.random.normal(jax.random.PRNGKey(3), (1, 8, 32), jnp.float32)
    _, _, tail = ssm.ssm_rows(u, _layer(params), cfg, jnp.arange(8)[None, :] < 2)
    assert tail.shape == (3, 1, 96)
    assert float(jnp.abs(tail[0]).max()) == 0.0 and float(jnp.abs(tail[1:]).min()) > 0.0


# -- the expert layer at another width, and the MLP's kinds ---------------------------------
def test_held_experts_in_a_latent_with_a_scale_against_every_expert_over_every_token():
    import jax
    import jax.numpy as jnp

    from ray_lightning_tpu.parallel.moe import moe_ffn_held, route_top_k

    ks = jax.random.split(jax.random.PRNGKey(7), 6)
    T, D, Dl, F, E, held = 40, 32, 12, 20, 16, (4, 8)
    x, a = jax.random.normal(ks[0], (T, D)), jax.random.normal(ks[1], (T, Dl))
    p = {"router": 0.3 * jax.random.normal(ks[2], (D, E)), "router_bias": 0.05 * jax.random.normal(ks[3], (E,)),
         "wi": 0.3 * jax.random.normal(ks[4], (held[1], 1, Dl, F)), "wo": 0.3 * jax.random.normal(ks[5], (held[1], F, Dl))}
    out, stats = moe_ffn_held(p, x, held=held, top_k=3, scoring="sigmoid", expert_in=a, variant="relu2", scale=2.5)
    gates, experts = route_top_k(x, p["router"], p["router_bias"], 3, "sigmoid", 2.5)
    assert np.allclose(np.asarray(gates.sum(-1)), 2.5, atol=1e-5)  # normalised over all three, then the scale
    want = jnp.zeros((T, Dl))
    for j in range(held[1]):
        w = jnp.sum(jnp.where(experts == held[0] + j, gates, 0.0), -1)
        want = want + w[:, None] * (jnp.square(jax.nn.relu(a @ p["wi"][j, 0])) @ p["wo"][j])
    assert out.shape == (T, Dl) and float(jnp.abs(out - want).max()) < 1e-5
    assert int(stats[0]) == T * 3 and 0 < int(stats[1]) < T * 3
    with pytest.raises(ValueError, match="takes 2 input matrices"):
        moe_ffn_held(p, x, held=held, top_k=3, scoring="sigmoid", expert_in=a)  # SwiGLU over a relu2 tree


# -- the modes that refuse, with the state's own reason -------------------------------------
def _engine(params, cfg, **kw):
    from ray_lightning_tpu.serve.engine import DecodeEngine

    return DecodeEngine(params, cfg, num_slots=2, max_seq=64, prefill_buckets=[16], **kw)


@pytest.mark.parametrize("name,kw", [
    ("paged KV cache", dict(kv_pages=16, kv_page=16)),
    ("prefix pool", dict(prefix_blocks=4)),
    ("KV store", dict(kvstore_dir="/nonexistent")),
    ("chunked prefill", dict(prefill_chunk=16)),
    ("piggybacked prefill chunks", dict(piggyback_chunks=1)),
    ("speculative decoding", dict(spec="ngram")),
])
def test_the_engine_refuses_each_mode_that_would_need_a_snapshot_of_the_state(params, cfg, name, kw):
    with pytest.raises(ValueError, match=f"{name}.*does not run.*would need a snapshot of the state"):
        _engine(params, cfg, **kw)


@pytest.mark.parametrize("fn_name,says", [
    ("gpt_prefill_chunk", "chunked prefill"), ("gpt_decode_verify", "speculative decoding"),
    ("gpt_generate", "gpt_generate"),
])
def test_each_restatement_of_the_block_refuses_a_state_layer_by_name(params, cfg, fn_name, says):
    import jax.numpy as jnp

    from ray_lightning_tpu.models import gpt
    from ray_lightning_tpu.models.mixed import empty_caches

    k, v = empty_caches(cfg, 1, 64, jnp.float32)
    tok, z = jnp.zeros((1, 4), jnp.int32), jnp.zeros((1,), jnp.int32)
    call = {
        "gpt_prefill_chunk": lambda: gpt.gpt_prefill_chunk(params, cfg, tok, k, v, 0),
        "gpt_decode_verify": lambda: gpt.gpt_decode_verify(params, cfg, tok, z, k, v),
        "gpt_generate": lambda: gpt.gpt_generate(params, cfg, tok, 4),
    }[fn_name]
    with pytest.raises(ValueError, match=f"{says}.*a state layer keeps one running state a request"):
        call()


def test_a_configuration_without_state_layers_is_refused_as_before():
    from ray_lightning_tpu.models.mixed import refuse_mixed

    cfg = GPTConfig(**dict(STATE, pos_embed="rope", layer_types=[["full", None], [None, "experts"]] * 2 + [["full", None]]))
    with pytest.raises(ValueError) as e:
        refuse_mixed(cfg, "chunked prefill (prefill_chunk)")
    assert "snapshot" not in str(e.value) and "decode fold only" in str(e.value)


# -- what the replica says -----------------------------------------------------------
@pytest.mark.parametrize("update", ["xla", "kernel"])
def test_the_replica_serves_it_and_reports_the_state_beside_the_cache(update, monkeypatch):
    """``update`` "kernel": the selection function is told a TPU, so the
    state's update is the kernel that walks the live slots (interpreted
    here) wherever the state is wide enough for it — a state of 128, where
    the configuration above keeps 16: the counts below do not depend on it
    but for the bytes and ``slot_steps_visited``."""
    import functools

    import jax

    from ray_lightning_tpu.models import ssm as ssm_mod
    from ray_lightning_tpu.obs import registry
    from ray_lightning_tpu.serve.server import ServeReplica

    # a registry of this test's own: the process's is shared with every other replica a worker builds, and
    # tests/test_mixed_layers.py reads exact expert-layer totals out of it
    own = registry.MetricsRegistry()
    monkeypatch.setattr(registry, "get_registry", lambda: own)
    N = 16
    if update == "kernel":
        N = 128
        monkeypatch.setattr(ssm_mod, "_step_heads", functools.partial(ssm_mod._step_heads, backend="tpu"))
    wide = dict(STATE, ssm_state=N, ssm_head_dim=8)
    assert ssm_mod._step_heads(jax.ShapeDtypeStruct((3, 8, 8, N), "float32"), 2) == (4 if update == "kernel" else 0)
    params = init_gpt_params(jax.random.PRNGKey(0), GPTConfig(**wide))
    rep = ServeReplica(params=params, model_config=wide, num_slots=3, max_seq=64,
                       prefill_buckets=[4, 16], decode_fold=4, watchdog=False)
    try:
        rng = np.random.default_rng(1)
        rids = [rep.submit(rng.integers(0, 96, size=n).tolist(), max_new_tokens=20) for n in (10, 3, 12, 2)]
        deadline = time.monotonic() + 120
        for rid in rids:
            while not rep.result(rid, wait_s=0.2)["done"]:
                assert time.monotonic() < deadline, "request did not finish"
        st = rep.stats()
        ssm, moe, cache = st["ssm"], st["moe"], st["cache"]
        assert ssm["state_layers"] == 2 and moe["expert_layers"] == 2 and moe["experts_held"] == [4, 8]
        assert ssm["prefill"] == {"rows_scanned": 16 + 4 + 16 + 4, "rows_real": 10 + 3 + 12 + 2}
        assert ssm["decode"]["slot_steps_live"] == 4 * 19 == moe["decode"]["pairs_routed"] // (2 * 3)
        assert ssm["decode"]["slot_steps"] >= ssm["decode"]["slot_steps_live"] and ssm["decode"]["slot_steps"] % 12 == 0
        # the slot-steps whose state was read and written: the live ones under the kernel, all under the XLA pass
        assert ssm["decode"]["slot_steps_visited"] == ssm["decode"][
            "slot_steps_live" if update == "kernel" else "slot_steps"]
        assert list(ssm["decode"]) == ["slot_steps", "slot_steps_live", "slot_steps_visited"]
        per_slot = 2 * (8 * 8 * N * 4 + 3 * (64 + 4 * N) * 4)
        assert cache["state"] == {"layers": 2, "rows_per_slot": 1, "bytes": 3 * per_slot, "row_layout": False}
        assert cache["full"]["layers"] == 1 and set(cache) == {"full", "state"}
        assert st["memory"]["kv_cache"]["bytes"] == cache["full"]["bytes"] + cache["state"]["bytes"]
        assert st["compiles_since_init"] == 0
        text = rep.metrics_text()
        assert f'rlt_serve_ssm_slot_steps_live_total {ssm["decode"]["slot_steps_live"]}\n' in text
        assert f'rlt_serve_ssm_slot_steps_total {ssm["decode"]["slot_steps"]}\n' in text
        assert f'rlt_serve_ssm_slot_steps_visited_total {ssm["decode"]["slot_steps_visited"]}\n' in text
        assert f'rlt_serve_ssm_rows_scanned_total {ssm["prefill"]["rows_scanned"]}\n' in text
        assert f'rlt_serve_kv_bytes{{kind="state"}} {cache["state"]["bytes"]}\n' in text
    finally:
        rep.stop()
