"""A configuration with mixed layer kinds and held experts
(``GPTConfig.layer_types``, models/mixed.py): how it is described, the
expert layer that is told which experts it holds, the modes that refuse
it by name, and what the replica says about it. The comparison with the
plain reference is ``tests/perfbench/test_mimo_v2_flash.py``."""
import time

import numpy as np
import pytest

from ray_lightning_tpu.models.gpt import GPTConfig, init_gpt_params

MIXED = dict(
    vocab_size=96, n_layer=3, n_head=4, n_kv_head=1, n_kv_head_window=2, d_model=32, d_ff=64, d_ff_expert=16,
    qk_head_dim=12, v_head_dim=8, max_seq=64, pos_embed="rope", rope_dim=4, rope_theta_window=1e4,
    norm_impl="rmsnorm", mlp_variant="swiglu", tie_word_embeddings=False, attn_window=8,
    attn_sink_logit=["window"], attn_value_scale=0.707,
    layer_types=[["full", "dense"], ["window", "experts"], ["full", "experts"]],
    n_experts=16, moe_top_k=4, moe_scoring="sigmoid", experts_held=[4, 8],
)


@pytest.fixture(scope="module")
def cfg():
    return GPTConfig(**MIXED)


@pytest.fixture(scope="module")
def params(cfg):
    import jax

    return init_gpt_params(jax.random.PRNGKey(0), cfg)


def test_json_lists_become_tuples_and_the_config_stays_hashable(cfg):
    assert cfg.layer_types == (("full", "dense"), ("window", "experts"), ("full", "experts"))
    assert cfg.experts_held == (4, 8) and cfg.attn_sink_logit == ("window",) and cfg.mixed
    assert hash(cfg) == hash(GPTConfig(**MIXED))
    assert not GPTConfig().mixed  # existing configurations build unchanged


@pytest.mark.parametrize("change,says", [
    (dict(layer_types=[["full", "dense"]]), "n_layer"),
    (dict(layer_types=[["full", "dense"], ["local", "experts"], ["full", "experts"]]), "layer_types entry"),
    (dict(norm_impl="layernorm"), "RMSNorm"),
    (dict(tie_word_embeddings=True), "untied"),
    (dict(attn_window=0), "attn_window"),
    (dict(rope_dim=5), "rope_dim"),
    (dict(experts_held=[12, 8]), "experts_held"),
    (dict(moe_scoring="tanh"), "moe_scoring"),
    (dict(n_kv_head_window=3), "KV heads"),
])
def test_a_mixed_configuration_that_cannot_run_says_what_is_wrong(change, says):
    with pytest.raises(ValueError, match=says):
        GPTConfig(**dict(MIXED, **change)).validate_variants()


def test_held_experts_need_layer_types():
    with pytest.raises(ValueError, match="layer_types"):
        GPTConfig(n_experts=8, experts_held=[0, 4]).validate_variants()


# -- the expert layer --------------------------------------------------------------
def _dense_oracle(p, x, held, top_k, scoring, valid=None):
    """Every held expert over every token, weighted by the gates."""
    import jax
    import jax.numpy as jnp

    from ray_lightning_tpu.parallel.moe import route_top_k

    gates, experts = route_top_k(x, p["router"], p.get("router_bias"), top_k, scoring)
    out = jnp.zeros_like(x)
    for j in range(held[1]):
        w = jnp.sum(jnp.where(experts == held[0] + j, gates, 0.0), -1)
        if valid is not None:
            w = w * valid
        z = jnp.einsum("td,cdf->tcf", x, p["wi"][j])
        out = out + w[:, None] * ((jax.nn.silu(z[:, 0]) * z[:, 1]) @ p["wo"][j])
    return out


@pytest.mark.parametrize("T,held,scoring,masked", [
    (7, (0, 4), "sigmoid", False),   # one tile an expert
    (200, (2, 3), "sigmoid", True),  # several tiles an expert, idle rows
    (64, (0, 16), "softmax", False),  # all experts held, softmax scores
    (40, (12, 4), "sigmoid", True),
])
def test_the_held_expert_layer_is_the_dense_sum_over_its_experts(T, held, scoring, masked):
    import jax
    import jax.numpy as jnp

    from ray_lightning_tpu.parallel.moe import held_row_tile, moe_ffn_held

    D, E, F, K = 32, 16, 24, 4
    ks = jax.random.split(jax.random.PRNGKey(T), 6)
    p = {"router": 0.3 * jax.random.normal(ks[0], (D, E)), "router_bias": 0.05 * jax.random.normal(ks[1], (E,)),
         "wi": 0.2 * jax.random.normal(ks[2], (held[1], 2, D, F)), "wo": 0.2 * jax.random.normal(ks[3], (held[1], F, D))}
    x = jax.random.normal(ks[4], (T, D))
    valid = (jax.random.uniform(ks[5], (T,)) < 0.7) if masked else None
    out, stats = jax.jit(lambda p, x, v: moe_ffn_held(p, x, held=held, top_k=K, scoring=scoring, valid=v))(p, x, valid)
    want = _dense_oracle(p, x, held, K, scoring, valid)
    assert float(jnp.abs(out - want).max()) < 1e-5 * max(1.0, float(jnp.abs(want).max()))
    n_valid = T if valid is None else int(valid.sum())
    assert int(stats[0]) == n_valid * K and 0 <= int(stats[1]) <= int(stats[0]) and int(stats[2]) <= held[1]
    if held[1] == E:
        assert int(stats[1]) == int(stats[0])  # all held: every pair lands here
    if valid is not None:
        assert float(jnp.abs(jnp.where(valid[:, None], 0.0, out)).max()) == 0.0  # idle rows get nothing
    assert 16 <= held_row_tile(T, K, E) <= 256


def test_an_expert_no_token_chose_costs_no_tile():
    """The loop runs over the tiles in use: with every token steered to
    one expert the other held experts' weights are never read (NaNs in
    them would reach the result otherwise)."""
    import jax.numpy as jnp

    from ray_lightning_tpu.parallel.moe import moe_ffn_held

    D, E, F = 8, 4, 8
    router = jnp.zeros((D, E)).at[:, 1].set(1.0)
    x = jnp.ones((5, D))
    wi = jnp.full((E, 2, D, F), jnp.nan).at[1].set(0.1)
    wo = jnp.full((E, F, D), jnp.nan).at[1].set(0.1)
    out, stats = moe_ffn_held({"router": router, "router_bias": jnp.zeros((E,)), "wi": wi, "wo": wo}, x,
                              held=(0, 4), top_k=1, scoring="sigmoid")
    assert bool(jnp.isfinite(out).all()) and [int(s) for s in stats] == [5, 5, 1]


# -- the modes that refuse -----------------------------------------------------------
def _engine(params, cfg, **kw):
    from ray_lightning_tpu.serve.engine import DecodeEngine

    return DecodeEngine(params, cfg, num_slots=2, max_seq=64, prefill_buckets=[16], **kw)


def _int8(params):
    from ray_lightning_tpu.utils.quantize import quantize_params_int8

    return quantize_params_int8(params)


def _mesh2():
    import jax
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:2]).reshape(2, 1), ("model", "data"))


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
def test_the_engine_refuses_each_unsupported_mode_by_name(params, cfg, name, kw):
    kw = {k: (v() if k == "mesh" else v) for k, v in kw.items()}
    p = kw.pop("params", lambda x: x)(params)
    with pytest.raises(ValueError, match=f"{name}.*does not run a configuration with mixed layer kinds"):
        _engine(p, cfg, **kw)


def _call(fn_name, params, cfg):
    import jax.numpy as jnp

    from ray_lightning_tpu.models import gpt
    from ray_lightning_tpu.models.mixed import empty_caches

    k, v = empty_caches(cfg, 1, 64, jnp.float32)
    tok = jnp.zeros((1, 4), jnp.int32)
    z, table = jnp.zeros((1,), jnp.int32), jnp.zeros((1, 4), jnp.int32)
    state = (z, z, jnp.zeros((1, 2), jnp.uint32), jnp.zeros((1,)), z, jnp.ones((1,)), z > 0, z, z)
    return {
        "gpt_prefill_chunk": lambda: gpt.gpt_prefill_chunk(params, cfg, tok, k, v, 0),
        "_piggyback_prefill": lambda: gpt._piggyback_prefill(params, cfg, (None,) * 12, z, z, z, z, z, k, v),
        "gpt_decode_verify": lambda: gpt.gpt_decode_verify(params, cfg, tok, z, k, v),
        "gpt_decode_step_paged": lambda: gpt.gpt_decode_step_paged(params, cfg, z, z, k, v, table, 16),
        "gpt_decode_verify_paged": lambda: gpt.gpt_decode_verify_paged(params, cfg, tok, z, k, v, table, 16),
        "gpt_prefill_chunk_paged": lambda: gpt.gpt_prefill_chunk_paged(params, cfg, tok, k, v, table, 0, 4, page=16),
        "gpt_decode_fold_spec": lambda: gpt.gpt_decode_fold_spec(
            params, cfg, *state, jnp.zeros((1, 64), jnp.int32), k, v, fold=1, depth=2, draft_fn=None),
        "gpt_decode_fold paged": lambda: gpt.gpt_decode_fold(
            params, cfg, *state, k, v, fold=1, page_table=table, page_size=16),
        "gpt_generate": lambda: gpt.gpt_generate(params, cfg, tok, 4),
        "gpt_logical_axes": lambda: gpt.gpt_logical_axes(cfg),
    }[fn_name]


@pytest.mark.parametrize("fn_name,says", [
    ("gpt_prefill_chunk", "chunked prefill"), ("_piggyback_prefill", "piggybacked prefill chunks"),
    ("gpt_decode_verify", "speculative decoding"), ("gpt_decode_step_paged", "paged KV cache"),
    ("gpt_decode_verify_paged", "paged KV cache"), ("gpt_prefill_chunk_paged", "paged KV cache"),
    ("gpt_decode_fold_spec", "speculative decoding"), ("gpt_decode_fold paged", "paged KV cache"),
    ("gpt_generate", "gpt_generate"), ("gpt_logical_axes", "sharded parameter tree"),
])
def test_each_restatement_of_the_block_refuses_by_name(params, cfg, fn_name, says):
    with pytest.raises(ValueError, match=f"{says}.*mixed layer kinds or held experts"):
        _call(fn_name, params, cfg)()


# -- what the replica says -----------------------------------------------------------
def test_the_replica_serves_it_and_reports_the_expert_layers_and_both_caches(params):
    from ray_lightning_tpu.serve.server import ServeReplica

    rep = ServeReplica(params=params, model_config=dict(MIXED), num_slots=2, max_seq=64,
                       prefill_buckets=[16], decode_fold=4, watchdog=False)
    try:
        rng = np.random.default_rng(1)
        rids = [rep.submit(rng.integers(0, 96, size=10).tolist(), max_new_tokens=20) for _ in range(3)]
        deadline = time.monotonic() + 120
        for rid in rids:
            while not rep.result(rid, wait_s=0.2)["done"]:
                assert time.monotonic() < deadline, "request did not finish"
        st = rep.stats()
        moe, cache = st["moe"], st["cache"]
        assert moe["experts_held"] == [4, 8] and moe["n_experts"] == 16 and moe["expert_layers"] == 2
        assert moe["prefill"]["pairs_routed"] == 3 * 10 * 2 * 4 and moe["prefill"]["admissions"] == 3
        assert moe["decode"]["pairs_routed"] == 3 * 19 * 2 * 4
        assert moe["decode"]["pairs_held"] <= moe["decode"]["pairs_routed"]
        assert cache["window"]["rows_per_slot"] == 8 and cache["full"]["rows_per_slot"] == 64
        assert st["memory"]["kv_cache"]["bytes"] == cache["full"]["bytes"] + cache["window"]["bytes"]
        assert st["compiles_since_init"] == 0
        text = rep.metrics_text()
        assert f'rlt_serve_moe_pairs_routed_total{{phase="decode"}} {moe["decode"]["pairs_routed"]}\n' in text
        assert 'rlt_serve_moe_token_steps_total{phase="prefill"} 3\n' in text
        assert f'rlt_serve_kv_bytes{{kind="window"}} {cache["window"]["bytes"]}\n' in text
    finally:
        rep.stop()


# -- the full layers' decode read and its counter (ops/decode_attention.py) ----------------------------
#: MIXED at widths Mosaic takes: full K rows of 2 x 192, V rows of 2 x 128, three blocks of 128 positions a slot
KERNEL = dict(MIXED, n_head=8, n_kv_head=2, qk_head_dim=192, v_head_dim=128, rope_dim=64, max_seq=384, attn_window=128)


def _serve(rep, sizes, new_tokens=20):
    rng = np.random.default_rng(1)
    rids = [rep.submit(rng.integers(0, 96, size=n).tolist(), max_new_tokens=new_tokens) for n in sizes]
    deadline = time.monotonic() + 240
    out = []
    for rid in rids:
        while not (res := rep.result(rid, wait_s=0.2))["done"]:
            assert time.monotonic() < deadline, "request did not finish"
        out.append(res["tokens"])
    return out


@pytest.mark.parametrize("read", ["xla", "planted_block"])
def test_the_replicas_attn_counter_counts_the_full_layers(params, read, monkeypatch):
    """``stats()["attn"]`` of the toy mimo configuration: its two full layers'
    rows (the window layer's ring is not counted). On the CPU the read is
    XLA's, every allocated row; with a block planted in the engine's
    ``_attn_reads`` the counter rounds each step's rows up to whole blocks."""
    from ray_lightning_tpu.obs import registry
    from ray_lightning_tpu.serve.server import ServeReplica

    own = registry.MetricsRegistry()
    monkeypatch.setattr(registry, "get_registry", lambda: own)
    rep = ServeReplica(params=params, model_config=dict(MIXED), num_slots=2, max_seq=64,
                       prefill_buckets=[16], decode_fold=4, watchdog=False)
    try:
        assert rep.engine._attn_reads == {"full": (2, 0)}
        if read == "planted_block":
            rep.engine._attn_reads["full"] = (2, 16)
        sizes = (10, 3, 12)
        _serve(rep, sizes)
        attn = rep.stats()["attn"]
        steps = [n + j for n in sizes for j in range(1, 20)]  # rows 0 .. pos each decode step's query saw
        assert attn["rows_live"] == 2 * sum(steps)
        assert attn["rows_allocated"] % (2 * 2 * 64 * 4) == 0 and attn["rows_allocated"] > 0
        if read == "xla":
            assert attn["rows_visited"] == attn["rows_allocated"]
        else:
            assert attn["rows_visited"] == 2 * sum(-(-rows // 16) * 16 for rows in steps)
            assert attn["rows_live"] < attn["rows_visited"] < attn["rows_allocated"]
        assert f'rlt_serve_attn_rows_visited_total {attn["rows_visited"]}\n' in rep.metrics_text()
    finally:
        rep.stop()


def test_the_full_layers_read_through_the_kernel_serves_the_xla_reads_tokens(monkeypatch):
    """The selection told "tpu": the fold's full layers read through the
    decode kernel (interpreted here), the engine's counter takes the block
    from the same answer, and the tokens are the XLA read's."""
    import jax

    from ray_lightning_tpu.obs import registry
    from ray_lightning_tpu.serve.server import ServeReplica
    from tests.utils import force_decode_kernel

    own = registry.MetricsRegistry()
    monkeypatch.setattr(registry, "get_registry", lambda: own)
    kernel_params = init_gpt_params(jax.random.PRNGKey(0), GPTConfig(**KERNEL))
    sizes = (10, 120, 3)  # the second crosses the first block's end while it decodes
    seen = {}
    for read in ("xla", "kernel"):
        if read == "kernel":
            force_decode_kernel(monkeypatch)
        rep = ServeReplica(params=kernel_params, model_config=dict(KERNEL), num_slots=3, max_seq=384,
                           prefill_buckets=[16, 128], decode_fold=4, watchdog=False)
        try:
            assert rep.engine._attn_reads == {"full": (2, 128 if read == "kernel" else 0)}
            seen[read] = (_serve(rep, sizes), rep.stats()["attn"])
            assert rep.stats()["compiles_since_init"] == 0
        finally:
            rep.stop()
    (want, xla), (got, kernel) = seen["xla"], seen["kernel"]
    assert got == want
    steps = [n + j for n in sizes for j in range(1, 20)]
    assert kernel["rows_live"] == xla["rows_live"] == 2 * sum(steps)
    assert xla["rows_visited"] == xla["rows_allocated"] == kernel["rows_allocated"]
    assert kernel["rows_visited"] == 2 * sum(-(-rows // 128) * 128 for rows in steps)
