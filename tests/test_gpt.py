"""GPT model family + GSPMDStrategy (dp/fsdp/tp/sp) tests.

Runs on the 8-virtual-CPU-device mesh from conftest. Mirrors the reference's
behavioral test style (weights move, metrics finite — tests/utils.py:236-272)
and adds TPU-specific assertions: parameter shardings land on the intended
mesh axes, tensor/sequence-parallel forwards agree with the dense one.
"""
import numpy as np
import pytest

from ray_lightning_tpu.models import GPTConfig, GPTLM, layers, make_fake_text
from ray_lightning_tpu.models.gpt import gpt_forward, init_gpt_params
from ray_lightning_tpu.strategies import GSPMDStrategy
from ray_lightning_tpu.trainer.module import unpack_optimizers

TINY = GPTConfig(
    vocab_size=64, n_layer=2, n_head=2, d_model=32, max_seq=32,
    attn_impl="reference",
)


def make_inprocess(mesh_shape, num_workers=8, **kw):
    """GSPMD strategy wired for in-process use (the __graft_entry__ pattern)."""
    from ray_lightning_tpu.parallel.env import DistEnv

    s = GSPMDStrategy(
        num_workers=num_workers, use_tpu=False, mesh_shape=mesh_shape, **kw
    )
    s.dist_env = DistEnv(
        world_size=num_workers, num_hosts=1, host_rank=0, local_chips=num_workers
    )
    s.mesh = s.build_mesh()
    return s


def test_forward_shape_and_flash_parity():
    import jax

    rng = jax.random.PRNGKey(0)
    params = init_gpt_params(rng, TINY)
    toks = np.asarray(
        jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, TINY.vocab_size)
    )
    ref = gpt_forward(params, toks, TINY)
    assert ref.shape == (2, 16, TINY.vocab_size)
    assert np.isfinite(np.asarray(ref)).all()
    import dataclasses

    flash_cfg = dataclasses.replace(TINY, attn_impl="flash")
    out = gpt_forward(params, toks, flash_cfg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-2)


def test_mesh_shape_validation():
    with pytest.raises(ValueError, match="covers"):
        GSPMDStrategy(num_workers=8, use_tpu=False, mesh_shape={"data": 4})
    with pytest.raises(ValueError, match="unknown mesh axis"):
        GSPMDStrategy(num_workers=8, use_tpu=False, mesh_shape={"tensor": 8})
    with pytest.raises(ValueError, match="sequence_parallel"):
        GSPMDStrategy(
            num_workers=8,
            use_tpu=False,
            mesh_shape={"data": 8},
            sequence_parallel=True,
        )


def test_param_shardings_land_on_mesh_axes():
    """wqkv heads dim -> model axis, embed dims -> fsdp axis; optimizer
    moments follow their parameters."""
    import jax
    from jax.sharding import PartitionSpec as P

    strategy = make_inprocess({"data": 2, "fsdp": 2, "model": 2})
    module = GPTLM(config=TINY, batch_size=4)
    strategy.bind_module(module)

    params = init_gpt_params(jax.random.PRNGKey(0), TINY)
    shardings = strategy.param_sharding(params)
    assert shardings["blocks"]["wqkv"].spec == P(None, "fsdp", None, "model", None)
    assert shardings["blocks"]["wi"].spec == P(None, "fsdp", "model")
    assert shardings["blocks"]["wo2"].spec == P(None, "model", "fsdp")
    assert shardings["wte"].spec == P("model", "fsdp")
    assert shardings["lnf_g"].spec == P(None)

    tx, _ = unpack_optimizers(module.configure_optimizers())
    opt_state = tx.init(params)
    opt_sh = strategy.opt_sharding(opt_state, params)
    flat = jax.tree_util.tree_leaves(opt_sh)
    specs = {s.spec for s in flat}
    assert P(None, "fsdp", None, "model", None) in specs  # mu/nu for wqkv
    assert P() in specs  # count scalar replicated


def test_tp_forward_matches_dense():
    """The same params under a dp2 x model4 mesh produce the same logits as
    the unsharded forward — GSPMD sharding must not change the math."""
    import jax

    strategy = make_inprocess({"data": 2, "model": 4})
    module = GPTLM(config=TINY, batch_size=4)
    strategy.bind_module(module)

    params = init_gpt_params(jax.random.PRNGKey(0), TINY)
    dense = gpt_forward(
        params,
        np.asarray(
            jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, TINY.vocab_size)
        ),
        TINY,
    )
    toks = np.asarray(
        jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, TINY.vocab_size)
    )
    placed = strategy.place_params(params)
    sharded = jax.jit(lambda p, t: gpt_forward(p, t, TINY))(placed, toks)
    np.testing.assert_allclose(np.asarray(sharded), np.asarray(dense), atol=1e-4)


def test_llama_variant_forward_and_sharding():
    """Llama-family knobs (RMSNorm, SwiGLU, RoPE, GQA, untied head): the
    variant trains under a tp/fsdp mesh and its sharded logits equal the
    unsharded forward; lm_head shards like the embedding table."""
    import dataclasses

    import jax
    from jax.sharding import PartitionSpec as P

    cfg = dataclasses.replace(
        GPTConfig.llama(
            vocab_size=64, n_layer=2, n_head=4, n_kv_head=2, d_model=32,
            d_ff=48, max_seq=32,
        ),
        attn_impl="reference",
    )
    params = init_gpt_params(jax.random.PRNGKey(0), cfg)
    assert "lm_head" in params
    # gate/up stacked (D, 2, F): tp shards of both halves co-locate.
    assert params["blocks"]["wi"].shape == (2, 32, 2, 48)
    toks = np.asarray(
        jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, cfg.vocab_size)
    )
    dense = gpt_forward(params, toks, cfg)
    assert np.isfinite(np.asarray(dense)).all()

    strategy = make_inprocess({"data": 2, "fsdp": 2, "model": 2})
    module = GPTLM(config=cfg, batch_size=4)
    strategy.bind_module(module)
    sh = strategy.param_sharding(params)
    assert sh["lm_head"].spec == P("model", "fsdp")
    placed = strategy.place_params(params)
    sharded = jax.jit(lambda p, t: gpt_forward(p, t, cfg))(placed, toks)
    np.testing.assert_allclose(np.asarray(sharded), np.asarray(dense), atol=1e-4)

    # Variant validation fails fast.
    with pytest.raises(ValueError, match="mlp_variant"):
        gpt_forward(
            params, toks, dataclasses.replace(cfg, mlp_variant="relu")
        )


def test_sequence_parallel_ring_matches_dense():
    """Ring attention over the seq axis reproduces the dense causal logits."""
    import jax

    strategy = make_inprocess(
        {"data": 2, "seq": 4}, sequence_parallel=True
    )
    module = GPTLM(config=TINY, batch_size=4)
    strategy.bind_module(module)
    assert module._seq_axis == "seq"

    params = init_gpt_params(jax.random.PRNGKey(0), TINY)
    toks = np.asarray(
        jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, TINY.vocab_size)
    )
    dense = gpt_forward(params, toks, TINY)
    placed = strategy.place_params(params)
    ringed = jax.jit(lambda p, t: module._forward(p, t))(placed, toks)
    np.testing.assert_allclose(np.asarray(ringed), np.asarray(dense), atol=1e-3)


def test_gspmd_compiled_step_trains():
    """Full sharded train step on dp2 x fsdp2 x model2: loss decreases and
    shardings survive the step (donation + out shardings stable)."""
    import jax

    strategy = make_inprocess({"data": 2, "fsdp": 2, "model": 2})
    module = GPTLM(config=TINY, batch_size=4, lr=1e-2, warmup_steps=2)
    strategy.bind_module(module)

    data = make_fake_text(64, seq_len=16, vocab=TINY.vocab_size)
    toks = data.arrays[0][:16]
    rng = jax.random.PRNGKey(0)
    params = module.init_params(rng, (toks,))
    tx, _ = unpack_optimizers(module.configure_optimizers())
    opt_state = tx.init(params)

    params = strategy.place_params(params)
    opt_state = strategy.place_opt_state(opt_state, params)
    batch = strategy.make_global_batch((toks,))
    step = strategy.compile_train_step(module, tx)

    losses = []
    for i in range(20):
        params, opt_state, logs = step(params, opt_state, batch, rng, i)
        losses.append(float(np.asarray(logs["loss"])))
    assert losses[-1] < losses[0] * 0.8, losses
    wqkv = params["blocks"]["wqkv"]
    expected = strategy.param_sharding(params)["blocks"]["wqkv"]
    assert wqkv.sharding.is_equivalent_to(expected, wqkv.ndim)


def test_gspmd_fallback_without_logical_axes():
    """Modules without param_logical_axes get ZeRO-3-style fsdp sharding."""
    import jax
    from jax.sharding import PartitionSpec as P

    from ray_lightning_tpu.models import MNISTClassifier

    strategy = make_inprocess({"fsdp": 8})
    module = MNISTClassifier(batch_size=4)
    strategy.bind_module(module)
    params = module.init_params(
        jax.random.PRNGKey(0), (np.zeros((8, 28, 28), np.float32), np.zeros(8, np.int32))
    )
    sh = strategy.param_sharding(params)
    assert sh["w1"].spec == P("fsdp", None)


def test_logical_spec_resolution():
    from jax.sharding import PartitionSpec as P

    from ray_lightning_tpu.parallel.logical import (
        DEFAULT_RULES,
        spec_from_logical,
    )

    strategy = make_inprocess({"data": 2, "fsdp": 2, "model": 2})
    mesh = strategy.mesh
    # indivisible dim stays replicated
    assert spec_from_logical((3, 32), ("heads", "embed"), DEFAULT_RULES, mesh) == P(
        None, "fsdp"
    )
    # a mesh axis is used at most once per spec
    assert spec_from_logical(
        (32, 32), ("embed", "embed"), DEFAULT_RULES, mesh
    ) == P("fsdp", None)
    with pytest.raises(ValueError, match="logical axes"):
        spec_from_logical((32,), ("embed", "mlp"), DEFAULT_RULES, mesh)


def test_logical_none_rule_override():
    """A prepended (name, None) rule pins the axis replicated (t5x-style
    first-match-wins), overriding later rules for the same name."""
    import jax
    from jax.sharding import PartitionSpec as P

    from ray_lightning_tpu.parallel.logical import DEFAULT_RULES

    strategy = make_inprocess(
        {"data": 2, "fsdp": 2, "model": 2},
        logical_axis_rules=[("heads", None)] + list(DEFAULT_RULES),
    )
    module = GPTLM(config=TINY)
    strategy.bind_module(module)
    params = init_gpt_params(jax.random.PRNGKey(0), TINY)
    sh = strategy.param_sharding(params)
    assert sh["blocks"]["wqkv"].spec == P(None, "fsdp", None, None, None)


def test_opt_sharding_no_shape_collision():
    """Same-shape params with different layouts (d_ff == d_model) keep
    per-param moment shardings (structure-matched, not shape-matched)."""
    import jax

    cfg = GPTConfig(
        vocab_size=64, n_layer=2, n_head=2, d_model=32, d_ff=32, max_seq=32,
        attn_impl="reference",
    )
    strategy = make_inprocess({"fsdp": 4, "model": 2})
    module = GPTLM(config=cfg)
    strategy.bind_module(module)
    params = init_gpt_params(jax.random.PRNGKey(0), cfg)
    tx, _ = unpack_optimizers(module.configure_optimizers())
    opt_state = tx.init(params)
    psh = strategy.param_sharding(params)
    osh = strategy.opt_sharding(opt_state, params)
    # Find the mu subtree (same treedef as params) inside the optax state.
    mu_sh = jax.tree_util.tree_leaves(
        osh, is_leaf=lambda n: isinstance(n, dict) and "blocks" in n
    )
    mu_trees = [n for n in mu_sh if isinstance(n, dict)]
    assert mu_trees, "no param-structured subtree found in opt shardings"
    for tree in mu_trees:
        assert tree["blocks"]["wi"].spec == psh["blocks"]["wi"].spec
        assert tree["blocks"]["wo2"].spec == psh["blocks"]["wo2"].spec
    assert psh["blocks"]["wi"].spec != psh["blocks"]["wo2"].spec


def test_gspmd_sampler_follows_dp_extent():
    """dp < num_hosts (tp spans hosts): host groups sharing a dp shard get
    identical sampler ranks; dp % hosts == 0 keeps per-host sharding."""
    from ray_lightning_tpu.parallel.env import DistEnv

    s = GSPMDStrategy(
        num_workers=8, use_tpu=False, mesh_shape={"data": 2, "model": 4}
    )
    s.dist_env = DistEnv(world_size=8, num_hosts=4, host_rank=3, local_chips=2)
    assert s.sampler_kwargs() == {"num_replicas": 2, "rank": 1}
    assert s.batch_multiplier == 1

    s.dist_env = DistEnv(world_size=8, num_hosts=2, host_rank=1, local_chips=4)
    assert s.sampler_kwargs() == {"num_replicas": 2, "rank": 1}

    s2 = GSPMDStrategy(
        num_workers=6, use_tpu=False, mesh_shape={"data": 3, "model": 2}
    )
    s2.dist_env = DistEnv(world_size=6, num_hosts=2, host_rank=0, local_chips=3)
    with pytest.raises(ValueError, match="divide"):
        s2.sampler_kwargs()


@pytest.mark.slow
def test_gptlm_fit_end_to_end(start_fabric, tmp_path):
    """Trainer.fit(GPTLM, GSPMDStrategy) through the actor fabric: the full
    driver->worker->driver path with a tp-sharded transformer."""
    fabric = start_fabric(num_cpus=2)
    from tests.utils import get_trainer, train_test

    strategy = GSPMDStrategy(
        num_workers=4,
        use_tpu=False,
        mesh_shape={"data": 2, "model": 2},
    )
    module = GPTLM(config=TINY, batch_size=4, n_train=64)
    trainer = get_trainer(
        strategy=strategy, max_epochs=1, default_root_dir=str(tmp_path)
    )
    train_test(trainer, module)
    assert trainer.callback_metrics.get("val_loss") is not None


def test_sequence_parallel_zigzag_matches_dense():
    """Zigzag layout end-to-end (permuted embedding, balanced attention,
    un-permuted before the head) reproduces the dense causal logits."""
    import dataclasses

    import jax

    cfg = dataclasses.replace(TINY, seq_impl="zigzag")
    strategy = make_inprocess({"data": 2, "seq": 4}, sequence_parallel=True)
    module = GPTLM(config=cfg, batch_size=4)
    strategy.bind_module(module)

    params = init_gpt_params(jax.random.PRNGKey(0), cfg)
    toks = np.asarray(
        jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, cfg.vocab_size)
    )
    dense = gpt_forward(params, toks, TINY)  # plain config, no mesh
    placed = strategy.place_params(params)
    zigzagged = jax.jit(lambda p, t: module._forward(p, t))(placed, toks)
    np.testing.assert_allclose(
        np.asarray(zigzagged), np.asarray(dense), atol=1e-3
    )


def test_sequence_parallel_zigzag_train_step():
    """One compiled zigzag train step: loss finite and decreasing."""
    import dataclasses

    import jax

    cfg = dataclasses.replace(TINY, seq_impl="zigzag")
    strategy = make_inprocess({"data": 2, "seq": 4}, sequence_parallel=True)
    module = GPTLM(config=cfg, batch_size=4, lr=1e-2, warmup_steps=2)
    strategy.bind_module(module)
    data = make_fake_text(32, seq_len=32, vocab=cfg.vocab_size)
    toks = data.arrays[0][:8]
    rng = jax.random.PRNGKey(0)
    params = module.init_params(rng, (toks,))
    tx, _ = unpack_optimizers(module.configure_optimizers())
    opt_state = tx.init(params)
    params = strategy.place_params(params)
    opt_state = strategy.place_opt_state(opt_state, params)
    batch = strategy.make_global_batch((toks,))
    step = strategy.compile_train_step(module, tx)
    losses = []
    for i in range(10):
        params, opt_state, logs = step(params, opt_state, batch, rng, i)
        losses.append(float(np.asarray(logs["loss"])))
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0], losses


def test_generate_kv_cache_matches_full_forward():
    """Greedy KV-cached decode must agree with argmax over the full-forward
    logits at every generated position (cache correctness)."""
    import jax

    from ray_lightning_tpu.models.gpt import gpt_generate

    params = init_gpt_params(jax.random.PRNGKey(3), TINY)
    prompt = np.asarray(
        jax.random.randint(jax.random.PRNGKey(4), (2, 5), 0, TINY.vocab_size),
        np.int32,
    )
    out = np.asarray(
        jax.jit(
            lambda p, t: gpt_generate(p, TINY, t, max_new_tokens=8)
        )(params, prompt)
    )
    assert out.shape == (2, 13)
    np.testing.assert_array_equal(out[:, :5], prompt)
    # Teacher-forcing check: feeding the generated prefix through the full
    # forward must reproduce each next token.
    for p in range(5 - 1, 13 - 1):
        logits = gpt_forward(params, out[:, : p + 1], TINY)
        np.testing.assert_array_equal(
            np.argmax(np.asarray(logits[:, -1]), -1), out[:, p + 1]
        )


def test_generate_learns_recurrence():
    """A briefly-trained tiny GPT greedily generates the affine recurrence
    t+1 = (5t + 7) % V it was trained on."""
    import jax

    from ray_lightning_tpu.trainer import Trainer

    module = GPTLM(config=TINY, batch_size=8, lr=3e-3, warmup_steps=5,
                   n_train=256)
    trainer = Trainer(
        max_epochs=6,
        enable_checkpointing=False,
        seed=0,
        num_sanity_val_steps=0,
    )
    trainer.fit(module)
    start = np.asarray([[3, (5 * 3 + 7) % 64]], np.int32)
    out = np.asarray(module.generate(start, max_new_tokens=10))
    expect = [3]
    for _ in range(11):
        expect.append((5 * expect[-1] + 7) % 64)
    matches = sum(int(out[0, i]) == expect[i] for i in range(12))
    assert matches >= 9, (out[0].tolist(), expect)


def test_sample_logits_topk_topp():
    """top-k/top-p filters: membership, greedy limits, determinism."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_lightning_tpu.models.gpt import sample_logits

    logits = jnp.asarray(
        [[4.0, 3.0, 2.0, 1.0, 0.0, -1.0, -2.0, -3.0]], jnp.float32
    )

    # temperature 0 -> argmax regardless of filters
    assert int(sample_logits(jax.random.PRNGKey(0), logits, 0.0, top_k=3)[0]) == 0

    # top_k=1 and tiny top_p both collapse to the argmax even at high temp
    for kw in ({"top_k": 1}, {"top_p": 1e-6}):
        ids = [
            int(sample_logits(jax.random.PRNGKey(s), logits, 5.0, **kw)[0])
            for s in range(20)
        ]
        assert set(ids) == {0}, (kw, ids)

    # top_k=3: every draw lands in the 3 highest-logit ids
    draws = [
        int(sample_logits(jax.random.PRNGKey(s), logits, 2.0, top_k=3)[0])
        for s in range(50)
    ]
    assert set(draws) <= {0, 1, 2} and len(set(draws)) > 1

    # top_p: mass of [4,3,2,...] softmax is ~0.64/0.24/0.09; p=0.7 keeps
    # {0,1} (token crossing p included)
    draws = [
        int(sample_logits(jax.random.PRNGKey(s), logits, 1.0, top_p=0.7)[0])
        for s in range(60)
    ]
    assert set(draws) == {0, 1}, sorted(set(draws))

    # same rng -> same sample (pure function)
    a = sample_logits(jax.random.PRNGKey(3), logits, 1.0, top_k=4, top_p=0.9)
    b = sample_logits(jax.random.PRNGKey(3), logits, 1.0, top_k=4, top_p=0.9)
    assert int(a[0]) == int(b[0])

    # batch shape preserved
    batch = jnp.tile(logits, (5, 1))
    out = sample_logits(jax.random.PRNGKey(1), batch, 1.0, top_k=2, top_p=0.9)
    assert out.shape == (5,)
    assert np.all(np.asarray(out) < 8)


def test_generate_with_sampling_filters():
    """gpt_generate composes with top-k/top-p; output shape and prompt
    teacher-forcing hold; greedy run unchanged by filters."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_lightning_tpu.models.gpt import gpt_generate, init_gpt_params

    params = init_gpt_params(jax.random.PRNGKey(0), TINY)
    prompt = jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32)
    out = gpt_generate(
        params, TINY, prompt, max_new_tokens=5,
        temperature=0.8, rng=jax.random.PRNGKey(1), top_k=8, top_p=0.95,
    )
    assert out.shape == (2, 8)
    np.testing.assert_array_equal(np.asarray(out[:, :3]), np.asarray(prompt))
    assert np.all(np.asarray(out) >= 0) and np.all(np.asarray(out) < TINY.vocab_size)

    greedy = gpt_generate(params, TINY, prompt, max_new_tokens=5)
    greedy_filtered = gpt_generate(
        params, TINY, prompt, max_new_tokens=5, top_k=4, top_p=0.5
    )
    np.testing.assert_array_equal(np.asarray(greedy), np.asarray(greedy_filtered))


@pytest.mark.parametrize("family", ["gelu-stored", "swiglu-engine"])
def test_gqa_rope_shapes_and_kv_cache_equality(family):
    """GQA (n_kv_head < n_head) + RoPE: params carry Hkv-headed kv and no
    wpe; greedy KV-cached decode (grouped Hkv cache) agrees with the full
    forward at every position.

    ``swiglu-engine``: a llama-style block (three layers, RMSNorm, SwiGLU)
    whose decode runs on the tree an engine holds (``engine_weights``) while
    the full forward keeps the stored one; and the re-formed tree's prefill
    and first decode step give the stored tree's hidden states, K/V and
    logits bit for bit (float32 on the CPU: the same sums in the same
    order)."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from ray_lightning_tpu.models.gpt import engine_weights, gpt_decode_step, gpt_generate, gpt_prefill

    cfg = dataclasses.replace(TINY, n_head=4, n_kv_head=2, pos_embed="rope")
    if family == "swiglu-engine":
        cfg = dataclasses.replace(
            cfg, n_layer=3, norm_impl="rmsnorm", mlp_variant="swiglu", tie_word_embeddings=False
        )
    stored = init_gpt_params(jax.random.PRNGKey(3), cfg)
    params = stored
    if family == "swiglu-engine":
        params = engine_weights(stored, cfg)
        assert stored["blocks"]["wi"].shape == (3, cfg.d_model, 2, cfg.ff_dim)  # the interface, as it was
        assert sorted(set(params["blocks"]) - set(stored["blocks"])) == ["wi_gate", "wi_up"]
        assert params["blocks"]["wi_gate"].shape == (3, cfg.d_model, cfg.ff_dim)
        assert params["blocks"]["wq"].shape == (3, cfg.d_model, 4 * cfg.head_dim)
        assert params["blocks"]["wkv"].shape == (3, cfg.d_model, 2 * 2 * cfg.head_dim)
        assert engine_weights(params, cfg)["blocks"].keys() == params["blocks"].keys()  # re-forming twice: nothing
        toks = jax.random.randint(jax.random.PRNGKey(5), (2, 7), 0, cfg.vocab_size)

        def prefill_then_step(p):
            h, k, v = gpt_prefill(p, cfg, toks)
            pad = ((0, 0), (0, 0), (0, 1), (0, 0), (0, 0))
            return (h, k, v) + gpt_decode_step(
                p, cfg, toks[:, 0], jnp.full((2,), 7, jnp.int32), jnp.pad(k, pad), jnp.pad(v, pad)
            )

        for got, want in zip(jax.jit(prefill_then_step)(params), jax.jit(prefill_then_step)(stored)):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        params_fwd = stored
    else:
        params_fwd = params
    assert "wpe" not in stored
    assert stored["blocks"]["wkv"].shape == (
        cfg.n_layer, cfg.d_model, 2, 2, cfg.head_dim
    )
    assert stored["blocks"]["wq"].shape == (
        cfg.n_layer, cfg.d_model, 4, cfg.head_dim
    )

    prompt = np.asarray(
        jax.random.randint(jax.random.PRNGKey(4), (2, 5), 0, cfg.vocab_size),
        np.int32,
    )
    out = np.asarray(
        jax.jit(lambda p, t: gpt_generate(p, cfg, t, max_new_tokens=8))(
            params, prompt
        )
    )
    assert out.shape == (2, 13)
    for p in range(4, 12):
        logits = gpt_forward(params_fwd, out[:, : p + 1], cfg)
        np.testing.assert_array_equal(
            np.argmax(np.asarray(logits[:, -1]), -1), out[:, p + 1]
        )


def test_gqa_mqa_trains():
    """MQA (n_kv_head=1) end-to-end fit: loss finite, weights move."""
    import dataclasses

    from ray_lightning_tpu.trainer import Trainer
    from tests.utils import train_test

    cfg = dataclasses.replace(TINY, n_head=4, n_kv_head=1, pos_embed="rope")
    module = GPTLM(config=cfg, batch_size=8, n_train=64)
    trainer = Trainer(
        max_epochs=1, enable_checkpointing=False, seed=0,
        num_sanity_val_steps=0,
    )
    train_test(trainer, module)


def test_zigzag_rope_matches_dense():
    """RoPE under the zigzag layout rotates by TRUE token positions, so the
    sequence-parallel logits still equal the dense ones."""
    import dataclasses

    import jax

    cfg = dataclasses.replace(
        TINY, seq_impl="zigzag", pos_embed="rope", n_head=4, n_kv_head=2
    )
    strategy = make_inprocess({"data": 2, "seq": 4}, sequence_parallel=True)
    module = GPTLM(config=cfg, batch_size=4)
    strategy.bind_module(module)

    params = init_gpt_params(jax.random.PRNGKey(0), cfg)
    toks = np.asarray(
        jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, cfg.vocab_size)
    )
    dense_cfg = dataclasses.replace(cfg, seq_impl="ring")
    dense = gpt_forward(params, toks, dense_cfg)  # no mesh -> dense attention
    placed = strategy.place_params(params)
    zigzagged = jax.jit(lambda p, t: module._forward(p, t))(placed, toks)
    np.testing.assert_allclose(
        np.asarray(zigzagged), np.asarray(dense), atol=1e-3
    )


def test_mqa_under_tensor_parallel_replicates_kv():
    """MQA (1 kv head) with a model axis: q/o shard over heads, the
    indivisible kv head falls through to replication (logical.py rule
    fallback) and the sharded logits still match dense."""
    import dataclasses

    import jax
    from jax.sharding import PartitionSpec as P

    cfg = dataclasses.replace(TINY, n_head=4, n_kv_head=1, pos_embed="rope")
    strategy = make_inprocess({"data": 2, "model": 4})
    module = GPTLM(config=cfg, batch_size=4)
    strategy.bind_module(module)

    params = init_gpt_params(jax.random.PRNGKey(0), cfg)
    sh = strategy.param_sharding(params)
    # no fsdp axis in this mesh -> embed replicated; heads -> model
    assert sh["blocks"]["wq"].spec == P(None, None, "model", None)
    # size-1 kv head dim cannot split over model=4 -> replicated
    assert sh["blocks"]["wkv"].spec[3] is None

    toks = np.asarray(
        jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)
    )
    dense = gpt_forward(params, toks, cfg)
    placed = strategy.place_params(params)
    sharded = jax.jit(lambda p, t: module._forward(p, t))(placed, toks)
    np.testing.assert_allclose(
        np.asarray(sharded), np.asarray(dense), atol=1e-3
    )


def test_gpt_sliding_window():
    """attn_window: training forward matches a masked reference; KV-cached
    decode agrees with the full forward; seq-parallel + window rejects."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import pytest

    from ray_lightning_tpu.models.gpt import gpt_generate

    cfg = dataclasses.replace(TINY, attn_window=8, pos_embed="rope")
    params = init_gpt_params(jax.random.PRNGKey(0), cfg)
    toks = np.asarray(
        jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, cfg.vocab_size)
    )
    windowed = gpt_forward(params, toks, cfg)
    full = gpt_forward(
        params, toks, dataclasses.replace(cfg, attn_window=0)
    )
    assert np.isfinite(np.asarray(windowed)).all()
    # The window genuinely changes late-position logits.
    assert np.abs(np.asarray(windowed[:, -1]) - np.asarray(full[:, -1])).max() > 1e-4

    prompt = np.asarray([[1, 2, 3, 4, 5]], np.int32)
    out = np.asarray(
        gpt_generate(params, cfg, jnp.asarray(prompt), max_new_tokens=8)
    )
    for p in range(4, 12):
        logits = gpt_forward(params, out[:, : p + 1], cfg)
        np.testing.assert_array_equal(
            np.argmax(np.asarray(logits[:, -1]), -1), out[:, p + 1]
        )

    # Window + sequence parallelism composes on the ring path: the ring is
    # band-limited to ceil((W-1)/S_local)+1 rotations and reproduces the
    # dense windowed logits.
    strategy = make_inprocess({"data": 2, "seq": 4}, sequence_parallel=True)
    module = GPTLM(config=cfg, batch_size=4)
    strategy.bind_module(module)
    placed = strategy.place_params(params)
    ringed = jax.jit(lambda p, t: module._forward(p, t))(placed, toks)
    np.testing.assert_allclose(
        np.asarray(ringed), np.asarray(windowed), atol=1e-3
    )

    # Sinks ride the seq-parallel path too (the "--modern" config).
    sink_cfg = dataclasses.replace(cfg, attn_sinks=2)
    sink_params = init_gpt_params(jax.random.PRNGKey(0), sink_cfg)
    dense_sink = gpt_forward(sink_params, toks, sink_cfg)
    module_s = GPTLM(config=sink_cfg, batch_size=4)
    strategy.bind_module(module_s)
    placed_s = strategy.place_params(sink_params)
    ringed_sink = jax.jit(lambda p, t: module_s._forward(p, t))(
        placed_s, toks
    )
    np.testing.assert_allclose(
        np.asarray(ringed_sink), np.asarray(dense_sink), atol=1e-3
    )

    # zigzag + window: fails fast at forward entry, pointing at ring.
    zz_cfg = dataclasses.replace(cfg, seq_impl="zigzag")
    module_z = GPTLM(config=zz_cfg, batch_size=4)
    strategy.bind_module(module_z)
    with pytest.raises(ValueError, match="seq_impl='ring'"):
        jax.jit(lambda p, t: module_z._forward(p, t))(placed, toks)


def test_gpt_window_with_sinks_decode():
    """attn_sinks + attn_window: decode matches the full forward."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from ray_lightning_tpu.models.gpt import gpt_generate

    cfg = dataclasses.replace(TINY, attn_window=8, attn_sinks=2)
    params = init_gpt_params(jax.random.PRNGKey(0), cfg)
    prompt = np.asarray([[1, 2, 3, 4, 5]], np.int32)
    out = np.asarray(
        gpt_generate(params, cfg, jnp.asarray(prompt), max_new_tokens=10)
    )
    for p in range(4, 14):
        logits = gpt_forward(params, out[:, : p + 1], cfg)
        np.testing.assert_array_equal(
            np.argmax(np.asarray(logits[:, -1]), -1), out[:, p + 1]
        )


def test_chunked_lm_loss_matches_dense():
    """chunked_lm_loss == lm_loss in value AND grads (incl. padded tail).

    S=15 with chunk=4 exercises the pad-and-mask path."""
    import jax
    import jax.numpy as jnp

    from ray_lightning_tpu.models.gpt import chunked_lm_loss, lm_loss

    params = init_gpt_params(jax.random.PRNGKey(0), TINY)
    toks = jnp.asarray(
        np.random.default_rng(0).integers(0, TINY.vocab_size, (3, 16)),
        jnp.int32,
    )

    def dense(p):
        logits = gpt_forward(p, toks[:, :-1], TINY)
        return lm_loss(logits, toks[:, 1:])

    def chunked(p):
        hidden = gpt_forward(p, toks[:, :-1], TINY, return_hidden=True)
        return chunked_lm_loss(hidden, p["wte"], toks[:, 1:], chunk=4)

    l_d, a_d = dense(params)
    g_d = jax.grad(lambda p: dense(p)[0])(params)
    g_c = jax.grad(lambda p: chunked(p)[0])(params)
    l_c, a_c = jax.jit(chunked)(params)
    np.testing.assert_allclose(float(l_c), float(l_d), rtol=1e-5)
    np.testing.assert_allclose(float(a_c), float(a_d), rtol=1e-6)
    for kd, kc in zip(
        jax.tree_util.tree_leaves(g_d), jax.tree_util.tree_leaves(g_c)
    ):
        np.testing.assert_allclose(
            np.asarray(kc), np.asarray(kd), rtol=2e-4, atol=1e-6
        )


def test_gptlm_fit_with_chunked_loss(start_fabric):
    """End-to-end fit with loss_chunk on, through RayShardedStrategy — the
    strategy the benchmark's training cell and chip_smoke.py run (chunked
    head + ZeRO)."""
    import dataclasses

    from ray_lightning_tpu.strategies import RayShardedStrategy
    from ray_lightning_tpu.trainer import Trainer

    start_fabric(num_cpus=2)
    cfg = dataclasses.replace(TINY, loss_chunk=8)
    module = GPTLM(config=cfg, batch_size=8, n_train=64)
    trainer = Trainer(
        # 3 epochs: at 2 the loss lands within noise of the ln(V) bound
        # on some jax versions' rng/numerics (observed 4.167 vs 4.159).
        max_epochs=3,
        enable_checkpointing=False,
        seed=0,
        num_sanity_val_steps=0,
        strategy=RayShardedStrategy(num_workers=2, use_tpu=False),
    )
    trainer.fit(module)
    metrics = {k: float(v) for k, v in trainer.callback_metrics.items()}
    assert np.isfinite(metrics["loss"])
    assert metrics["loss"] < np.log(TINY.vocab_size)


@pytest.mark.slow
def test_gptlm_fit_gspmd_with_fold(start_fabric, tmp_path):
    """GSPMD (dp x tp) fit with steps_per_execution=2: the stacked
    (K, B, S) batch sharding shifts the per-step spec right by one and
    the folded executable runs under multi-axis shardings."""
    start_fabric(num_cpus=2)
    from tests.utils import get_trainer, train_test

    strategy = GSPMDStrategy(
        num_workers=4,
        use_tpu=False,
        mesh_shape={"data": 2, "model": 2},
    )
    module = GPTLM(config=TINY, batch_size=4, n_train=64)
    trainer = get_trainer(
        strategy=strategy,
        max_epochs=1,
        default_root_dir=str(tmp_path),
        steps_per_execution=2,
    )
    train_test(trainer, module)
    assert trainer.callback_metrics.get("val_loss") is not None


# -- the decode step's cache write (in place into the stacked arrays) ------
DECODE_VARIANTS = {
    "gpt2-mha-learned": {},
    "llama-gqa-rope": dict(
        n_head=4, n_kv_head=2, pos_embed="rope", norm_impl="rmsnorm",
        mlp_variant="swiglu", tie_word_embeddings=False,
    ),
}
#: and one more for the cache of rows below
ROW_VARIANTS = {
    **DECODE_VARIANTS,
    # four KV heads under eight query heads, a window of three and one sink:
    # the band mask hides rows that a wrong mask would read
    "llama-gqa-window-sinks": dict(
        n_head=8, n_kv_head=4, pos_embed="rope", norm_impl="rmsnorm",
        mlp_variant="swiglu", tie_word_embeddings=False, attn_window=3,
        attn_sinks=1,
    ),
}


def _decode_step_case(variant, S=8, B=3, seed=5):
    """Toy decode-step inputs: params, config, one token and one position
    a slot, and two random caches of ``S`` rows (shorter than ``max_seq``,
    so a position past the cache is still a position the model has)."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    cfg = dataclasses.replace(TINY, **ROW_VARIANTS[variant])
    params = init_gpt_params(jax.random.PRNGKey(seed), cfg)
    shape = (cfg.n_layer, B, S, cfg.kv_head, cfg.head_dim)
    kk, kv, kc = jax.random.split(jax.random.PRNGKey(seed + 1), 3)
    k_cache = jax.random.normal(kk, shape, jnp.dtype(cfg.compute_dtype))
    v_cache = jax.random.normal(kv, shape, jnp.dtype(cfg.compute_dtype))
    cur = jax.random.randint(kc, (B,), 0, cfg.vocab_size, jnp.int32)
    return cfg, params, cur, k_cache, v_cache


def _walk_eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else (v,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _walk_eqns(inner)


@pytest.mark.parametrize("variant", sorted(DECODE_VARIANTS))
def test_decode_step_writes_rows_into_the_stacked_cache(variant):
    """Counted from the jaxpr: the step never rebuilds a cache (no
    concatenate — what ``jnp.stack`` lowers to — with the stacked shape),
    writes each cache exactly once a layer, and returns both with the
    shape and dtype they came in with."""
    import jax
    import jax.numpy as jnp

    from ray_lightning_tpu.models.gpt import gpt_decode_step

    cfg, params, cur, k_cache, v_cache = _decode_step_case(variant)
    pos = jnp.asarray([0, 3, 7], jnp.int32)
    closed = jax.make_jaxpr(
        lambda p, c, q, k, v: gpt_decode_step(p, cfg, c, q, k, v)
    )(params, cur, pos, k_cache, v_cache)
    stacked = k_cache.shape
    builds, writes = [], []
    for eqn in _walk_eqns(closed.jaxpr):
        name = eqn.primitive.name
        shapes = [getattr(v.aval, "shape", None) for v in eqn.outvars]
        if stacked not in shapes:
            continue
        if name == "concatenate":
            builds.append(eqn)
        elif name.startswith("scatter") or name == "dynamic_update_slice":
            writes.append(eqn)
    assert builds == []
    assert len(writes) == 2 * cfg.n_layer  # one a layer a cache
    for eqn in writes:
        # B rows of (Hkv, hd) go in, not a layer of the cache
        assert eqn.invars[-1].aval.shape == stacked[1:2] + stacked[3:]
    logits, k_out, v_out = closed.out_avals
    assert logits.shape == (cur.shape[0], cfg.vocab_size)
    for got, want in ((k_out, k_cache), (v_out, v_cache)):
        assert (got.shape, got.dtype) == (want.shape, want.dtype)


@pytest.mark.parametrize("past_end", [0, 3])
@pytest.mark.parametrize("variant", sorted(DECODE_VARIANTS))
def test_decode_step_clamps_a_position_past_the_cache(
    variant, past_end, monkeypatch
):
    """A slot at ``pos == S`` (and ``S + 3``) writes its row at ``S - 1``,
    as the ``dynamic_update_slice`` the step used to write with clamped
    its start: that formulation is kept here as the expected behaviour,
    and caches and logits are compared bit for bit."""
    import jax
    import jax.numpy as jnp

    from ray_lightning_tpu.models import gpt as G

    S = 8
    cfg, params, cur, k_cache, v_cache = _decode_step_case(variant, S=S)
    pos = jnp.asarray([2, S + past_end, S - 1], jnp.int32)

    def step(*a):
        return jax.jit(lambda p, c, q, k, v: G.gpt_decode_step(p, cfg, c, q, k, v))(*a)

    logits, k_out, v_out = step(params, cur, pos, k_cache, v_cache)

    def write_by_slot(cache, li, new, pos):
        # The write before the change: a layer out of the stack, one
        # clamping dynamic_update_slice a slot, the layer back in.
        def one(c, n, p):
            return jax.lax.dynamic_update_slice_in_dim(c, n[None], p, axis=0)

        return cache.at[li].set(jax.vmap(one)(cache[li], new, pos))

    monkeypatch.setattr(layers, "_write_cache_rows", write_by_slot)
    want_logits, want_k, want_v = step(params, cur, pos, k_cache, v_cache)
    np.testing.assert_array_equal(np.asarray(k_out), np.asarray(want_k))
    np.testing.assert_array_equal(np.asarray(v_out), np.asarray(want_v))
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(want_logits))
    # and the row is where the clamp puts it: only row S - 1 of that slot
    # changed, in every layer
    changed = np.any(
        np.asarray(k_out != k_cache), axis=(0, 3, 4)
    )  # (B, S)
    assert changed[1].tolist() == [False] * (S - 1) + [True]
    assert changed[0].tolist() == [i == 2 for i in range(S)]


# -- the decode step on a cache of rows (a position's KV heads side by side) --
#: positions of the three slots: inside the cache, and one slot past its end
#: (the clamp to the last row)
ROW_POSITIONS = {"inside": [0, 3, 7], "past-the-end": [2, 8 + 3, 7]}


def _as_rows(cache):
    """(L, B, S, Hkv, hd) -> (L, B, S, Hkv * hd): the same values."""
    return cache.reshape(cache.shape[:3] + (-1,))


def _row_and_head_outputs(fn, variant, pos, weights="stored", **case_kw):
    """``fn(params, cfg, cur_or_toks, pos, k, v)`` once on the 5-D cache and
    once on the cache of rows holding the same values. Random caches: K
    differs in every KV head, so a wrong head-to-group map cannot pass.
    ``weights="engine"`` gives the run on rows the tree a single-device
    engine holds (``engine_weights``: gate and up apart, ``wq`` / ``wkv``
    flat) and leaves the head-axis run the stored tree."""
    import jax
    import jax.numpy as jnp

    from ray_lightning_tpu.models.gpt import engine_weights

    cfg, params, cur, k_cache, v_cache = _decode_step_case(variant, **case_kw)
    pos = jnp.asarray(pos, jnp.int32)
    held = {"stored": params, "engine": engine_weights(params, cfg)}[weights]
    if weights == "engine":
        assert "wi" not in held["blocks"] and held["blocks"]["wq"].ndim == 3

    def run(p, k, v):
        return jax.jit(lambda p, c, q, k, v: fn(p, cfg, c, q, k, v))(p, cur, pos, k, v)

    return cfg, (k_cache, v_cache), run(params, k_cache, v_cache), run(held, _as_rows(k_cache), _as_rows(v_cache))


def _assert_rows_equal_heads(before, heads_out, rows_out, changed_rows):
    """Logits and written rows to 1e-5 (the first layer's rows, which no
    attention precedes, bit for bit), and only ``changed_rows[b]`` of slot
    b differ from the cache that went in."""
    np.testing.assert_allclose(np.asarray(rows_out[0]), np.asarray(heads_out[0]), rtol=0, atol=1e-5)
    for was, heads, rows in zip(before, heads_out[1:], rows_out[1:]):
        assert rows.shape == _as_rows(was).shape and rows.dtype == was.dtype
        np.testing.assert_array_equal(np.asarray(rows[0]), np.asarray(_as_rows(heads)[0]))
        np.testing.assert_allclose(np.asarray(rows), np.asarray(_as_rows(heads)), rtol=0, atol=1e-5)
        changed = np.any(np.asarray(rows != _as_rows(was)), axis=(0, 3))  # (B, S)
        for b, want in enumerate(changed_rows):
            assert np.flatnonzero(changed[b]).tolist() == sorted(want), (b, changed[b])


#: the re-formed tree beside the stored one, where a GQA / SwiGLU variant has leaves to re-form
ENGINE_WEIGHTS_CASES = [("llama-gqa-rope", "inside", "engine"), ("llama-gqa-window-sinks", "past-the-end", "engine")]


@pytest.mark.parametrize(
    "variant,where,weights",
    [(v, w, "stored") for v in sorted(ROW_VARIANTS) for w in sorted(ROW_POSITIONS)] + ENGINE_WEIGHTS_CASES,
)
def test_decode_step_on_a_cache_of_rows_equals_the_step_on_the_head_axis_cache(variant, where, weights):
    """``weights="engine"``: the step as the single-device engine runs it (a
    cache of rows AND the re-formed tree) against the step on the stored
    tree: the first layer's rows bit for bit — the flat projections sum in
    the einsum's order — and the logits to the file's tolerance."""
    from ray_lightning_tpu.models import gpt as G

    pos = ROW_POSITIONS[where]
    cfg, before, heads_out, rows_out = _row_and_head_outputs(G.gpt_decode_step, variant, pos, weights=weights)
    _assert_rows_equal_heads(before, heads_out, rows_out, [[min(p, 7)] for p in pos])


@pytest.mark.parametrize("variant", sorted(ROW_VARIANTS))
def test_decode_step_on_rows_fails_when_a_query_group_reads_its_neighbours_kv_head(variant, monkeypatch):
    """The planted fault: the block-diagonal layout built with the identity
    shifted by one KV head. The logits then miss by ten thousand times the
    tolerance of the test above (0.26 to 0.36 against 6e-8 read sound)."""
    import jax.numpy as jnp

    from ray_lightning_tpu.models import gpt as G

    monkeypatch.setattr(G, "_kv_head_of_group", lambda n: jnp.roll(jnp.eye(n, dtype=jnp.float32), 1, axis=1))
    _, _, heads_out, rows_out = _row_and_head_outputs(G.gpt_decode_step, variant, ROW_POSITIONS["inside"])
    assert float(np.abs(np.asarray(rows_out[0]) - np.asarray(heads_out[0])).max()) > 0.1


@pytest.mark.parametrize(
    "variant,weights", [(v, "stored") for v in sorted(ROW_VARIANTS)] + [("llama-gqa-rope", "engine")]
)
def test_decode_verify_on_a_cache_of_rows_equals_verify_on_the_head_axis_cache(variant, weights):
    """Three query rows a slot (Q > 1); the last slot's rows run past the
    cache's end and are dropped by the masked write in both layouts."""
    import jax
    import jax.numpy as jnp

    from ray_lightning_tpu.models import gpt as G

    Q, pos = 3, [0, 4, 6]

    def verify(params, cfg, cur, pos, k, v):
        toks = jnp.stack([cur, (cur + 1) % cfg.vocab_size, (cur + 5) % cfg.vocab_size], axis=1)
        return G.gpt_decode_verify(params, cfg, toks, pos, k, v)

    cfg, before, heads_out, rows_out = _row_and_head_outputs(verify, variant, pos, weights=weights)
    assert rows_out[0].shape == (3, Q, cfg.vocab_size)
    _assert_rows_equal_heads(before, heads_out, rows_out, [[r for r in range(p, p + Q) if r < 8] for p in pos])


@pytest.mark.parametrize("variant", sorted(ROW_VARIANTS))
def test_decode_fold_carries_a_cache_of_rows_and_changes_one_row_a_slot_a_step(variant):
    """Three folded steps on a cache of rows: the tokens and the state are
    those of the fold on the 5-D cache, each slot's rows ``pos .. pos + 2``
    change (a frozen slot rewrites the row it stands on) and nothing else."""
    import jax
    import jax.numpy as jnp

    from ray_lightning_tpu.models import gpt as G

    fold, pos = 3, [0, 2, 5]

    def folded(params, cfg, cur, pos, k, v):
        B = cur.shape[0]
        out = G.gpt_decode_fold(
            params, cfg, cur, pos, jax.random.split(jax.random.PRNGKey(9), B), jnp.zeros((B,)),
            jnp.zeros((B,), jnp.int32), jnp.ones((B,)), jnp.asarray([True, True, False]),
            jnp.asarray([5, 2, 4], jnp.int32), jnp.full((B,), -1, jnp.int32), k, v, fold=fold,
        )
        toks, emit, cur, pos, _, active, remaining, k, v = out
        state = [toks, emit, cur[None], pos[None], active[None], remaining[None]]
        return jnp.concatenate([a.astype(jnp.float32) for a in state]), k, v

    _, before, heads_out, rows_out = _row_and_head_outputs(folded, variant, pos)
    # slot 0 runs all three steps, slot 1 freezes after two (then rewrites
    # the row it stopped on), slot 2 is idle and rewrites its own row
    _assert_rows_equal_heads(before, heads_out, rows_out, [[0, 1, 2], [2, 3, 4], [5]])
