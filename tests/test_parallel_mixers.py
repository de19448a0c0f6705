"""A layer with two mixers side by side (``layer_types`` mixer ``"full+ssm"``:
full attention and a Mamba-2 state layer on one normed input, models/mixed.py)
and the muP scalars (``GPTConfig.multipliers``): how the configuration is
described and refused, the state's arithmetic with the scalars on against a
position-by-position evaluation, what a right-padded prompt and a slot's
second request leave in BOTH caches, the modes that refuse it by name, what
the engine counts — and that the three mixed configurations the benchmark
already runs have the trees and the counters they had (a snapshot taken on
the commit before this one: ``tests/data/mixed_trees_pr46.json``). The
comparison with the plain reference is ``tests/perfbench/test_falcon_h1.py``."""
import dataclasses
import functools
import glob
import json
import os

import numpy as np
import pytest

from ray_lightning_tpu.models.gpt import GPTConfig, init_gpt_params

HERE = os.path.dirname(os.path.abspath(__file__))
#: the published ratios at toy widths: 5 query heads a KV head, 2 groups of 16 state heads, every scalar off one
MULT = (3.0, 0.5, 1.25, 0.9, 0.5, 0.8, 0.2, 1.5, 2.0, 1.6, 2.4, 1.2, 1.7, 2.5)
PARALLEL = dict(
    vocab_size=96, n_layer=3, n_head=10, n_kv_head=2, d_model=48, qk_head_dim=8, v_head_dim=8, d_ff=64, max_seq=64,
    pos_embed="rope", rope_theta=1000.0, norm_impl="rmsnorm", mlp_variant="swiglu", tie_word_embeddings=False,
    layer_types=[["full+ssm", "dense"]] * 3,
    ssm_heads=32, ssm_head_dim=2, ssm_groups=2, ssm_state=8, ssm_conv=4, ssm_chunk=8, multipliers=MULT,
)


@pytest.fixture(scope="module")
def cfg():
    return GPTConfig(**PARALLEL)


@pytest.fixture(scope="module")
def params(cfg):
    import jax

    p = init_gpt_params(jax.random.PRNGKey(0), cfg)
    b = p["blocks"]
    # leaves that the program's own initialisation leaves at one and zero, moved off them
    b.update(ssm_A_log=b["ssm_A_log"] + 0.3, ssm_dt_bias=b["ssm_dt_bias"] - 0.2, ssm_D=b["ssm_D"] * 0.7,
             ssm_conv_w=b["ssm_conv_w"] * 30.0, ssm_conv_b=b["ssm_conv_b"] + 0.1)
    return p


# -- how it is described ------------------------------------------------------------------
def test_a_parallel_layer_is_a_layer_of_both_kinds_with_one_norm(cfg, params):
    from ray_lightning_tpu.models.mixed import count_kind, count_part, empty_caches, layer_specs, mixed_param_shapes

    assert cfg.layer_types[0] == ("full+ssm", "dense") and cfg.mixed and hash(cfg) == hash(GPTConfig(**PARALLEL))
    assert [count_kind(cfg, k) for k in ("full", "ssm", "dense", "window", "latent", "experts")] == [3, 3, 3, 0, 0, 0]
    assert count_part(cfg, 0) == count_part(cfg, 1) == 3
    assert [(s.mixer, s.mixer_index, s.side, s.side_index, s.norm1_index, s.mlp_index) for s in layer_specs(cfg)] == [
        ("full", i, "ssm", i, i, i) for i in range(3)]
    shapes = mixed_param_shapes(cfg)["blocks"]
    assert shapes["ln1_g"] == (3, 48) == shapes["ln2_g"]  # ONE norm for the two mixers, one for the MLP
    assert shapes["full_wq"] == (3, 48, 10, 8) and shapes["full_wk"] == (3, 48, 2, 8) and shapes["full_wo"] == (3, 10, 8, 48)
    assert shapes["ssm_wx"] == (3, 48, 64 + 2 * 2 * 8) and shapes["ssm_wo"] == (3, 64, 48)
    assert shapes["dense_wi"] == (3, 2, 48, 64)
    assert {k: tuple(v.shape) for k, v in params["blocks"].items()} == shapes
    import jax.numpy as jnp

    k, v = empty_caches(cfg, 4, 64, jnp.bfloat16)
    assert set(k) == set(v) == {"full", "ssm"}  # the cache pytree keeps its shape: a stack a K/V kind, a leaf a state
    assert k["full"].shape == v["full"].shape == (3, 4, 64, 2 * 8) and len(k["ssm"]) == len(v["ssm"]) == 3
    assert k["ssm"][0].shape == (4, 32, 2, 8) and k["ssm"][0].dtype == jnp.float32 and v["ssm"][0].shape == (3, 4, 96)


def test_a_parallel_layer_may_stand_among_layers_of_one_mixer():
    from ray_lightning_tpu.models.mixed import layer_specs, mixed_param_shapes

    cfg = GPTConfig(**dict(PARALLEL, n_layer=4, layer_types=[["ssm", None], ["full+ssm", "dense"], ["full", "dense"],
                                                                  ["full+ssm", None]]))
    cfg.validate_variants()
    assert [(s.mixer, s.mixer_index, s.side, s.side_index, s.norm1_index) for s in layer_specs(cfg)] == [
        ("ssm", 0, None, 0, 0), ("full", 0, "ssm", 1, 1), ("full", 1, None, 0, 2), ("full", 2, "ssm", 2, 3)]
    shapes = mixed_param_shapes(cfg)["blocks"]
    assert shapes["ln1_g"][0] == 4 and shapes["ln2_g"][0] == 2 and shapes["full_wq"][0] == 3 == shapes["ssm_wz"][0]


@pytest.mark.parametrize("change,says", [
    (dict(layer_types=[["ssm+full", "dense"]] * 3), "layer_types entry"),
    (dict(layer_types=[["full+window", "dense"]] * 3), "layer_types entry"),
    (dict(ssm_state=0), "state layers need.*the state half of a 'full\\+ssm' layer is one"),
    (dict(ssm_heads=0), "state layers need"),
    (dict(n_kv_head=3), "divisible by the full layers' KV heads.*the attention half of 'full\\+ssm'"),
    (dict(multipliers=MULT[:12]), "multipliers has 12 values"),
    (dict(multipliers=MULT[:13] + (0.0,)), "finite, non-zero"),
    (dict(multipliers=MULT[:13] + (float("nan"),)), "finite, non-zero"),
    (dict(mlp_variant="gelu"), "SwiGLU or relu2"),
    (dict(tie_word_embeddings=True), "untied head"),
])
def test_a_parallel_configuration_that_cannot_run_says_what_is_wrong(change, says):
    with pytest.raises(ValueError, match=says):
        GPTConfig(**dict(PARALLEL, **change)).validate_variants()


def test_the_scalars_need_layer_types_and_default_to_nothing():
    with pytest.raises(ValueError, match="multipliers.*need layer_types"):
        GPTConfig(multipliers=MULT).validate_variants()
    plain = GPTConfig(**dict(PARALLEL, multipliers=()))
    plain.validate_variants()
    assert all(plain.multiplier(n) == 1.0 for n in GPTConfig.MULTIPLIERS) and len(GPTConfig.MULTIPLIERS) == 14
    cfg = GPTConfig(**dict(PARALLEL, multipliers=list(MULT)))  # JSON hands a list over
    assert cfg.multipliers == MULT and cfg.multiplier("key") == 0.5 and cfg.multiplier("mlp_out") == 2.5


# -- the state half's arithmetic, scalars on ---------------------------------------------------
def _state_leaves(params, i=0):
    return {k[len("ssm_"):]: v[i] for k, v in params["blocks"].items() if k.startswith("ssm_")}


def test_the_scaled_state_layer_is_the_plain_one_on_scaled_weights_and_steps_as_it_scans(cfg, params):
    """The five in-projection scalars, the input's and the output's: the
    layer with them on equals the layer without them whose ``wz``, the
    three parts of ``wx``, ``wdt`` and ``wo`` carry the scalars (3e-6 of
    the largest output, float32); and ``ssm_rows`` over 21 rows (two
    whole chunks and a part) is ``ssm_step`` fed the rows one by one, in
    output, state and conv tail — one recurrence, no second copy."""
    import jax
    import jax.numpy as jnp

    from ray_lightning_tpu.models import ssm

    lp = _state_leaves(params)
    u = jax.random.normal(jax.random.PRNGKey(1), (2, 21, 48), jnp.float32)
    out, state, tail = ssm.ssm_rows(u, lp, cfg)
    m = dict(zip(GPTConfig.MULTIPLIERS, MULT))
    di, gn = 64, 16
    cols = np.repeat([m["ssm_x"], m["ssm_b"], m["ssm_c"]], [di, gn, gn]).astype(np.float32)
    folded = dict(lp, wz=lp["wz"] * (m["ssm_in"] * m["ssm_z"]), wx=lp["wx"] * (m["ssm_in"] * cols),
                  wdt=lp["wdt"] * (m["ssm_in"] * m["ssm_dt"]), wo=lp["wo"] * m["ssm_out"])
    plain = dataclasses.replace(cfg, multipliers=())
    want, want_state, want_tail = ssm.ssm_rows(u, folded, plain)
    top = float(jnp.abs(want).max())
    assert float(jnp.abs(out - want).max()) < 3e-6 * top and top > 1e-3
    assert float(jnp.abs(state - want_state).max()) < 3e-6 * float(jnp.abs(want_state).max())
    assert float(jnp.abs(tail - want_tail).max()) < 3e-6 * float(jnp.abs(want_tail).max())
    unscaled = ssm.ssm_rows(u, lp, plain)[0]
    assert float(jnp.abs(unscaled - want).max()) > 0.1 * top  # the scalars are not a rounding
    s, t = ssm.empty_state(cfg, 2, jnp.float32)
    outs = []
    for i in range(21):
        o, s, t = ssm.ssm_step(u[:, i:i + 1], lp, cfg, s, t)
        outs.append(o)
    assert float(jnp.abs(out - jnp.concatenate(outs, axis=1)).max()) < 1e-5 * top
    assert float(jnp.abs(state - s).max()) < 1e-5 * float(jnp.abs(s).max()) and float(jnp.abs(tail - t).max()) == 0.0


# -- what a prompt leaves in both caches ---------------------------------------------------------
@pytest.fixture(scope="module")
def rows_pass(params, cfg):
    """The admission's pass over a right-padded prompt, one program a bucket."""
    import jax

    from ray_lightning_tpu.models.mixed import mixed_rows

    return jax.jit(lambda rows, n: mixed_rows(params, cfg, rows, true_len=n))


def _admit(rows_pass, k_cache, v_cache, prompt, bucket, slot):
    import jax.numpy as jnp

    from ray_lightning_tpu.models.mixed import write_prefill_rows

    rows = np.zeros((1, bucket), np.int32)
    rows[0, :len(prompt)] = prompt
    h, pf_k, pf_v, counts = rows_pass(jnp.asarray(rows), jnp.int32(len(prompt)))
    assert set(pf_k) == {"full", "ssm"} and [int(c) for c in counts[3:]] == [bucket, len(prompt)]
    return write_prefill_rows(k_cache, v_cache, pf_k, pf_v, jnp.int32(slot), jnp.int32(len(prompt))), h


def test_a_padded_prompt_leaves_what_the_unpadded_one_leaves_and_a_slots_second_request_nothing_of_the_first(cfg, rows_pass):
    """Slot 0 takes a prompt of 8 tokens in a bucket of 8 (no padding),
    slot 1 the same prompt right-padded to 32: every state, every conv
    tail and the first 8 K and V rows of every layer agree. Then slot 1
    takes a SHORTER prompt after a longer one: its states and tails are
    the ones a fresh slot gets (slot 2), and its K/V rows up to the
    prompt's end are too — the rows past it are the first request's, and
    lie behind the position mask."""
    import jax.numpy as jnp

    from ray_lightning_tpu.models.mixed import empty_caches

    rng = np.random.default_rng(2)
    prompt, long_prompt, short = rng.integers(0, 96, 8), rng.integers(0, 96, 29), rng.integers(0, 96, 5)
    k, v = empty_caches(cfg, 3, 64, jnp.float32)
    (k, v), h0 = _admit(rows_pass, k, v, prompt, 8, 0)
    (k, v), h1 = _admit(rows_pass, k, v, prompt, 32, 1)
    assert float(jnp.abs(h0[0] - h1[0, :8]).max()) < 1e-5
    for i in range(3):
        state, tail = k["ssm"][i], v["ssm"][i]  # (slots, H, P, N) and (taps - 1, slots, channels)
        assert float(jnp.abs(state[0] - state[1]).max()) < 1e-6 * float(jnp.abs(state[0]).max())
        assert float(jnp.abs(tail[:, 0] - tail[:, 1]).max()) < 1e-6 and float(jnp.abs(tail[:, 0]).max()) > 0
        for half in (k, v):
            assert float(jnp.abs(half["full"][i, 0, :8] - half["full"][i, 1, :8]).max()) < 1e-6
    (k, v), _ = _admit(rows_pass, k, v, long_prompt, 32, 1)
    (k, v), _ = _admit(rows_pass, k, v, short, 8, 1)
    (k, v), _ = _admit(rows_pass, k, v, short, 8, 2)
    for i in range(3):
        assert float(jnp.abs(k["ssm"][i][1] - k["ssm"][i][2]).max()) == 0.0  # the state is written whole
        assert float(jnp.abs(v["ssm"][i][:, 1] - v["ssm"][i][:, 2]).max()) == 0.0  # and the conv tail
        for half in (k, v):
            assert float(jnp.abs(half["full"][i, 1, :5] - half["full"][i, 2, :5]).max()) == 0.0


def test_decode_after_a_slots_second_request_is_decode_in_a_fresh_slot(cfg, params, rows_pass):
    """The step reads nothing of the first request: slot 1 (a long
    request, then a short one) and slot 2 (the short one alone) give the
    same logits for six decoded tokens, in both caches' terms."""
    import jax
    import jax.numpy as jnp

    from ray_lightning_tpu.models.mixed import empty_caches, mixed_decode_step

    rng = np.random.default_rng(3)
    long_prompt, short = rng.integers(0, 96, 29), rng.integers(0, 96, 5)
    k, v = empty_caches(cfg, 3, 64, jnp.float32)
    (k, v), _ = _admit(rows_pass, k, v, long_prompt, 32, 1)
    (k, v), _ = _admit(rows_pass, k, v, short, 8, 1)
    (k, v), _ = _admit(rows_pass, k, v, short, 8, 2)
    step = jax.jit(lambda cur, pos, k, v, act: mixed_decode_step(params, cfg, cur, pos, k, v, active=act))
    active = jnp.asarray([False, True, True])
    for t in range(6):
        cur, pos = jnp.asarray([0, 7 + t, 7 + t], jnp.int32), jnp.asarray([0, 5 + t, 5 + t], jnp.int32)
        logits, k, v, _ = step(cur, pos, k, v, active)
        assert float(jnp.abs(logits[1] - logits[2]).max()) < 1e-5 * float(jnp.abs(logits[2]).max())


# -- the modes that refuse it ----------------------------------------------------------------
@pytest.mark.parametrize("name,kw", [
    ("paged KV cache", dict(kv_pages=16, kv_page=16)),
    ("prefix pool", dict(prefix_blocks=4)),
    ("chunked prefill", dict(prefill_chunk=16)),
    ("piggybacked prefill chunks", dict(piggyback_chunks=1)),
    ("speculative decoding", dict(spec="ngram")),
])
def test_the_engine_refuses_for_a_parallel_layer_what_it_refuses_for_any_mixed_configuration(params, cfg, name, kw):
    from ray_lightning_tpu.serve.engine import DecodeEngine

    with pytest.raises(ValueError, match=f"{name}.*does not run.*would need a snapshot of the state"):
        DecodeEngine(params, cfg, num_slots=2, max_seq=64, prefill_buckets=[16], **kw)


# -- what the engine counts --------------------------------------------------------------------
def test_the_engine_counts_three_state_parts_and_three_attention_parts_a_token_step(params, cfg):
    """Three parallel layers: ``stats()["ssm"]`` says three state layers
    and counts a slot-step once a slot and token step (the cache's bytes
    are the three states' and tails'), ``stats()["attn"]`` counts the rows
    of three full layers a token step — both count EVERY layer."""
    from ray_lightning_tpu.serve.engine import DecodeEngine

    eng = DecodeEngine(params, cfg, num_slots=2, max_seq=64, prefill_buckets=[8, 32], decode_fold=2)
    rng = np.random.default_rng(5)
    reqs = [dict(prompt=rng.integers(0, 96, n).tolist(), request_id=f"r{i}", max_new_tokens=m)
            for i, (n, m) in enumerate([(5, 7), (20, 4)])]
    got = {r["request_id"]: 1 for r in reqs}
    eng.admit_many(reqs)
    for _ in range(20):
        for _, rid, _, _ in eng.step():
            got[rid] += 1
        if eng.num_active == 0:
            break
    assert got == {"r0": 7, "r1": 4}
    ssm, attn, cache = eng.ssm_stats(), eng.attn_stats(), eng.cache_stats()
    assert ssm["state_layers"] == 3 and ssm["prefill"] == {"rows_scanned": 8 + 32, "rows_real": 25}
    steps = ssm["decode"]["slot_steps"] // 2  # token steps: both slots are lanes of each
    assert ssm["decode"]["slot_steps_live"] == 6 + 3 and steps >= 6 and ssm["decode"]["slot_steps"] % 4 == 0
    assert ssm["decode"]["slot_steps_visited"] == ssm["decode"]["slot_steps"]  # the XLA pass, here on the CPU
    assert attn["rows_allocated"] == steps * 3 * 2 * 64  # three full layers' rows a token step
    assert attn["rows_visited"] == attn["rows_allocated"]  # the XLA read, here on the CPU
    # the rows a live slot's step stands on: positions 0 .. pos, a layer
    assert attn["rows_live"] == 3 * (sum(range(6, 12)) + sum(range(21, 24)))
    assert set(cache) == {"full", "state"} and cache["full"]["layers"] == cache["state"]["layers"] == 3
    assert cache["state"]["bytes"] == 2 * 3 * (32 * 2 * 8 * 4 + 3 * 96 * 4)
    assert eng.moe_stats() == {}


@pytest.fixture(scope="module")
def under_both_updates():
    """Two parallel layers whose state is wide enough for the kernel
    (``ssm_state`` 128, heads of 8 rows) through the engine's fold, twice:
    as it runs here (the XLA pass) and told a TPU (the kernel, interpreted).
    ``{update: (tokens by request, stats()["ssm"]["decode"])}``."""
    import jax

    from ray_lightning_tpu.models import ssm
    from ray_lightning_tpu.serve.engine import DecodeEngine

    cfg = GPTConfig(**dict(PARALLEL, n_layer=2, layer_types=[["full+ssm", "dense"]] * 2, ssm_heads=4, ssm_head_dim=8,
                           ssm_state=128))
    params = init_gpt_params(jax.random.PRNGKey(0), cfg)
    out = {}
    for update in ("xla", "kernel"):
        with pytest.MonkeyPatch.context() as mp:
            if update == "kernel":
                mp.setattr(ssm, "_step_heads", functools.partial(ssm._step_heads, backend="tpu"))
            eng = DecodeEngine(params, cfg, num_slots=3, max_seq=64, prefill_buckets=[8], decode_fold=2)
            rng = np.random.default_rng(5)
            reqs = [dict(prompt=rng.integers(0, 96, n).tolist(), request_id=f"r{i}", max_new_tokens=m)
                    for i, (n, m) in enumerate([(5, 7), (3, 4)])]
            got = {r["request_id"]: [] for r in reqs}
            eng.admit_many(reqs)
            for _ in range(20):
                for _, rid, tok, _ in eng.step():
                    got[rid].append(tok)
                if eng.num_active == 0:
                    break
            out[update] = (got, eng.ssm_stats()["decode"])
    return out


@pytest.mark.parametrize("update", ["xla", "kernel"])
def test_a_parallel_layers_state_half_visits_the_live_slots_under_the_kernel(under_both_updates, update):
    """Under the kernel the state half visits the live slot-steps and no
    other, on the XLA pass every one; ``slot_steps`` and ``slot_steps_live``
    read the same under both, to the number, and so do the tokens."""
    tokens, d = under_both_updates[update]
    assert d["slot_steps_live"] == 6 + 3 and d["slot_steps"] % 6 == 0 and d["slot_steps"] >= 3 * 6
    assert d["slot_steps_visited"] == (d["slot_steps_live"] if update == "kernel" else d["slot_steps"])
    other_tokens, other = under_both_updates["xla" if update == "kernel" else "kernel"]
    assert tokens == other_tokens and [len(v) for v in tokens.values()] == [6, 3]
    assert (d["slot_steps"], d["slot_steps_live"]) == (other["slot_steps"], other["slot_steps_live"])


# -- the configurations the benchmark already runs ------------------------------------------------
@pytest.mark.parametrize("toy", ["toy_mimo", "toy_nemotron", "toy_kanana"])
def test_a_mixed_configuration_of_the_benchmark_has_the_trees_and_counters_it_had(toy):
    """The parameter tree, both halves of the cache tree and every key of
    ``stats()``'s four groups (with the state and attention counts of one
    admission and four steps) for the toy roots' three mixed
    configurations, against the snapshot the commit before the parallel
    layer wrote with this same code."""
    import jax
    import jax.numpy as jnp

    from ray_lightning_tpu.models import mixed
    from ray_lightning_tpu.serve.engine import DecodeEngine

    with open(os.path.join(HERE, "data", "mixed_trees_pr46.json")) as f:
        want = json.load(f)[toy]
    path, = glob.glob(os.path.join(HERE, "perfbench", toy, "configs", "*.json"))
    with open(path) as f:
        cfg = GPTConfig(**json.load(f)["program_config"])
    assert not cfg.multipliers and all(s.side is None for s in mixed.layer_specs(cfg))

    def keys(d):
        return {k: keys(v) for k, v in d.items()} if isinstance(d, dict) else None

    def tree(t):
        return json.loads(json.dumps(jax.tree_util.tree_map(lambda a: [list(a.shape), str(a.dtype)], t)))

    shapes = mixed.mixed_param_shapes(cfg)
    assert {k: (list(v) if k != "blocks" else {kk: list(vv) for kk, vv in v.items()})
            for k, v in shapes.items()} == want["params"]
    kc, vc = jax.eval_shape(lambda: mixed.empty_caches(cfg, 3, 32, jnp.float32))
    assert tree(kc) == want["k_cache"] and tree(vc) == want["v_cache"]
    eng = DecodeEngine(init_gpt_params(jax.random.PRNGKey(0), cfg), cfg, num_slots=2, max_seq=32,
                       prefill_buckets=[8], decode_fold=1)
    eng.admit_many([dict(prompt=[1, 2, 3], request_id="a", max_new_tokens=3)])
    for _ in range(4):
        eng.step()
    stats = {"moe": eng.moe_stats(), "ssm": eng.ssm_stats(), "attn": eng.attn_stats(), "cache": eng.cache_stats()}
    if stats["ssm"]:
        # the one key since the snapshot (PR 48): off a TPU the XLA pass moves every slot's state
        assert stats["ssm"]["decode"].pop("slot_steps_visited") == stats["ssm"]["decode"]["slot_steps"]
    # the four keys since the snapshot (PR 49): one admission of a bucket of 8, off a TPU the XLA read's
    layers = sum(mixed.count_kind(cfg, kind) for kind in mixed.ATTN_KINDS)
    square = sum(mixed.count_kind(cfg, kind) for kind in ("full", "latent"))
    prefill = [stats["attn"].pop("prefill_" + key) for key in ("rows", "rows_kernel", "tiles", "tiles_visited")]
    assert prefill == [8 * layers, 0, square, square]
    assert {k: keys(v) for k, v in stats.items()} == want["stats"]
    assert {"ssm": stats["ssm"], "attn": stats["attn"]} == want["counts"]


@pytest.mark.parametrize("toy,program", [
    ("toy_mimo", "fold"), ("toy_mimo", "admission"), ("toy_kanana", "fold"), ("toy_kanana", "admission"),
    ("toy_nemotron", "admission"), ("toy_falcon_h1", "admission"),
])
def test_a_program_without_a_state_layers_decode_step_is_the_one_it_was(toy, program):
    """The jaxpr of the toy roots' decode fold and admission, to the byte
    (sha256 of its text), against what the commit before the state
    layers' decode kernel traced with this same code (PR 47's tree:
    ``tests/data/mixed_jaxprs_pr47.json``): the kernel, the live mask it
    takes and the seventh count are in the fold of a configuration WITH
    state layers and in no other program — the mimo and kanana cells'
    folds and every configuration's admission are the parent's."""
    from tests.utils import mixed_program_hashes

    with open(os.path.join(HERE, "data", "mixed_jaxprs_pr47.json")) as f:
        want = json.load(f)[toy][program]
    path, = glob.glob(os.path.join(HERE, "perfbench", toy, "configs", "*.json"))
    with open(path) as f:
        cfg = GPTConfig(**json.load(f)["program_config"])
    assert mixed_program_hashes(cfg)[program] == want

