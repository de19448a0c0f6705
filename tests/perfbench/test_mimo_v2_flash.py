"""The ``mimo_v2_flash`` family's plain reference against the program at a
toy size on the CPU, float32 on both sides: the forward pass, bucketed
prefill then decode through both caches (model functions and the dense
engine), the same comparison under a lower precision and under each term
of the mathematics planted out of the reference, the shares of the expert
layer adding up to the whole, and the toy root's rehearsal."""
import dataclasses
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from pb import reference, weights
from pb.spec import Spec

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TOY = os.path.join(HERE, "toy_mimo")
CFG = json.load(open(os.path.join(TOY, "configs", "toy-mimo.json")))
V, W = CFG["vocab_size"], CFG["sliding_window"]
#: float32 on both sides and the same operations in another order: what is
#: left is rounding, 1e-7 of the logits' norm here. 1e-5 leaves two orders
#: of room and lies more than an order under the least of the planted
#: faults (2.7e-4: the wrong rotary base over a window of 8 positions; the
#: others give 3e-3 to 1e-1).
TOL = 1e-5


def _dims(cfg=CFG):
    return Spec(ROOT).dims(cfg)


def _program_cfg(**over):
    from ray_lightning_tpu.models.gpt import GPTConfig

    return dataclasses.replace(GPTConfig(**CFG["program_config"]), **over)


def _rel(a, ref):
    return float(np.linalg.norm(np.asarray(a) - np.asarray(ref)) / np.linalg.norm(np.asarray(ref)))


@pytest.fixture(scope="module")
def params():
    return weights.make_params(2**31 + 11, _dims(), 128, "float32")


def test_the_seeded_tree_is_the_tree_the_program_takes(params):
    from ray_lightning_tpu.models.mixed import mixed_param_shapes

    want = mixed_param_shapes(_program_cfg())
    assert {k: tuple(v.shape) for k, v in params["blocks"].items()} == want["blocks"]
    assert {k: tuple(v.shape) for k, v in params.items() if k != "blocks"} == {
        k: v for k, v in want.items() if k != "blocks"}
    # no leaf is exactly one or zero: each term moves the result
    assert all(float(np.abs(np.asarray(v) - np.round(np.asarray(v))).max()) > 0 for v in params["blocks"].values())


def test_forward_agrees_and_a_lower_precision_does_not(params):
    import jax.numpy as jnp

    from ray_lightning_tpu.models.gpt import gpt_forward

    dims = _dims()
    toks = jnp.asarray(np.random.default_rng(0).integers(0, V, (2, 37)), jnp.int32)
    ref = reference.logits_of(params, toks, dims)
    assert _rel(gpt_forward(params, toks, _program_cfg()), ref) < TOL
    assert _rel(gpt_forward(params, toks, _program_cfg(compute_dtype="bfloat16")), ref) > 1e-3
    assert _rel(reference.logits_of(params, toks, dims, lowp=True), ref) > 1e-2


# -- prefill, then decode through both caches ------------------------------------
#: (prompt length, bucket, tokens decoded): shorter than the window, longer
#: than it, and one that wraps the ring of W = 8 rows seven times; the
#: second slot stops early and stays frozen while the others go on.
CASES = [(5, 8, 60), (40, 64, 9), (13, 16, 50)]


@pytest.fixture(scope="module")
def served_logits(params):
    """The program's logits at every position of three sequences of given
    tokens: bucketed prefill into a slot each, then decode steps at
    per-slot positions through the full cache and the ring, the second
    slot frozen after its 9 tokens."""
    import jax
    import jax.numpy as jnp

    from ray_lightning_tpu.models.gpt import _lm_head, _rmsnorm
    from ray_lightning_tpu.models.mixed import empty_caches, mixed_decode_step, mixed_rows, write_prefill_rows

    cfg = _program_cfg()
    rng = np.random.default_rng(3)
    seqs = [rng.integers(0, V, P + n).astype(np.int32) for P, _, n in CASES]
    k_cache, v_cache = empty_caches(cfg, len(CASES), 128, jnp.float32)
    got = [np.zeros((len(s), V), np.float32) for s in seqs]
    for slot, ((P, Pb, _), seq) in enumerate(zip(CASES, seqs)):
        prompt = np.zeros((1, Pb), np.int32)
        prompt[0, :P] = seq[:P]
        h, pf_k, pf_v, _ = mixed_rows(params, cfg, jnp.asarray(prompt), true_len=jnp.int32(P))
        k_cache, v_cache = write_prefill_rows(k_cache, v_cache, pf_k, pf_v, jnp.int32(slot), jnp.int32(P))
        got[slot][:P] = np.asarray(_lm_head(_rmsnorm(h[0, :P], params["lnf_g"], cfg.norm_eps), params["lm_head"]))
    step = jax.jit(lambda cur, pos, k, v, act: mixed_decode_step(params, cfg, cur, pos, k, v, active=act))
    pos = np.array([P for P, _, _ in CASES], np.int32)
    ends = np.array([len(s) for s in seqs], np.int32)
    while (pos < ends).any():
        active = pos < ends
        cur = np.array([s[min(p, len(s) - 1)] for s, p in zip(seqs, pos)], np.int32)
        logits, k_cache, v_cache, _ = step(jnp.asarray(cur), jnp.asarray(pos), k_cache, v_cache, jnp.asarray(active))
        for slot in np.nonzero(active)[0]:
            got[slot][pos[slot]] = np.asarray(logits[slot])
        pos = np.where(active, pos + 1, pos)  # a frozen slot writes its row again and again
    return seqs, got


def _reference_logits(params, seqs, dims, lowp=False):
    import jax.numpy as jnp

    out = []
    for s in seqs:
        toks = np.zeros((1, 128), np.int32)
        toks[0, : len(s)] = s
        out.append(np.asarray(reference.logits_of(params, jnp.asarray(toks), dims, lowp)[0, : len(s)]))
    return out


def test_prefill_and_decode_through_both_caches_agree_with_the_full_forward(params, served_logits):
    seqs, got = served_logits
    for g, r in zip(got, _reference_logits(params, seqs, _dims())):
        assert _rel(g, r) < TOL
        # position by position too: one wrong ring row would hide in a norm
        assert np.abs(g - r).max() < 1e-4 * np.abs(r).max()


def _no_correction_bias(p):
    return dict(p, blocks=dict(p["blocks"], moe_router_bias=np.zeros_like(p["blocks"]["moe_router_bias"])))


def _route_normalised_over_held(fam):
    import jax
    import jax.numpy as jnp

    def route(h2, wr, c, dims, lowp):
        sigma = jax.nn.sigmoid(reference.mm("td,de->te", h2, wr, lowp))
        _, top = jax.lax.top_k(sigma + c, dims["top_k"])
        chosen = jnp.zeros_like(sigma).at[jnp.arange(sigma.shape[0])[:, None], top].set(1.0)
        first, count = dims["experts_held"]
        held = (jnp.arange(sigma.shape[1]) >= first) & (jnp.arange(sigma.shape[1]) < first + count)
        w = sigma * chosen * held
        return w / jnp.maximum(jnp.sum(w, -1, keepdims=True), 1e-9)

    return route


#: name -> (change to the sizes, change to the weights, replacement piece)
PLANTED = {
    "float8 (the control)": ({}, None, None),
    "no sink logit": ({"swa_sink": False}, None, None),
    "rotary over the whole head": ({"rope_dim": CFG["head_dim"]}, None, None),
    "the full layers' theta in window layers": ({"swa_rope_theta": float(CFG["rope_theta"])}, None, None),
    "top-k of sigma without the correction bias": ({}, _no_correction_bias, None),
    "weights normalised over held experts only": ({}, None, _route_normalised_over_held),
    "value scale dropped": ({"value_scale": 1.0}, None, None),
    "a window of one less": ({"window": W - 1}, None, None),
    "a window of one more": ({"window": W + 1}, None, None),
}


@pytest.mark.parametrize("name", list(PLANTED))
def test_the_comparison_fails_with_a_term_planted_out_of_the_reference(params, served_logits, monkeypatch, name):
    from pb import plug

    change, reweigh, piece = PLANTED[name]
    dims = dict(_dims(), **change)
    if piece is not None:
        fam = plug.family_of(dims)
        monkeypatch.setattr(fam, "route", piece(fam))
    seqs, got = served_logits
    refs = _reference_logits(reweigh(params) if reweigh else params, seqs, dims, lowp=name.startswith("float8"))
    worst = max(_rel(g, r) for g, r in zip(got, refs))
    print(f"planted {name!r}: {worst:.3g}")
    assert worst > 10 * TOL, name


@pytest.mark.parametrize("fold", [1, 4])
def test_the_dense_engine_serves_what_the_reference_puts_first(params, fold):
    """Bucketed admission, decode fold ``fold``, a request that ends early
    (its slot frozen, then taken again): every served token is the
    reference's first choice at its position, and the counts that left the
    device with the tokens add up."""
    from ray_lightning_tpu.serve.engine import DecodeEngine

    eng = DecodeEngine(params, _program_cfg(), num_slots=3, max_seq=128, prefill_buckets=[8, 16, 64], decode_fold=fold)
    compiled = eng.compiled_count
    rng = np.random.default_rng(1)
    reqs = [dict(prompt=rng.integers(0, V, P).tolist(), request_id=f"r{i}", max_new_tokens=n)
            for i, (P, n) in enumerate([(5, 60), (40, 9), (13, 50), (9, 12)])]
    outs = {r["request_id"]: [] for r in reqs}
    for r, (_, tok, _) in zip(reqs[:3], eng.admit_many(reqs[:3])):
        outs[r["request_id"]].append(tok)
    late = reqs[3]
    for _ in range(200):
        for _, rid, tok, _ in eng.step():
            outs[rid].append(tok)
        if late is not None and eng.free_slots():
            outs[late["request_id"]].append(eng.admit_many([late])[0][1])
            late = None
        if late is None and eng.num_active == 0:
            break
    assert [len(outs[r["request_id"]]) for r in reqs] == [60, 9, 50, 12]
    assert eng.compiled_count == compiled
    res = reference.serve_reference(
        params, [{"prompt": r["prompt"], "tokens": outs[r["request_id"]]} for r in reqs], _dims(), pad_to=128)
    assert res["widest_gap"] <= 1e-5 and res["greedy_agree_share"] == 1.0
    moe, layers, k = eng.moe_stats(), 3, CFG["num_experts_per_tok"]
    assert moe["decode"]["pairs_routed"] == (59 + 8 + 49 + 11) * layers * k
    assert moe["prefill"]["pairs_routed"] == (5 + 40 + 13 + 9) * layers * k and moe["prefill"]["admissions"] == 4
    assert 0 < moe["decode"]["pairs_held"] < moe["decode"]["pairs_routed"]
    assert 0 < moe["decode"]["experts_hit"] <= moe["decode"]["token_steps"] * layers * 8
    cache = eng.cache_stats()
    assert (cache["full"]["rows_per_slot"], cache["window"]["rows_per_slot"]) == (128, W)
    assert cache["full"]["layers"] == cache["window"]["layers"] == 2


# -- the share and the whole --------------------------------------------------------
def test_the_sixteen_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """``experts_held = (2 r, 2)``, r = 0..15, of 32 experts: the outputs of
    the program's expert layer add up to what the reference gives for the
    whole layer (the router over all experts, nothing counted twice), and
    the pairs that landed on the shares are all the pairs routed."""
    import jax
    import jax.numpy as jnp

    from pb import plug
    from ray_lightning_tpu.parallel.moe import moe_ffn_held

    dims = dict(_dims(), experts_held=[0, 32])
    fam = plug.family_of(dims)
    D, E, F, T = dims["d"], 32, dims["expert_ff"], 50
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    x = jax.random.normal(ks[0], (T, D), jnp.float32)
    router = 0.2 * jax.random.normal(ks[1], (D, E), jnp.float32)
    bias = 0.05 * jax.random.normal(ks[2], (E,), jnp.float32)
    wi = 0.1 * jax.random.normal(ks[3], (E, 2, D, F), jnp.float32)
    wo = 0.1 * jax.random.normal(ks[4], (E, F, D), jnp.float32)
    whole = fam.experts(x, fam.route(x, router, bias, dims, False), wi, wo, dims, False)
    total, held_pairs, routed = jnp.zeros_like(whole), 0, None
    for r in range(16):
        out, stats = moe_ffn_held(
            {"router": router, "router_bias": bias, "wi": wi[2 * r: 2 * r + 2], "wo": wo[2 * r: 2 * r + 2]},
            x, held=(2 * r, 2), top_k=dims["top_k"], scoring="sigmoid")
        total = total + out
        held_pairs += int(stats[1])
        routed = int(stats[0])
    assert _rel(total, whole) < TOL
    assert routed == T * dims["top_k"] and 100.0 * held_pairs / routed == 100.0


# -- the toy root: the family, the readers and the counters down the harness's path ---
def test_the_toy_root_rehearses_with_the_new_readers():
    env = {k: v for k, v in os.environ.items() if k != "BENCH_RUN"}
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "run.py"), "--bench-root", TOY, "--rehearse",
         "--workload", "toy-mimo.serve-mixedlen", "--seed", str(2**31 + 17), "--seconds", "3", "--trace", "1"],
        cwd=ROOT, env=env, timeout=900, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    assert "REHEARSAL finished: correct=True" in p.stdout and "leftovers: none" in p.stdout
    for said in ("held experts hit a step and expert layer: ", "pairs on held experts: "):
        assert said in p.stdout, (said, p.stdout[-3000:])
