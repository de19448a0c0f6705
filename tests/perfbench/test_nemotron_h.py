"""The ``nemotron_h`` family's plain reference against the program at a toy
size on the CPU, float32 on both sides: the forward pass, bucketed prefill
then decode through the running state, the conv tail and the K/V cache
(model functions and the dense engine), the same comparison under a lower
precision and with each term of the mathematics planted out in turn, the
shares of the expert layer adding up to the whole with the shared expert
counted once, and the toy root's rehearsal."""
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
TOY = os.path.join(HERE, "toy_nemotron")
CFG = json.load(open(os.path.join(TOY, "configs", "toy-nemotron.json")))
V, Q = CFG["vocab_size"], CFG["chunk_size"]
#: float32 on both sides; the program evaluates the recurrence in chunks
#: (sums of exp(cum_q - cum_s) terms) where the reference multiplies decays
#: step by step, and groups the experts' rows otherwise: what is left is
#: rounding, 4.0e-7 to 4.9e-7 of the logits' norm over the six served
#: sequences and 4.3e-7 in the forward pass. 3e-6 leaves six times of room
#: and lies at a nineteenth of the least of the planted faults (5.8e-5: the
#: routed scale dropped — at toy widths the routed experts' part of a layer
#: is small; the others read 1.7e-4 to 1.3: the planted test prints them).
TOL = 3e-6


def _dims(cfg=CFG):
    return Spec(ROOT).dims(cfg)


def _program_cfg(**over):
    from ray_lightning_tpu.models.gpt import GPTConfig

    return dataclasses.replace(GPTConfig(**CFG["program_config"]), **over)


def _rel(a, ref):
    return float(np.linalg.norm(np.asarray(a) - np.asarray(ref)) / np.linalg.norm(np.asarray(ref)))


@pytest.fixture(scope="module")
def params():
    return weights.make_params(2**31 + 23, _dims(), 96, "float32")


def test_the_seeded_tree_is_the_tree_the_program_takes(params):
    from ray_lightning_tpu.models.mixed import mixed_param_shapes

    want = mixed_param_shapes(_program_cfg())
    assert {k: tuple(v.shape) for k, v in params["blocks"].items()} == want["blocks"]
    assert {k: tuple(v.shape) for k, v in params.items() if k != "blocks"} == {
        k: v for k, v in want.items() if k != "blocks"}
    # no leaf is exactly one or zero: each term moves the result
    assert all(float(np.abs(np.asarray(v) - np.round(np.asarray(v))).max()) > 0 for v in params["blocks"].values())
    # the toy keeps the structure: heads > groups > 1, a latent narrower than the residual, top-k > 1 of more
    # experts than are held, all three kinds of layer
    d = _dims()
    assert d["ssm_heads"] > d["ssm_groups"] > 1 and d["latent"] < d["d"]
    assert 1 < d["top_k"] and d["experts_held"][1] < d["experts"] and set(d["pattern"]) == set("ME*")


def test_forward_agrees_and_a_lower_precision_does_not(params):
    import jax.numpy as jnp

    from ray_lightning_tpu.models.gpt import gpt_forward

    dims = _dims()
    toks = jnp.asarray(np.random.default_rng(0).integers(0, V, (2, 37)), jnp.int32)  # 37: no whole chunks
    ref = reference.logits_of(params, toks, dims)
    assert _rel(gpt_forward(params, toks, _program_cfg()), ref) < TOL
    assert _rel(gpt_forward(params, toks, _program_cfg(compute_dtype="bfloat16")), ref) > 1e-3
    assert _rel(reference.logits_of(params, toks, dims, lowp=True), ref) > 1e-2


# -- prefill, then decode through the state, the conv tail and the K/V cache ---------------
#: (prompt length, bucket, tokens decoded): prompts of 1, 2 and 3 tokens
#: (shorter than the conv's 3 rows of memory), of a chunk exactly and of a
#: chunk plus one, each right-padded to its bucket, and one of several
#: chunks; the fourth stops early and its slot stays frozen while the
#: others go on.
CASES = [(1, 4, 12), (2, 4, 30), (3, 4, 9), (Q, 16, 5), (Q + 1, 16, 40), (37, 64, 20)]


def _serve(params, seqs):
    """The program's logits at every position of the sequences of given
    tokens: bucketed prefill into a slot each, then decode steps at
    per-slot positions, idle lanes beside them (two more slots than
    requests), a slot frozen once its sequence has ended."""
    import jax
    import jax.numpy as jnp

    from ray_lightning_tpu.models.gpt import _lm_head, _rmsnorm
    from ray_lightning_tpu.models.mixed import empty_caches, mixed_decode_step, mixed_rows, write_prefill_rows

    cfg = _program_cfg()
    n = len(CASES)
    k_cache, v_cache = empty_caches(cfg, n + 2, 96, jnp.float32)
    got = [np.zeros((len(s), V), np.float32) for s in seqs]
    for slot, ((P, Pb, _), seq) in enumerate(zip(CASES, seqs)):
        prompt = np.zeros((1, Pb), np.int32)
        prompt[0, :P] = seq[:P]
        h, pf_k, pf_v, st = mixed_rows(params, cfg, jnp.asarray(prompt), true_len=jnp.int32(P))
        assert [int(x) for x in st[3:]] == [Pb, P]
        k_cache, v_cache = write_prefill_rows(k_cache, v_cache, pf_k, pf_v, jnp.int32(slot), jnp.int32(P))
        got[slot][:P] = np.asarray(_lm_head(_rmsnorm(h[0, :P], params["lnf_g"], cfg.norm_eps), params["lm_head"]))
    step = jax.jit(lambda cur, pos, k, v, act: mixed_decode_step(params, cfg, cur, pos, k, v, active=act))
    pos = np.array([P for P, _, _ in CASES] + [0, 0], np.int32)
    ends = np.array([len(s) for s in seqs] + [0, 0], np.int32)
    while (pos < ends).any():
        active = pos < ends
        cur = np.array([s[min(p, len(s) - 1)] for s, p in zip(seqs, pos)] + [0, 0], np.int32)
        logits, k_cache, v_cache, _ = step(jnp.asarray(cur), jnp.asarray(pos), k_cache, v_cache, jnp.asarray(active))
        for slot in np.nonzero(active)[0]:
            got[slot][pos[slot]] = np.asarray(logits[slot])
        pos = np.where(active, pos + 1, pos)  # a frozen slot advances its own state again and again
    return got


@pytest.fixture(scope="module")
def seqs():
    rng = np.random.default_rng(3)
    return [rng.integers(0, V, P + n).astype(np.int32) for P, _, n in CASES]


@pytest.fixture(scope="module")
def served_logits(params, seqs):
    return _serve(params, seqs)


def _reference_logits(params, seqs, dims, lowp=False):
    import jax.numpy as jnp

    out = []
    for s in seqs:
        toks = np.zeros((1, 64), np.int32)
        toks[0, : len(s)] = s
        out.append(np.asarray(reference.logits_of(params, jnp.asarray(toks), dims, lowp)[0, : len(s)]))
    return out


@pytest.fixture(scope="module")
def sound_reference(params, seqs):
    return _reference_logits(params, seqs, _dims())


def test_prefill_and_decode_through_the_state_agree_with_the_full_forward(served_logits, sound_reference):
    for g, r in zip(served_logits, sound_reference):
        assert _rel(g, r) < TOL
        # position by position too: one wrong carry would hide in a norm
        assert np.abs(g - r).max() < 1e-4 * np.abs(r).max()


# -- each term planted out of the reference in turn ------------------------------------------
def _zeroed(*names):
    return lambda p: dict(p, blocks=dict(p["blocks"], **{n: np.zeros_like(p["blocks"][n]) for n in names}))


def _conv_taps_reversed(fam):
    sound = fam.conv
    return {"conv": lambda xbc, w, b: sound(xbc, w[::-1], b)}


def _state_not_carried(fam):
    """The scan starts from zero again at every chunk boundary."""
    import jax.numpy as jnp

    sound = fam.scan

    def scan(x, dt, A, bh, ch, dims, lowp):
        S = x.shape[1]
        return jnp.concatenate(
            [sound(x[:, s:s + Q], dt[:, s:s + Q], A, bh[:, s:s + Q], ch[:, s:s + Q], dims, lowp)
             for s in range(0, S, Q)], axis=1)

    return {"scan": scan}


def _gate_after_norm(fam):
    import jax

    def gate_norm(y, z, g, dims):
        B, S, _ = y.shape
        y = y.reshape(B, S, dims["ssm_groups"], -1)
        y = y / ((y * y).mean(-1, keepdims=True) + dims["norm_eps"]) ** 0.5
        return y.reshape(B, S, -1) * g * jax.nn.silu(z)

    return {"gate_norm": gate_norm}


def _norm_over_all_channels(fam):
    sound = fam.gate_norm
    return {"gate_norm": lambda y, z, g, dims: sound(y, z, g, dict(dims, ssm_groups=1))}


def _group_by_modulo(fam):
    import jax.numpy as jnp

    return {"head_groups": lambda bc, dims: jnp.tile(bc, (1, 1, dims["ssm_heads"] // dims["ssm_groups"], 1))}


def _rotary_in_attention(fam):
    def attention(u, leaf, dims, lowp):
        q = reference.rope(reference.mm("bsd,dhk->bshk", u, leaf("wq"), lowp), 10000.0)
        k = reference.rope(reference.mm("bsd,dhk->bshk", u, leaf("wk"), lowp), 10000.0)
        v = reference.mm("bsd,dhk->bshk", u, leaf("wv"), lowp)
        return reference.mm("bshk,hkd->bsd", reference.attention(q, k, v, 0, lowp), leaf("wo"), lowp)

    return {"attention": attention}


def _experts_fed_the_input(fam):
    """The experts read the layer's input (its first ``latent`` channels: the widths differ) and not its projection."""
    return {"latent_in": lambda t, w_down, lowp: t[:, : w_down.shape[1]]}


def _relu_for_relu2(fam):
    import jax

    return {"act": jax.nn.relu}


def _route_normalised_over_held(fam):
    import jax
    import jax.numpy as jnp

    def route(t, wr, c, dims, lowp):
        sigma = jax.nn.sigmoid(reference.mm("td,de->te", t, wr, lowp))
        _, top = jax.lax.top_k(sigma + c, dims["top_k"])
        chosen = jnp.zeros_like(sigma).at[jnp.arange(sigma.shape[0])[:, None], top].set(1.0)
        first, count = dims["experts_held"]
        held = (jnp.arange(sigma.shape[1]) >= first) & (jnp.arange(sigma.shape[1]) < first + count)
        w = sigma * chosen * held
        return dims["scale"] * w / jnp.maximum(jnp.sum(w, -1, keepdims=True), 1e-9)

    return {"route": route}


def _shared_fed_the_latent(fam):
    """The shared expert reads the latent (padded with zeros to the input's width) and not the input."""
    import jax.numpy as jnp

    return {"shared_in": lambda t, a: jnp.pad(a, ((0, 0), (0, t.shape[1] - a.shape[1])))}


#: name -> (change to the sizes, change to the weights, replacement pieces)
PLANTED = {
    "float8 (the control)": ({}, None, None),
    "conv taps reversed": ({}, None, _conv_taps_reversed),
    "conv bias dropped": ({}, _zeroed("ssm_conv_b"), None),
    "the state not carried across a chunk boundary": ({}, None, _state_not_carried),
    "dt_bias dropped": ({}, _zeroed("ssm_dt_bias"), None),
    "D x dropped": ({}, _zeroed("ssm_D"), None),
    "the gate after the norm": ({}, None, _gate_after_norm),
    "the norm over all channels instead of a group": ({}, None, _norm_over_all_channels),
    "the group of head h as h % G": ({}, None, _group_by_modulo),
    "rotary applied in the attention layer": ({}, None, _rotary_in_attention),
    "experts fed the layer's input": ({}, None, _experts_fed_the_input),
    "relu for relu squared": ({}, None, _relu_for_relu2),
    "the routed scale dropped": ({"scale": 1.0}, None, None),
    "weights normalised over held experts only": ({}, None, _route_normalised_over_held),
    "the shared expert dropped": ({}, _zeroed("moe_shared_wo2"), None),
    "the shared expert fed the latent": ({}, None, _shared_fed_the_latent),
}


@pytest.mark.parametrize("name", list(PLANTED))
def test_the_comparison_fails_with_a_term_planted_out_of_the_reference(params, seqs, served_logits, monkeypatch, name):
    from pb import plug

    change, reweigh, pieces = PLANTED[name]
    dims = dict(_dims(), **change)
    if pieces is not None:
        fam = plug.family_of(dims)
        for piece, fn in pieces(fam).items():
            monkeypatch.setattr(fam, piece, fn)
    refs = _reference_logits(reweigh(params) if reweigh else params, seqs, dims, lowp=name.startswith("float8"))
    worst = max(_rel(g, r) for g, r in zip(served_logits, refs))
    print(f"planted {name!r}: {worst:.3g}")
    assert worst > 10 * TOL, name


#: what a right-padded prompt must not do, planted into the PROGRAM's prefill (the reference has no padding to get
#: wrong): which of ``ssm_rows``' two outputs is taken from a pass that treats the padding as prompt
PADDING = {"padding rows advancing the state": 1, "the conv tail taken from padded rows": 2}


@pytest.mark.parametrize("name", list(PADDING))
def test_the_comparison_fails_when_padding_is_taken_for_prompt(params, seqs, sound_reference, monkeypatch, name):
    from ray_lightning_tpu.models import ssm

    sound = ssm.ssm_rows

    def planted(u, lp, cfg, valid=None):
        out, blind = list(sound(u, lp, cfg, valid)), sound(u, lp, cfg, None)
        out[PADDING[name]] = blind[PADDING[name]]
        return tuple(out)

    monkeypatch.setattr(ssm, "ssm_rows", planted)
    worst = max(_rel(g, r) for g, r in zip(_serve(params, seqs), sound_reference))
    print(f"planted {name!r}: {worst:.3g}")
    assert worst > 10 * TOL, name


@pytest.mark.parametrize("fold", [1, 4])
def test_the_dense_engine_serves_what_the_reference_puts_first(params, fold):
    """Bucketed admission, decode fold ``fold``, idle lanes, a request that
    ends early (its slot frozen, then taken again by a shorter request
    after a longer one): every served token is the reference's first
    choice at its position, and the counts that left the device with the
    tokens add up."""
    from ray_lightning_tpu.serve.engine import DecodeEngine

    eng = DecodeEngine(params, _program_cfg(), num_slots=4, max_seq=96, prefill_buckets=[4, 16, 64], decode_fold=fold)
    compiled = eng.compiled_count
    rng = np.random.default_rng(1)
    sizes = [(3, 40), (37, 9), (Q + 1, 30), (2, 12)]
    reqs = [dict(prompt=rng.integers(0, V, P).tolist(), request_id=f"r{i}", max_new_tokens=n)
            for i, (P, n) in enumerate(sizes)]
    outs = {r["request_id"]: [] for r in reqs}
    for r, (_, tok, _) in zip(reqs[:3], eng.admit_many(reqs[:3])):
        outs[r["request_id"]].append(tok)
    late, late_slot = reqs[3], None
    for _ in range(200):
        for _, rid, tok, _ in eng.step():
            outs[rid].append(tok)
        if late is not None and len(outs["r1"]) == 9 and len(eng.free_slots()) == 2:
            # into the slot the longest prompt has left: nothing of its state may be left
            late_slot, tok, _ = eng.admit_many([late])[0]
            outs[late["request_id"]].append(tok)
            late = None
        if late is None and eng.num_active == 0:
            break
    assert [len(outs[r["request_id"]]) for r in reqs] == [n for _, n in sizes]
    assert late_slot == 1 and eng.compiled_count == compiled
    res = reference.serve_reference(
        params, [{"prompt": r["prompt"], "tokens": outs[r["request_id"]]} for r in reqs], _dims(), pad_to=64)
    assert res["widest_gap"] <= 1e-5 and res["greedy_agree_share"] == 1.0  # in logit units, not a share of a norm
    moe, layers, k = eng.moe_stats(), 3, CFG["num_experts_per_tok"]
    decoded = sum(n - 1 for _, n in sizes)
    assert moe["decode"]["pairs_routed"] == decoded * layers * k
    assert moe["prefill"]["pairs_routed"] == sum(P for P, _ in sizes) * layers * k and moe["prefill"]["admissions"] == 4
    assert 0 < moe["decode"]["pairs_held"] < moe["decode"]["pairs_routed"]
    ssm = eng.ssm_stats()
    assert ssm["state_layers"] == 3 and ssm["decode"]["slot_steps_live"] == decoded
    assert ssm["decode"]["slot_steps"] % (4 * fold) == 0 and ssm["decode"]["slot_steps"] > decoded
    assert ssm["prefill"] == {"rows_scanned": 4 + 64 + 16 + 4, "rows_real": sum(P for P, _ in sizes)}
    cache, d = eng.cache_stats(), _dims()
    per_slot = 3 * (8 * 8 * 16 * 4 + 3 * (64 + 2 * 2 * 16) * 4)  # float32 state; the tail in the compute dtype
    assert cache["state"] == {"layers": 3, "rows_per_slot": 1, "bytes": 4 * per_slot, "row_layout": False}
    assert cache["full"]["layers"] == 1 and cache["full"]["rows_per_slot"] == 96
    from pb import plug

    assert plug.family_of(d).state_bytes_per_slot(d, tail_bytes=4) == per_slot


# -- the share and the whole --------------------------------------------------------
def test_the_four_shares_of_an_expert_layer_add_up_to_the_uncut_layer(params):
    """``experts_held = (r E/4, E/4)``, r = 0..3, of the toy's 32 experts:
    the routed parts of the program's expert layer — each share's sum in
    the latent through the same up-projection, which is linear — plus the
    shared expert COUNTED ONCE add up to what the reference gives for the
    whole layer (the router over all experts), and the pairs that landed on
    the shares are all the pairs routed."""
    import jax
    import jax.numpy as jnp

    from pb import plug
    from ray_lightning_tpu.models.mixed import LayerSpec, _experts_part

    E, T = 32, 50
    dims = dict(_dims(), experts_held=[0, E])
    fam = plug.family_of(dims)
    D, Dl, F, Fs = dims["d"], dims["latent"], dims["expert_ff"], dims["shared_ff"]
    ks = jax.random.split(jax.random.PRNGKey(5), 9)
    u = jax.random.normal(ks[0], (1, T, D), jnp.float32)
    lp = {
        "router": 0.2 * jax.random.normal(ks[1], (D, E)), "router_bias": 0.05 * jax.random.normal(ks[2], (E,)),
        "latent_down": 0.2 * jax.random.normal(ks[3], (D, Dl)), "latent_up": 0.2 * jax.random.normal(ks[4], (Dl, D)),
        "shared_wi": 0.2 * jax.random.normal(ks[5], (1, D, Fs)), "shared_wo2": 0.2 * jax.random.normal(ks[6], (Fs, D)),
    }
    wi = 0.2 * jax.random.normal(ks[7], (E, 1, Dl, F))
    wo2 = 0.2 * jax.random.normal(ks[8], (E, F, Dl))
    whole = fam.expert_layer(
        u, lambda n: {"wi": wi, "wo2": wo2, **lp}[n], lambda n: {"wi": wi, "wo2": wo2}[n], dims, False)
    ls = LayerSpec(0, None, "experts", 0, 0, 0, 0)
    no_shared = {k: v for k, v in lp.items() if not k.startswith("shared_")}
    total, held_pairs, routed = jnp.zeros_like(whole), 0, None
    for r in range(4):
        cfg = _program_cfg(experts_held=(r * E // 4, E // 4))
        share = dict(wi=wi[None, r * 8:(r + 1) * 8], wo2=wo2[None, r * 8:(r + 1) * 8])
        # every share computes the shared expert alike: it is counted with the first alone
        out, stats = _experts_part(u, dict(lp if r == 0 else no_shared, **share), ls, cfg, None)
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
         "--workload", "toy-nemotron.serve-shortchat", "--seed", str(2**31 + 17), "--seconds", "3", "--trace", "1"],
        cwd=ROOT, env=env, timeout=900, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    assert "REHEARSAL finished: correct=True" in p.stdout and "leftovers: none" in p.stdout
    for said in ("held experts hit a step and expert layer: ", "pairs on held experts: ", "state layers: "):
        assert said in p.stdout, (said, p.stdout[-3000:])
