"""The ``deepseek_v3`` family's plain reference (materialised attention,
no cache) against the program (materialised prefill, absorbed decode
through the cache of latent rows) at a toy size on the CPU: the forward
pass, bucketed prefill then decode (model functions and the dense
engine), the two forms of the attention on the same rows, the same
comparison in bfloat16 and with each rule of the block planted out in
turn, the eight shares of the expert layer adding up to the whole with
the shared expert counted once, the new metric readers on a hand-made
run, and the toy root's rehearsal."""
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
TOY = os.path.join(HERE, "toy_kanana")
CFG = json.load(open(os.path.join(TOY, "configs", "toy-kanana.json")))
V = CFG["vocab_size"]
#: float32 on both sides. The program absorbs ``W_kv_b`` into the query and
#: the output where the reference builds keys and values, rotates pairs
#: into a half-split order where the reference rotates in place, and groups
#: the experts' rows otherwise: what is left is rounding, 2e-7 to 4e-7 of
#: the logits' norm over the served sequences. 3e-6 leaves seven times of
#: room and lies at 1/370 of the least of the planted faults (the scale of
#: the no-position dims alone, 1.1e-3; the test prints them all).
TOL = 3e-6
#: bfloat16 in the program (weights, activations and the cached rows rounded
#: to 8 bits of mantissa, sums in float32) against the float32 reference:
#: 5e-3 to 6e-3 of the logits' norm at these widths and four layers. 3e-2
#: leaves five times of room. It bounds the rounding and is no detector: the
#: three rotary faults read 1e-3 to 2e-3, under bfloat16's own rounding, and
#: it is the float32 comparison above that catches them.
TOL_BF16 = 3e-2


def _dims(cfg=CFG):
    return Spec(ROOT).dims(cfg)


def _program_cfg(**over):
    from ray_lightning_tpu.models.gpt import GPTConfig

    return dataclasses.replace(GPTConfig(**CFG["program_config"]), **over)


def _rel(a, ref):
    return float(np.linalg.norm(np.asarray(a) - np.asarray(ref)) / np.linalg.norm(np.asarray(ref)))


@pytest.fixture(scope="module")
def params():
    return weights.make_params(2**31 + 29, _dims(), 128, "float32")


def test_the_seeded_tree_is_the_tree_the_program_takes(params):
    from pb import plug
    from ray_lightning_tpu.models.mixed import mixed_param_shapes

    want = mixed_param_shapes(_program_cfg())
    assert {k: tuple(v.shape) for k, v in params["blocks"].items()} == want["blocks"]
    assert {k: tuple(v.shape) for k, v in params.items() if k != "blocks"} == {
        k: v for k, v in want.items() if k != "blocks"}
    assert all(float(np.abs(np.asarray(v) - np.round(np.asarray(v))).max()) > 0 for v in params["blocks"].values())
    # the toy keeps the structure: a latent narrower than the heads' keys together, a rotary part and a
    # no-position part, a dense leading layer, top-k > 1 of more experts than are held, two shared experts
    d = _dims()
    assert d["lora"] < d["heads"] * d["nope"] and d["rope_dim"] and d["first_dense"] == 1 < d["layers"]
    assert 1 < d["top_k"] and d["experts_held"][1] * 8 == d["experts"] and d["shared_ff"] == 2 * d["expert_ff"]
    kinds = plug.family_of(d).param_shapes(d, 128)["blocks"]
    # the residual writes, but the shared experts': they are the larger part of an expert layer (param_shapes)
    assert {k for k, (_, kind) in kinds.items() if kind == "r"} == {"lat_wo", "dense_wo2", "moe_wo2"}


def test_the_published_sizes_count_what_the_issue_counted():
    """The cell's own configuration: bytes and parameters from shapes."""
    from pb import plug

    spec = Spec(ROOT)
    d = spec.dims(spec.config("kanana-2-30b-a3b-d16-ep8"))
    fam = plug.family_of(d)
    assert fam._attn_params(d) == 26_345_472 and fam.expert_params(d) == 4_718_592
    assert fam.total_params(d) == 1_802_895_360  # 3.61 GB in bfloat16
    assert fam.kv_bytes_per_token(d) == 16 * 1152
    shapes = fam.param_shapes(d, 6656)
    held = sum(int(np.prod(s)) for s, _ in shapes["blocks"].values()) + sum(
        int(np.prod(v[0])) for k, v in shapes.items() if k != "blocks")
    gains_and_biases = 2 * 16 * 2048 + 16 * 512 + 15 * 128 + 2048
    assert held == fam.total_params(d) + gains_and_biases
    # a decode step at 100k live positions and 13 experts hit a layer: weights 0.86 + experts 1.84 + rows 1.84 GB
    need = fam.sparse_decode_step_bytes(d, 100_000.0, 0.0, 13.0)
    assert need == pytest.approx(2 * fam.always_read_params(d) + 15 * 13 * 2 * 4_718_592 + 100_000 * 18_432)


def test_a_branch_of_the_block_that_is_not_written_down_is_refused():
    with pytest.raises(ValueError, match="q_lora_rank"):
        _dims(dict(CFG, q_lora_rank=1536))
    with pytest.raises(ValueError, match="n_group"):
        _dims(dict(CFG, n_group=8))


def test_forward_agrees_and_a_lower_precision_does_not(params):
    import jax.numpy as jnp

    from ray_lightning_tpu.models.gpt import gpt_forward

    dims = _dims()
    toks = jnp.asarray(np.random.default_rng(0).integers(0, V, (2, 37)), jnp.int32)
    ref = reference.logits_of(params, toks, dims)
    assert _rel(gpt_forward(params, toks, _program_cfg()), ref) < TOL
    low = _rel(gpt_forward(params, toks, _program_cfg(compute_dtype="bfloat16")), ref)
    assert 1e-3 < low < TOL_BF16, low
    assert _rel(reference.logits_of(params, toks, dims, lowp=True), ref) > 1e-2


# -- prefill, then decode through the cache of latent rows -------------------------------------
#: (prompt length, bucket, tokens decoded): a prompt of one token, one that fills its bucket, one right-padded,
#: one of several query blocks of the reference; the second stops early and its slot stays frozen.
CASES = [(1, 8, 12), (16, 16, 5), (9, 16, 40), (70, 96, 20)]


def _serve(params, seqs, cfg=None):
    """The program's logits at every position of the sequences of given
    tokens: bucketed prefill into a slot each, then decode steps at
    per-slot positions, idle lanes beside them (two more slots than
    requests), a slot frozen once its sequence has ended."""
    import jax
    import jax.numpy as jnp

    from ray_lightning_tpu.models.gpt import _lm_head, _rmsnorm
    from ray_lightning_tpu.models.mixed import empty_caches, mixed_decode_step, mixed_rows, write_prefill_rows

    cfg = cfg or _program_cfg()
    cdt = jnp.dtype(cfg.compute_dtype)
    params = jax.tree_util.tree_map(lambda a: a.astype(cdt), params)
    n = len(CASES)
    k_cache, v_cache = empty_caches(cfg, n + 2, 128, cdt)
    # the latents in the first half, the shared rotary keys in the second: 16 + 8 values a position
    assert set(k_cache) == set(v_cache) == {"latent"}
    assert k_cache["latent"].shape == (4, n + 2, 128, 16) and v_cache["latent"].shape == (4, n + 2, 128, 8)
    got = [np.zeros((len(s), V), np.float32) for s in seqs]
    for slot, ((P, Pb, _), seq) in enumerate(zip(CASES, seqs)):
        prompt = np.zeros((1, Pb), np.int32)
        prompt[0, :P] = seq[:P]
        h, pf_k, pf_v, st = mixed_rows(params, cfg, jnp.asarray(prompt), true_len=jnp.int32(P))
        assert [int(x) for x in st[3:]] == [Pb, P]
        assert pf_k["latent"].shape == (4, 1, Pb, 16) and pf_v["latent"].shape == (4, 1, Pb, 8)
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
        pos = np.where(active, pos + 1, pos)
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
        toks = np.zeros((1, 96), np.int32)
        toks[0, : len(s)] = s
        out.append(np.asarray(reference.logits_of(params, jnp.asarray(toks), dims, lowp)[0, : len(s)]))
    return out


@pytest.fixture(scope="module")
def sound_reference(params, seqs):
    return _reference_logits(params, seqs, _dims())


def test_prefill_and_decode_through_the_latent_cache_agree_with_the_full_forward(served_logits, sound_reference):
    for g, r in zip(served_logits, sound_reference):
        assert _rel(g, r) < TOL
        assert np.abs(g - r).max() < 1e-4 * np.abs(r).max()  # position by position too


def test_the_same_in_bfloat16_stays_within_its_stated_tolerance(params, seqs, sound_reference):
    """Weights, activations and the cached rows in bfloat16, the
    configuration's stated dtype, against the float32 reference."""
    worst = max(_rel(g, r) for g, r in zip(_serve(params, seqs, _program_cfg(compute_dtype="bfloat16")), sound_reference))
    print(f"bfloat16 through the cache: {worst:.3g}")
    assert 1e-3 < worst < TOL_BF16


def test_the_absorbed_and_the_materialised_attention_agree_on_the_same_rows(params):
    """One layer, one sequence: the no-cache path over all rows against the
    decode path fed the same rows one at a time through a cache, which
    never builds a key or a value."""
    import jax
    import jax.numpy as jnp

    from ray_lightning_tpu.models.mixed import _latent_part, _layer_leaves, _rope_by_kind, empty_caches, layer_specs

    cfg = _program_cfg()
    ls = layer_specs(cfg)[2]
    lp = _layer_leaves(params["blocks"], ls)
    S = 33
    h = jax.random.normal(jax.random.PRNGKey(4), (1, S, cfg.d_model), jnp.float32)
    pos = jnp.arange(S, dtype=jnp.int32)[None]
    rows_out, (lat, keys) = _latent_part(h, lp, ls, cfg, _rope_by_kind(cfg, pos), None, None)
    assert lat.shape == (1, S, 16) and keys.shape == (1, S, 8)
    caches = empty_caches(cfg, 1, 64, jnp.float32)
    outs = []
    for t in range(S):
        p = jnp.asarray([t], jnp.int32)
        o, caches = _latent_part(h[:, t:t + 1], lp, ls, cfg, _rope_by_kind(cfg, p[:, None]), p, caches)
        outs.append(o)
    assert _rel(jnp.concatenate(outs, axis=1), rows_out) < 1e-6
    # what the steps wrote is what the rows' pass hands to the cache, and no other layer's rows were touched
    assert float(jnp.abs(caches[0]["latent"][2, 0, :S] - lat[0]).max()) < 1e-6
    assert float(jnp.abs(caches[1]["latent"][2, 0, :S] - keys[0]).max()) < 1e-6
    assert float(jnp.abs(caches[0]["latent"][:2]).max()) == 0.0 == float(jnp.abs(caches[1]["latent"][3]).max())


# -- each rule planted out of the reference in turn ---------------------------------------------
def _zeroed(*names):
    return lambda p: dict(p, blocks=dict(p["blocks"], **{n: np.zeros_like(p["blocks"][n]) for n in names}))


def _key_rotated_per_head(fam):
    """The 8-wide rotary key taken for four heads' keys of 2 dims, each
    rotated as a head of its own (at a head's frequencies, not the key's)."""
    import jax.numpy as jnp

    def shared_key(k_r, theta, heads):
        B, S, d = k_r.shape
        k = fam.rope(k_r.reshape(B, S, heads, d // heads), theta).reshape(B, S, 1, d)
        return jnp.broadcast_to(k, (B, S, heads, d))

    return {"shared_key": shared_key}


def _half_split_pairs(fam):
    return {"rope": lambda x, theta: reference.rope(x, theta)}


def _latent_left_unnormed(fam):
    return {"latent_norm": lambda c, g, eps: c}


def _scale_of_the_nope_dims(fam):
    return {"score_scale": lambda dims: dims["nope"] ** -0.5}


def _shared_counted_an_eighth(fam):
    sound = fam.shared
    return {"shared": lambda t, wi, wo2, lowp: sound(t, wi, wo2, lowp) / 8.0}


def _route_normalised_over_held(fam):
    import jax
    import jax.numpy as jnp

    def route(t, wr, b, dims, lowp):
        sigma = jax.nn.sigmoid(reference.mm("td,de->te", t, wr, lowp))
        _, top = jax.lax.top_k(sigma + b, dims["top_k"])
        chosen = jnp.zeros_like(sigma).at[jnp.arange(sigma.shape[0])[:, None], top].set(1.0)
        first, count = dims["experts_held"]
        held = (jnp.arange(sigma.shape[1]) >= first) & (jnp.arange(sigma.shape[1]) < first + count)
        w = sigma * chosen * held
        return dims["scale"] * w / jnp.maximum(jnp.sum(w, -1, keepdims=True), 1e-9)

    return {"route": route}


#: name -> (change to the sizes, change to the weights, replacement pieces)
PLANTED = {
    "float8 (the control)": ({}, None, None),
    "the rotary key rotated per head instead of once": ({}, None, _key_rotated_per_head),
    "half-split pairs instead of interleaved ones": ({}, None, _half_split_pairs),
    "the latent left un-normed": ({}, None, _latent_left_unnormed),
    "the scale 1/sqrt(128) of the no-position dims alone": ({}, None, _scale_of_the_nope_dims),
    "the routed scale left out": ({"scale": 1.0}, None, None),
    "the shared expert counted 1/8": ({}, None, _shared_counted_an_eighth),
    "the correction bias dropped from the choice": ({}, _zeroed("moe_router_bias"), None),
    "weights normalised over held experts only": ({}, None, _route_normalised_over_held),
    "the first layer given experts too": ({"first_dense": 0}, lambda p: dict(p, blocks=dict(
        p["blocks"], **{k: np.concatenate([np.asarray(v[:1]), np.asarray(v)]) for k, v in p["blocks"].items()
                        if k.startswith("moe_")})), None),
}


@pytest.mark.parametrize("name", list(PLANTED))
def test_the_comparison_fails_with_a_rule_planted_out_of_the_reference(params, seqs, served_logits, monkeypatch, name):
    from pb import plug

    change, reweigh, pieces = PLANTED[name]
    dims = dict(_dims(), **change)
    if pieces is not None:
        fam = plug.family_of(dims)
        for piece, fn in pieces(fam).items():
            monkeypatch.setattr(fam, piece, fn)
    refs = _reference_logits(reweigh(params) if reweigh else params, seqs, dims, lowp=name.startswith("float8"))
    worst = max(_rel(g, r) for g, r in zip(served_logits, refs))
    print(f"planted {name!r}: {worst:.3g} = {worst / TOL:.0f} x the tolerance")
    assert worst > 10 * TOL, name


@pytest.mark.parametrize("fold", [1, 4])
def test_the_dense_engine_serves_what_the_reference_puts_first(params, fold):
    """Bucketed admission, decode fold ``fold``, idle lanes, a request that
    ends early, its slot taken again: every served token is the
    reference's first choice at its position, and the counts add up — the
    expert layers' that left the device with the tokens, and the latent
    rows' that the host reckons from the slots' records."""
    from ray_lightning_tpu.serve.engine import DecodeEngine

    eng = DecodeEngine(params, _program_cfg(), num_slots=4, max_seq=128, prefill_buckets=[8, 16, 96], decode_fold=fold)
    compiled = eng.compiled_count
    rng = np.random.default_rng(1)
    sizes = [(3, 40), (70, 9), (16, 30), (2, 12)]
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
            late_slot, tok, _ = eng.admit_many([late])[0]  # into the slot the longest prompt has left
            outs[late["request_id"]].append(tok)
            late = None
        if late is None and eng.num_active == 0:
            break
    assert [len(outs[r["request_id"]]) for r in reqs] == [n for _, n in sizes]
    assert late_slot == 1 and eng.compiled_count == compiled
    res = reference.serve_reference(
        params, [{"prompt": r["prompt"], "tokens": outs[r["request_id"]]} for r in reqs], _dims(), pad_to=128)
    assert res["widest_gap"] <= 1e-5 and res["greedy_agree_share"] == 1.0
    moe, layers, k = eng.moe_stats(), 3, CFG["num_experts_per_tok"]
    decoded = sum(n - 1 for _, n in sizes)
    assert moe["decode"]["pairs_routed"] == decoded * layers * k
    assert moe["prefill"]["pairs_routed"] == sum(P for P, _ in sizes) * layers * k and moe["prefill"]["admissions"] == 4
    assert 0 < moe["decode"]["pairs_held"] < moe["decode"]["pairs_routed"]
    attn = eng.attn_stats()
    # a token step's query sees the prompt and what was generated before it: positions 0 .. P + j - 1 for token j
    assert attn["rows_live"] == 4 * sum(P + j for P, n in sizes for j in range(1, n))
    assert attn["rows_visited"] == attn["rows_allocated"] > attn["rows_live"]  # the XLA read: every allocated row
    assert attn["rows_allocated"] % (4 * 4 * 128 * fold) == 0  # latent layers x slots x max_seq, a fold at a time
    cache = eng.cache_stats()
    assert cache == {"latent": {"layers": 4, "rows_per_slot": 128, "bytes": 4 * 4 * 128 * 24 * 4, "row_layout": True}}
    assert eng.memory_stats()["kv_cache"]["bytes"] == cache["latent"]["bytes"]
    from pb import plug

    d = _dims()
    assert plug.family_of(d).kv_bytes_per_token(d, 4) * 4 * 128 == cache["latent"]["bytes"]


# -- the share and the whole --------------------------------------------------------------
def test_the_eight_shares_of_an_expert_layer_add_up_to_the_uncut_layer(params):
    """``experts_held = (r E/8, E/8)``, r = 0..7, of the toy's 32 experts:
    the routed parts of the program's expert layer plus the shared expert
    COUNTED ONCE add up to what the reference gives for the whole layer
    (the router over all experts), and the pairs that landed on the shares
    are all the pairs routed."""
    import jax
    import jax.numpy as jnp

    from pb import plug
    from ray_lightning_tpu.models.mixed import LayerSpec, _experts_part

    E, T = 32, 50
    dims = dict(_dims(), experts_held=[0, E])
    fam = plug.family_of(dims)
    D, F, Fs = dims["d"], dims["expert_ff"], dims["shared_ff"]
    ks = jax.random.split(jax.random.PRNGKey(5), 7)
    u = jax.random.normal(ks[0], (1, T, D), jnp.float32)
    lp = {
        "router": 0.2 * jax.random.normal(ks[1], (D, E)), "router_bias": 0.05 * jax.random.normal(ks[2], (E,)),
        "shared_wi": 0.2 * jax.random.normal(ks[3], (2, D, Fs)), "shared_wo2": 0.2 * jax.random.normal(ks[4], (Fs, D)),
    }
    wi = 0.2 * jax.random.normal(ks[5], (E, 2, D, F))
    wo2 = 0.2 * jax.random.normal(ks[6], (E, F, D))
    whole = fam.expert_layer(
        u, lambda n: {"wi": wi, "wo2": wo2, **lp}[n], lambda n: {"wi": wi, "wo2": wo2}[n], dims, False)
    ls = LayerSpec(0, None, "experts", 0, 0, 0, 0)
    no_shared = {k: v for k, v in lp.items() if not k.startswith("shared_")}
    total, held_pairs, routed = jnp.zeros_like(whole), 0, None
    for r in range(8):
        cfg = _program_cfg(experts_held=(r * E // 8, E // 8))
        share = dict(wi=wi[None, r * 4:(r + 1) * 4], wo2=wo2[None, r * 4:(r + 1) * 4])
        # every share computes the shared expert alike: it is counted with the first alone
        out, stats = _experts_part(u, dict(lp if r == 0 else no_shared, **share), ls, cfg, None)
        total = total + out
        held_pairs += int(stats[1])
        routed = int(stats[0])
    assert _rel(total, whole) < TOL
    assert routed == T * dims["top_k"] and held_pairs == routed
    # counted on every share the shared expert would weigh eight times
    shared_part = fam.shared(u[0], lp["shared_wi"], lp["shared_wo2"], False)
    assert _rel(total + 7 * shared_part[None], whole) > 0.5


# -- the new readers on a hand-made run ----------------------------------------------------------
def test_the_three_new_readers_read_a_hand_made_run_and_nothing_from_a_program_without_the_counters():
    """``latent_decode_hbm_roofline_pct`` (the sparse step's reader on this
    family's byte count), ``latent_rows_live_pct`` and
    ``prefill_device_share_pct`` at the cell's own sizes and the v5e's
    peaks; a program from before the counters (the parent, measured with
    this benchmark laid over it) gives each nothing to read, and none
    raises."""
    from pb import costs, plug

    spec = Spec(ROOT)
    cell = spec.cell("kanana-2-30b-a3b-d16-ep8.serve-docqa")
    dims, mix = spec.dims(spec.config(cell["config"])), spec.traffic(cell["traffic"])
    fam = plug.family_of(dims)

    def stats(k):
        return {"moe": {"expert_layers": 15,
                        "decode": {"token_steps": 400 * k, "experts_hit": 400 * k * 15 * 13, "pairs_routed": 1, "pairs_held": 1},
                        "prefill": {"pairs_routed": 1, "pairs_held": 1}},
                "attn": {"rows_allocated": 16 * 64 * 6656 * 400 * k, "rows_visited": 16 * 64 * 6656 * 400 * k,
                         "rows_live": 16 * 90_000 * 400 * k}}

    program = {"records": [{"recv_s": [0.0, 30.0], "prompt_len": 2000, "tokens": [1] * 200}] * 40,
               "stats0": stats(1), "stats1": stats(3)}
    trace = {"devices": 1, "busy_s": 2.8, "window_s": 3.0,
             "modules": {"jit_step_impl(1)": [0.072, 0.072, 0.076], "jit_admit_impl(2)": [0.2, 0.3, 0.5]}}
    ctx = {"cell": cell["name"], "dims": dims, "mix": mix, "program": program, "trace": trace, "seconds": 30.0,
           "peaks": costs.peaks("TPU v5 lite")}
    seen = {}
    for name in ("latent_decode_hbm_roofline_pct", "latent_rows_live_pct", "prefill_device_share_pct"):
        ctx["params"] = spec.metric_params(name)
        seen[name] = spec.reader(name)(ctx)
        assert spec.reader(name)(dict(ctx, program={"records": program["records"], "stats0": {}, "stats1": {}},
                                      trace=dict(trace, modules={}))) is None
    # 40 requests live all through the window at 2,100 positions each: 84,000 live positions; 13 experts hit
    need = fam.sparse_decode_step_bytes(dims, 84_000.0, 0.0, 13.0)
    assert seen["latent_decode_hbm_roofline_pct"] == pytest.approx(100.0 * need / 819e9 / (0.072 / 4))
    assert 0 < seen["latent_decode_hbm_roofline_pct"] < 100
    assert seen["latent_rows_live_pct"] == pytest.approx(100.0 * 90_000 / (64 * 6656))
    assert seen["prefill_device_share_pct"] == pytest.approx(100.0 * 1.0 / 2.8)


# -- the toy root: the family, the readers and the counters down the harness's path ---
def test_the_toy_root_rehearses_with_the_new_readers():
    env = {k: v for k, v in os.environ.items() if k != "BENCH_RUN"}
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "run.py"), "--bench-root", TOY, "--rehearse",
         "--workload", "toy-kanana.serve-docqa", "--seed", str(2**31 + 17), "--seconds", "3", "--trace", "1"],
        cwd=ROOT, env=env, timeout=900, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    assert "REHEARSAL finished: correct=True" in p.stdout and "leftovers: none" in p.stdout
    for said in ("held experts hit a step and expert layer: ", "pairs on held experts: ", "latent rows: "):
        assert said in p.stdout, (said, p.stdout[-3000:])
