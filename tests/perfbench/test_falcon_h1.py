"""The ``falcon_h1`` family's plain reference against the program at a toy
size on the CPU: the forward pass, bucketed prefill then decode through BOTH
caches of every layer (the K/V rows and the running state with its conv
tail) in float32 and in bfloat16, the same comparison with each of the
fourteen muP scalars set to one in turn, the rotation left out and other
terms of the mathematics planted out of the reference, the dense engine
serving it, the configuration's byte counts recomputed from shapes, the
new arrival process and the new reader's arithmetic, and the toy root's
rehearsal."""
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
TOY = os.path.join(HERE, "toy_falcon_h1")
CFG = json.load(open(os.path.join(TOY, "configs", "toy-falcon-h1.json")))
V, Q = CFG["vocab_size"], CFG["mamba_chunk_size"]
#: float32 on both sides, relative to the logits' norm over a served
#: sequence; the program evaluates the recurrence in chunks where the
#: reference multiplies decays step by step, folds the state layer's input
#: scalar into its parts' and reads the cache through a one-matmul form of
#: the grouped attention: what is left is rounding, 3e-7 to 6e-7 read. 3e-6
#: leaves five times of room and lies at a two-thousandth of the least
#: planted fault (7e-3: the dt scalar set to one; the others read 2e-2 to
#: 0.96: the planted test prints them).
TOL = 3e-6
#: bfloat16 compute on float32 weights against the float32 reference: eight
#: bits of mantissa in every activation and product operand through three
#: layers read 0.8e-2 to 1.1e-2 of the logits' norm (forward and served);
#: 3e-2 leaves three times of room, and float8 (the control) reads 7e-2.
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
    return weights.make_params(2**31 + 23, _dims(), 96, "float32")


def test_the_seeded_tree_is_the_tree_the_program_takes_and_every_part_is_seen(params):
    from pb import plug
    from ray_lightning_tpu.models.mixed import mixed_param_shapes

    want = mixed_param_shapes(_program_cfg())
    assert {k: tuple(v.shape) for k, v in params["blocks"].items()} == want["blocks"]
    assert {k: tuple(v.shape) for k, v in params.items() if k != "blocks"} == {
        k: v for k, v in want.items() if k != "blocks"}
    # no leaf is exactly one or zero: each term moves the result
    assert all(float(np.abs(np.asarray(v) - np.round(np.asarray(v))).max()) > 0 for v in params["blocks"].values())
    # the toy keeps the published ratios: 5 query heads a KV head, 2 groups of 16 state heads, and no scalar is one
    d = _dims()
    assert d["heads"] == 5 * d["kv_heads"] and d["ssm_groups"] == 2 and d["ssm_heads"] == 16 * d["ssm_groups"]
    assert len(d["mult"]) == 14 and all(abs(m - 1.0) > 0.05 for m in d["mult"].values())
    assert list(_program_cfg().multipliers) == [d["mult"][n] for n in plug.family_of(d).MULTIPLIERS]
    import jax.numpy as jnp

    toks = jnp.asarray(np.random.default_rng(0).integers(0, V, (2, 48)), jnp.int32)
    sizes = plug.family_of(d).part_sizes(params, toks, d)
    print("toy part sizes", sizes)
    assert 0.3 < sizes["score_std"] < 3.0  # a softmax that chooses: a wrong rotation or key scalar moves it
    parts = [sizes["A"], sizes["M"], sizes["mlp"]]
    assert max(parts) < 4 * min(parts) and min(parts) > 0.05 * sizes["embedding"]  # each write is seen in the residual


def test_forward_agrees_and_a_lower_precision_does_not(params):
    import jax.numpy as jnp

    from ray_lightning_tpu.models.gpt import gpt_forward

    dims = _dims()
    toks = jnp.asarray(np.random.default_rng(0).integers(0, V, (2, 37)), jnp.int32)  # 37: no whole chunks
    import jax

    ref = reference.logits_of(params, toks, dims)
    fwd = jax.jit(gpt_forward, static_argnums=2)
    assert _rel(fwd(params, toks, _program_cfg()), ref) < TOL
    assert 1e-3 < _rel(fwd(params, toks, _program_cfg(compute_dtype="bfloat16")), ref) < TOL_BF16
    assert _rel(reference.logits_of(params, toks, dims, lowp=True), ref) > 2 * TOL_BF16


def test_the_references_blocks_change_no_number(params, monkeypatch):
    """The head in blocks of vocabulary rows and the MLP in blocks of its
    columns (what the reference does for memory at the published widths:
    32 and 3 blocks; one each at the toy's) give the logits of one block."""
    import jax.numpy as jnp

    from pb import plug

    dims = _dims()
    fam = plug.family_of(dims)
    toks = jnp.asarray(np.random.default_rng(4).integers(0, V, (1, 19)), jnp.int32)
    whole = reference.logits_of(params, toks, dims)
    monkeypatch.setattr(fam, "_HEAD_BLOCK", 40)
    monkeypatch.setattr(fam, "_FF_BLOCK", 32)
    assert fam._blocks_of(V, 40) == 4 and fam._blocks_of(dims["ff"], 32) == 3
    assert _rel(reference.logits_of(params, toks, dims), whole) < 1e-6


# -- prefill, then decode through the state, the conv tail and the K/V rows of every layer ---------------
#: (prompt length, bucket, tokens decoded): prompts of 1, 2 and 3 tokens
#: (shorter than the conv's 3 rows of memory), of a chunk exactly and of a
#: chunk plus one, each right-padded to its bucket, and one of several
#: chunks; the fourth stops early and its slot stays frozen while the
#: others go on.
CASES = [(1, 4, 12), (2, 4, 30), (3, 4, 9), (Q, 16, 5), (Q + 1, 16, 40), (37, 64, 20)]


def _serve(params, seqs, compute_dtype="float32"):
    """The program's logits at every position of the sequences of given
    tokens: bucketed prefill into a slot each (the admission's pass: both
    halves of the cache written), then decode steps at per-slot positions,
    idle lanes beside them (two more slots than requests), a slot frozen
    once its sequence has ended."""
    import jax
    import jax.numpy as jnp

    from ray_lightning_tpu.models.gpt import _rmsnorm
    from ray_lightning_tpu.models.mixed import (
        empty_caches, mixed_decode_step, mixed_logits, mixed_rows, write_prefill_rows,
    )

    cfg = _program_cfg(compute_dtype=compute_dtype)
    n = len(CASES)
    k_cache, v_cache = empty_caches(cfg, n + 2, 96, jnp.dtype(compute_dtype))
    got = [np.zeros((len(s), V), np.float32) for s in seqs]
    rows = jax.jit(lambda prompt, n: mixed_rows(params, cfg, prompt, true_len=n))  # one program a bucket
    for slot, ((P, Pb, _), seq) in enumerate(zip(CASES, seqs)):
        prompt = np.zeros((1, Pb), np.int32)
        prompt[0, :P] = seq[:P]
        h, pf_k, pf_v, st = rows(jnp.asarray(prompt), jnp.int32(P))
        assert [int(x) for x in st[3:]] == [Pb, P] and set(pf_k) == {"full", "ssm"}
        k_cache, v_cache = write_prefill_rows(k_cache, v_cache, pf_k, pf_v, jnp.int32(slot), jnp.int32(P))
        got[slot][:P] = np.asarray(mixed_logits(_rmsnorm(h[0, :P], params["lnf_g"], cfg.norm_eps), params, cfg))
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
    import jax
    import jax.numpy as jnp

    fwd = jax.jit(lambda t: reference.logits_of(params, t, dims, lowp)[0])
    out = []
    for s in seqs:
        toks = np.zeros((1, 64), np.int32)
        toks[0, : len(s)] = s
        out.append(np.asarray(fwd(jnp.asarray(toks))[: len(s)]))
    return out


@pytest.fixture(scope="module")
def sound_reference(params, seqs):
    return _reference_logits(params, seqs, _dims())


def test_prefill_and_decode_through_both_caches_agree_with_the_full_forward(served_logits, sound_reference):
    for g, r in zip(served_logits, sound_reference):
        assert _rel(g, r) < TOL
        # position by position too, the decoded ones among them: one wrong carry would hide in a norm
        assert np.abs(g - r).max() < 1e-4 * np.abs(r).max()


def test_in_bfloat16_they_agree_to_its_rounding(params, seqs, sound_reference):
    got = _serve(params, seqs, "bfloat16")
    worst = max(_rel(g, r) for g, r in zip(got, sound_reference))
    print(f"bfloat16 compute, served: {worst:.3g}")
    assert 1e-3 < worst < TOL_BF16


# -- each scalar set to one, the rotation left out, other terms planted out of the reference -----------
def _conv_taps_reversed(fam):
    sound = fam.conv
    return {"conv": lambda xbc, w, b: sound(xbc, w[::-1], b)}


def _state_not_carried(fam):
    """The scan starts from zero again at every chunk boundary."""
    import jax.numpy as jnp

    sound = fam.scan

    def scan(x, dt, A, bh, ch, lowp):
        S = x.shape[1]
        return jnp.concatenate(
            [sound(x[:, s:s + Q], dt[:, s:s + Q], A, bh[:, s:s + Q], ch[:, s:s + Q], lowp) for s in range(0, S, Q)],
            axis=1)

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


def _interleaved_rotation(fam):
    """Pairs (2i, 2i + 1) instead of the half-split (i, i + hd/2)."""
    import jax.numpy as jnp

    def rope(x, theta):
        half = x.shape[-1] // 2
        to_split = jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1)
        y = reference.rope(to_split, theta)
        return jnp.stack([y[..., :half], y[..., half:]], -1).reshape(x.shape)

    return {"rope": rope}


def _two_norms(fam):
    """The state mixer reads a norm of its own (the attention's gain squared): not ONE normed input."""
    sound = fam.mamba

    def mamba(u, leaf, dims, lowp):
        return sound(u * leaf("ln1_g"), leaf, dims, lowp)

    return {"mamba": mamba}


def _sequential_mixers(fam):
    """Attention first, then the state mixer on the UPDATED residual: not side by side."""
    def layer(h, lp, dims, lowp):
        def leaf(name):
            return lp[name].astype(reference.F32)

        a = fam.attention(reference.rmsnorm(h, leaf("ln1_g"), dims["norm_eps"]), leaf, dims, lowp)
        h = h + a
        m = fam.mamba(reference.rmsnorm(h, leaf("ln1_g"), dims["norm_eps"]), leaf, dims, lowp)
        h = h + m
        f = fam.mlp(reference.rmsnorm(h, leaf("ln2_g"), dims["norm_eps"]), lp.__getitem__, dims, lowp)
        return h + f, (a, m, f)

    return {"layer": layer}


def _one(name):
    return lambda dims: dict(dims, mult=dict(dims["mult"], **{name: 1.0}))


#: name -> (change to the sizes, replacement pieces)
PLANTED = {"float8 (the control)": (None, None)}
PLANTED.update({f"the scalar {n} set to one": (_one(n), None) for n in (
    "embedding", "lm_head", "attn_in", "attn_out", "key", "ssm_in", "ssm_out",
    "ssm_z", "ssm_x", "ssm_b", "ssm_c", "ssm_dt", "mlp_gate", "mlp_out")})
PLANTED.update({
    "the rotation left out": (None, lambda fam: {"rope": lambda x, theta: x}),
    "the rotation over neighbouring pairs": (None, _interleaved_rotation),
    "another rotary base": (lambda dims: dict(dims, rope_theta=dims["rope_theta"] * 4.0), None),
    "a norm of its own for the state mixer": (None, _two_norms),
    "the mixers one after the other": (None, _sequential_mixers),
    "conv taps reversed": (None, _conv_taps_reversed),
    "the state not carried across a chunk boundary": (None, _state_not_carried),
    "the gate after the norm": (None, _gate_after_norm),
    "the norm over all channels instead of a group": (None, _norm_over_all_channels),
    "the group of head h as h % G": (None, _group_by_modulo),
})


@pytest.mark.parametrize("name", list(PLANTED))
def test_the_comparison_fails_with_a_term_planted_out_of_the_reference(params, seqs, served_logits, monkeypatch, name):
    from pb import plug

    change, pieces = PLANTED[name]
    dims = _dims()
    fam = plug.family_of(dims)
    assert set(dims["mult"]) == set(fam.MULTIPLIERS)
    if change is not None:
        dims = change(dims)
    if pieces is not None:
        for piece, fn in pieces(fam).items():
            monkeypatch.setattr(fam, piece, fn)
    # the longest two sequences say it: the planted reference is run over fewer rows than the sound one
    refs = _reference_logits(params, seqs[-2:], dims, lowp=name.startswith("float8"))
    worst = max(_rel(g, r) for g, r in zip(served_logits[-2:], refs))
    print(f"planted {name!r}: {worst:.3g}")
    assert worst > 10 * TOL, name


@pytest.mark.parametrize("fold", [1, 4])
def test_the_dense_engine_serves_what_the_reference_puts_first(params, fold):
    """Bucketed admission, decode fold ``fold``, idle lanes, a request that
    ends early (its slot frozen, then taken again by a shorter request
    after a longer one): every served token is the reference's first
    choice at its position, and both kinds of counter count every layer."""
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
            # into the slot the longest prompt has left: nothing of its state or of its rows may be read
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
    decoded, layers = sum(n - 1 for _, n in sizes), CFG["num_hidden_layers"]
    ssm, attn, cache = eng.ssm_stats(), eng.attn_stats(), eng.cache_stats()
    assert ssm["state_layers"] == layers and ssm["decode"]["slot_steps_live"] == decoded
    assert ssm["decode"]["slot_steps"] % (4 * fold) == 0 and ssm["decode"]["slot_steps"] > decoded
    assert ssm["prefill"] == {"rows_scanned": 4 + 64 + 16 + 4, "rows_real": sum(P for P, _ in sizes)}
    steps = ssm["decode"]["slot_steps"] // 4
    assert attn["rows_allocated"] == steps * layers * 4 * 96  # every layer's rows, every token step
    assert attn["rows_live"] == layers * sum(sum(range(P + 1, P + n)) for P, n in sizes)
    assert eng.moe_stats() == {} and set(cache) == {"full", "state"}
    assert cache["full"]["layers"] == cache["state"]["layers"] == layers and cache["full"]["rows_per_slot"] == 96
    from pb import plug

    d = _dims()
    assert cache["state"]["bytes"] == 4 * plug.family_of(d).state_bytes_per_slot(d, tail_bytes=4)
    assert cache["full"]["bytes"] == 4 * 96 * plug.family_of(d).kv_bytes_per_token(d, kv_bytes=4)


# -- the configuration at its published widths ----------------------------------------------------
def test_the_configurations_file_keeps_every_published_number_and_its_bytes_follow_from_shapes():
    import re

    from pb import plug

    spec = Spec(ROOT)
    cfg = spec.config("falcon-h1-34b-d6")
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):  # the catalog beside the guide, where this machine has it
        row = next(json.loads(ln) for ln in open(catalog) if '"Falcon-H1-34B-Instruct"' in ln)
        differ = sorted(k for k, v in row["config"].items() if cfg.get(k) != v)
        assert differ == cfg["reduced"] == ["num_hidden_layers"] and cfg["source"] == row["source_url"]
        assert cfg["published"] == {"num_hidden_layers": row["config"]["num_hidden_layers"]}
    assert cfg["num_hidden_layers"] in (5, 6) and cfg["vocab_size"] == 261120  # never under four; the whole vocabulary
    dims, pc = spec.dims(cfg), cfg["program_config"]
    fam = plug.family_of(dims)
    shapes = fam.param_shapes(dims, pc["max_seq"])
    count = lambda names: sum(int(np.prod(shapes["blocks"][n][0][1:])) for n in names)  # noqa: E731
    L = dims["layers"]
    attn = count(["full_wq", "full_wk", "full_wv", "full_wo"])
    state = count([n for n in shapes["blocks"] if n.startswith("ssm_")])
    mlp = count(["dense_wi", "dense_wo2"])
    layer = attn + state + mlp + 2 * dims["d"]
    total = L * layer + 2 * dims["vocab"] * dims["d"] + dims["d"]
    assert (attn, mlp) == (fam.attn_params(dims), fam.mlp_params(dims)) and 0 < state - fam.state_layer_params(dims) < 4e4
    assert abs(total - fam.total_params(dims)) < L * 5e4  # the counts leave out the conv, the norms and the per-head vectors
    mix = spec.traffic("serve-burstchat")["replica"]
    slots, rows = int(mix["num_slots"]), int(mix["max_seq"])
    said = cfg["deployment"]
    read = lambda what: float(re.search(what, said).group(1).replace(",", ""))  # noqa: E731
    assert read(r"attention ([\d.]+) M") == round(attn / 1e6, 2) and read(r"Mamba-2 mixer ([\d.]+) M") == round(state / 1e6, 2)
    assert read(r"MLP ([\d.]+) M") == round(mlp / 1e6, 2) and read(r"a layer ([\d.]+) M") == round(layer / 1e6, 1)
    assert read(r"head ([\d,.]+) M each") == round(dims["vocab"] * dims["d"] / 1e6, 1)
    assert read(r": ([\d,.]+) M parameters") == round(total / 1e6, 1) and read(r"parameters, ([\d.]+) GB") == round(2 * total / 1e9, 2)
    state_b = slots * L * dims["ssm_heads"] * dims["ssm_head_dim"] * dims["ssm_state"] * 4
    tails_b = slots * fam.state_bytes_per_slot(dims) - state_b
    kv_b = slots * rows * fam.kv_bytes_per_token(dims)
    assert read(r"recurrent state [^=]*= ([\d.]+) GB") == round(state_b / 1e9, 2)
    assert read(r"K/V [^=]*= ([\d.]+) GB") == round(kv_b / 1e9, 2) and read(r"conv tails ([\d.]+) GB") == round(tails_b / 1e9, 2)
    whole = 2 * total + state_b + tails_b + kv_b
    assert read(r": ([\d.]+) GB, \d+% of the chip") == round(whole / 1e9, 2)
    assert int(read(r"GB, (\d+)% of the chip")) == round(100 * whole / 16e9)
    # the program is given the same sizes, and the scalars in the order the program names them
    from ray_lightning_tpu.models.gpt import GPTConfig
    from ray_lightning_tpu.models.mixed import mixed_param_shapes

    prog = mixed_param_shapes(GPTConfig(**pc))
    assert {k: v[0] for k, v in shapes["blocks"].items()} == prog["blocks"] and shapes["wte"][0] == prog["wte"]
    assert list(GPTConfig.MULTIPLIERS) == list(fam.MULTIPLIERS)
    assert pc["multipliers"] == [dims["mult"][n] for n in fam.MULTIPLIERS]
    # what a decode step moves at 40 live requests of 450 positions: the shares the cell's why names
    step = fam.hybrid_decode_step_bytes(dims, 64, 40 * 450)
    state_share = (fam.state_weight_bytes(dims) + 2 * 64 * fam.state_bytes_per_slot(dims)) / step
    head_share = 2 * dims["vocab"] * dims["d"] / step
    assert round(100 * state_share) == 36 and round(100 * head_share) == 24


# -- the new arrival process and the new reader ------------------------------------------------------
def test_gamma_gaps_are_the_quantiles_of_a_squared_normal_and_no_other_shape_is_offered():
    from pb import plug, traffic

    gaps = np.asarray(plug.module("generators", "gamma").raw_gaps({"process": "gamma", "shape": 0.5}, 4000))
    assert abs(gaps.mean() - 0.5) < 2e-3 and abs(gaps.std() / gaps.mean() - 2 ** 0.5) < 5e-3  # Poisson's is 1
    assert (np.diff(gaps) > 0).all() and gaps[0] > 0
    with pytest.raises(ValueError, match="shape 0.5"):
        plug.module("generators", "gamma").raw_gaps({"process": "gamma", "shape": 2.0}, 10)
    mix = Spec(ROOT).traffic("serve-burstchat")
    a = traffic.serve_schedule(mix, 1, 30.0, 1000)
    b = traffic.serve_schedule(mix, 2**31 + 5, 30.0, 1000)
    assert [r["due_s"] for r in a] == [r["due_s"] for r in b] and a[5]["prompt"] != b[5]["prompt"]  # one arrangement
    due = np.asarray([r["due_s"] for r in a if r["counted"]])
    assert len(due) == round(30 * mix["arrival"]["rate_rps"]) and 0 <= due.min() and due.max() < 30
    # the generator puts a request at the MIDDLE of its gap, so the time between two requests is the mean of two
    # gaps and varies 1 / sqrt(2) as much as a gap: 1.0 here where the Poisson cells read 0.71 — burstier by sqrt(2)
    cv = np.diff(due).std() / np.diff(due).mean()
    calm = traffic.serve_schedule(dict(mix, arrival=dict(mix["arrival"], process="poisson")), 1, 30.0, 1000)
    due_calm = np.asarray([r["due_s"] for r in calm if r["counted"]])
    cv_calm = np.diff(due_calm).std() / np.diff(due_calm).mean()
    assert 0.9 < cv < 1.1 and 0.62 < cv_calm < 0.78 and 1.3 < cv / cv_calm < 1.5, (cv, cv_calm)


def test_state_step_bytes_pct_on_a_hand_made_run():
    """64 slots, 100 token steps: the reader's arithmetic from the counters
    and the family's byte functions, and nothing from a program or a
    family that lacks them."""
    spec = Spec(ROOT)
    read = spec.reader("state_step_bytes_pct")
    dims = spec.dims(spec.config("falcon-h1-34b-d6"))
    stats = lambda steps, rows: {  # noqa: E731
        "ssm": {"decode": {"slot_steps": 64 * steps, "slot_steps_live": 40 * steps},
                "prefill": {"rows_scanned": 0, "rows_real": 0}},
        "attn": {"rows_allocated": 64 * 2048 * 6 * steps, "rows_visited": rows, "rows_live": rows}}
    ctx = {"dims": dims, "mix": {"replica": {"num_slots": 64}},
           "program": {"stats0": stats(10, 1000), "stats1": stats(110, 1000 + 100 * 6 * 18000)}}
    from pb import plug

    fam = plug.family_of(dims)
    state = fam.state_weight_bytes(dims) + 2 * 64 * fam.state_bytes_per_slot(dims)
    total = 2 * fam.matmul_params(dims) + 2 * 64 * fam.state_bytes_per_slot(dims) + 18000 * fam.kv_bytes_per_token(dims)
    assert abs(read(ctx) - 100.0 * state / total) < 1e-9 and 35 < read(ctx) < 37
    assert read(dict(ctx, program={"stats0": {}, "stats1": {}})) is None  # the parent: no counters
    assert read(dict(ctx, dims=spec.dims(spec.config("nemotron-3-super-d11-ep4")))) is None  # no state_weight_bytes


# -- the toy root: the family, the generator, the readers and the counters down the harness's path ---
def test_the_toy_root_rehearses_with_the_new_generator_and_reader():
    env = {k: v for k, v in os.environ.items() if k != "BENCH_RUN"}
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "run.py"), "--bench-root", TOY, "--rehearse",
         "--workload", "toy-falcon-h1.serve-burstchat", "--seed", str(2**31 + 17), "--seconds", "3", "--trace", "1"],
        cwd=ROOT, env=env, timeout=900, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    assert "REHEARSAL finished: correct=True" in p.stdout and "leftovers: none" in p.stdout
    for said in ("state layers: ", "decode attention: ", "state layers' bytes: "):
        assert said in p.stdout, (said, p.stdout[-3000:])
