"""The ``deepseek_v3`` family as kakaocorp/kanana-2-30b-a3b-instruct-2601
publishes it (no query latent, one expert group, sigmoid scores): latent
attention, a dense leading layer, then routed experts beside shared ones,
as one chip's share of a deployment that divides the experts and the
vocabulary. The equations, per layer, ``x`` the residual, RMSNorm with
gain and ``rms_norm_eps``, no biases:

- ``h = RMSNorm(x)``. ``q = h Wq``: H heads of ``qk_nope_head_dim +
  qk_rope_head_dim``, each ``[q_nope; q_rope]`` (``q_lora_rank`` null: no
  query latent).
- ``[c; k_r] = h W_kv_a`` (``kv_lora_rank`` and ``qk_rope_head_dim``
  wide). ``c~ = RMSNorm(c)`` with a gain of its own. ``k_rope =
  RoPE(k_r)``: ONE rotary key a position, shared by all heads.
- ``[k_nope,h; v_h] = c~ W_kv_b`` for each head (``qk_nope_head_dim`` and
  ``v_head_dim`` wide).
- RoPE: base ``rope_theta``, no scaling, on ``q_rope`` and ``k_r`` only,
  over INTERLEAVED pairs ``(2i, 2i + 1)`` at frequency ``theta^(-2i /
  qk_rope_head_dim)`` (``rope_interleave`` true). Rotated here in place;
  the published code moves the pairs to a half-split order first, a
  permutation queries and keys share and no score sees.
- ``s_h,ij = (q_nope,h,i . k_nope,h,j + RoPE(q_rope,h)_i . k_rope,j) /
  sqrt(qk_head_dim)``, causal softmax over j, ``o_h = sum_j p_h,ij
  v_h,j``; ``x <- x + concat(o) Wo``.
- ``h2 = RMSNorm(x)``. The first ``first_k_dense_replace`` layers: ``x <- x
  + (silu(h2 Wg) * (h2 Wu)) Wd`` at ``intermediate_size``.
- The others: ``sigma = sigmoid(h2 Wr)`` over ALL published experts; T =
  the ``num_experts_per_tok`` largest of ``sigma + b`` (``b`` for the
  choice only; one group, no group limit); ``w_e = routed_scaling_factor
  * sigma_e / sum_{e' in T} sigma_e'``; ``x <- x + sum_{e in T and held}
  w_e FFN_e(h2) + FFN_shared(h2)``, ``FFN_e`` a SwiGLU of
  ``moe_intermediate_size``, ``FFN_shared`` ONE SwiGLU of
  ``n_shared_experts * moe_intermediate_size`` (the shared experts as the
  published block fuses them). The sum runs over the experts THIS share
  holds, the choice and the normalisation over all; the shared expert is
  computed whole on every share. What the absent experts would add is
  left out, and the partial result goes on.
- Final RMSNorm, logits over the held rows of the untied head.

A configuration that takes another branch of the published block (a
query latent, rotary scaling, expert groups, softmax scores, biases) is
refused by ``dims``: its equations are not written here.

``logits`` is the repo's plain reference for this family, in the
MATERIALISED form only: every head's keys and values are built from the
latent and attended as any attention's. ``jax.numpy`` in float32 at the
highest precision, no cache, no absorption of ``W_kv_b`` into the query,
attention a block of queries at a time so that the scores fit, the
experts one at a time. It imports nothing of the program. Its pieces are
module-level functions so that a test can put a deliberately wrong one in
their place.

See ``families/gpt2.py`` for what a family file is.
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from pb import reference as R

_Q_BLOCK = 256
#: Keys that choose a branch of the published block, and the branch written above.
_BRANCH = {
    "q_lora_rank": None, "rope_scaling": None, "rope_interleave": True, "scoring_func": "sigmoid",
    "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1, "norm_topk_prob": True, "moe_layer_freq": 1,
    "attention_bias": False, "hidden_act": "silu", "tie_word_embeddings": False,
}


def dims(cfg: Dict[str, Any]) -> Dict[str, Any]:
    other = {k: cfg.get(k) for k, want in _BRANCH.items() if cfg.get(k) != want}
    if other:
        raise ValueError(f"families/deepseek_v3.py writes down the block with {_BRANCH}; this configuration has {other}")
    pub = cfg.get("published", {})
    held = cfg.get("experts_held") or [0, int(cfg["n_routed_experts"])]
    if int(cfg["qk_head_dim"]) != int(cfg["qk_nope_head_dim"]) + int(cfg["qk_rope_head_dim"]):
        raise ValueError("qk_head_dim is qk_nope_head_dim + qk_rope_head_dim")
    return {
        "vocab": int(cfg["vocab_size"]), "layers": int(cfg["num_hidden_layers"]),
        "d": int(cfg["hidden_size"]), "heads": int(cfg["num_attention_heads"]),
        "nope": int(cfg["qk_nope_head_dim"]), "rope_dim": int(cfg["qk_rope_head_dim"]),
        "v_head_dim": int(cfg["v_head_dim"]), "lora": int(cfg["kv_lora_rank"]),
        "rope_theta": float(cfg["rope_theta"]),
        "first_dense": int(cfg["first_k_dense_replace"]),
        "ff": int(cfg["intermediate_size"]), "expert_ff": int(cfg["moe_intermediate_size"]),
        "shared_ff": int(cfg["n_shared_experts"]) * int(cfg["moe_intermediate_size"]),
        # the router keeps its published width; this share holds some of them
        "experts": int(pub.get("n_routed_experts", cfg["n_routed_experts"])),
        "experts_held": [int(held[0]), int(held[1])],
        "top_k": int(cfg["num_experts_per_tok"]), "scale": float(cfg["routed_scaling_factor"]),
        "norm_eps": float(cfg["rms_norm_eps"]),
        # no layer keeps a window of positions (the sparse step's roofline reader asks)
        "window": 0,
    }


def _counts(dims: Dict[str, Any]) -> Dict[str, int]:
    dense = min(dims["first_dense"], dims["layers"])
    return {"dense": dense, "moe": dims["layers"] - dense}


def param_shapes(dims: Dict[str, Any], max_seq: int) -> Dict[str, Any]:
    """The program's tree for mixed layers (``models/mixed.py``): leaves of
    one kind of layer stacked over the layers of that kind, gate and up of
    a SwiGLU as two (D, F) matrices side by side. ``lat_wkv_a`` maps the
    stream to ``[latent; rotary key]``, ``lat_wkv_b`` (heads leading) the
    normed latent to each head's ``[keys of its no-position dims; values]``.
    Kinds: every term moves the logits. Attention's ``lat_wo``, the dense
    MLP's and the routed experts' down-projections are residual writes
    (``r``); the shared experts' down-projection is ``w``, so that the fused
    shared expert is the larger part of an expert layer's output (0.37 of a
    stream of 1.4 at the last layer, where one routed expert at its weight
    of 0.4 adds 0.018). With it ``r`` too the stream was 0.36 and one expert
    exchanged for its neighbour at the edge of the top 6 — which bfloat16's
    rounding through sixteen layers does to a token in three — moved it by
    5%: the served tokens read 0.45-1.13 under the reference's best where
    the float8 control reads 1.05-1.52 (my chip runs, PR 38); now 0.10-0.29
    against 1.48-1.93. No term may move the logits so much that a rounding's
    choice outweighs an error (PR 32 found a cell without a check that
    way)."""
    L, D, H, V = dims["layers"], dims["d"], dims["heads"], dims["vocab"]
    dn, dr, dv, r, n = dims["nope"], dims["rope_dim"], dims["v_head_dim"], dims["lora"], _counts(dims)
    blocks: Dict[str, Any] = {
        "ln1_g": ((L, D), "g"), "ln2_g": ((L, D), "g"),
        "lat_wq": ((L, D, H, dn + dr), "w"), "lat_wkv_a": ((L, D, r + dr), "w"), "lat_kv_g": ((L, r), "g"),
        "lat_wkv_b": ((L, H, r, dn + dv), "w"), "lat_wo": ((L, H, dv, D), "r"),
    }
    if n["dense"]:
        blocks.update({"dense_wi": ((n["dense"], 2, D, dims["ff"]), "w"),
                       "dense_wo2": ((n["dense"], dims["ff"], D), "r")})
    if n["moe"]:
        e, held, F, Fs = n["moe"], dims["experts_held"][1], dims["expert_ff"], dims["shared_ff"]
        blocks.update({
            "moe_router": ((e, D, dims["experts"]), "w"), "moe_router_bias": ((e, dims["experts"]), "w"),
            "moe_wi": ((e, held, 2, D, F), "w"), "moe_wo2": ((e, held, F, D), "r"),
            "moe_shared_wi": ((e, 2, D, Fs), "w"), "moe_shared_wo2": ((e, Fs, D), "w"),
        })
    return {"wte": ((V, D), "w"), "lm_head": ((V, D), "w"), "lnf_g": ((D,), "g"), "blocks": blocks}


SPLIT = {
    "blocks/dense_wi": (1, ("gate", "up")), "blocks/moe_wi": (2, ("gate", "up")),
    "blocks/moe_shared_wi": (1, ("gate", "up")),
}


# -- the reference's pieces --------------------------------------------------------
def rope(x: jax.Array, theta: float) -> jax.Array:
    """Rotate (B, S, H, d) by position over neighbouring pairs ``(2i, 2i +
    1)``, in place: pair i turns by ``pos * theta^(-2i / d)``."""
    S, d = x.shape[1], x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=R.F32) / d)
    ang = jnp.arange(S, dtype=R.F32)[:, None] * freqs[None]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos], axis=-1).reshape(x.shape)


def shared_key(k_r: jax.Array, theta: float, heads: int) -> jax.Array:
    """The one rotary key a position (B, S, d), rotated once and handed to
    every head alike: (B, S, H, d)."""
    k = rope(k_r[:, :, None, :], theta)
    return jnp.broadcast_to(k, k.shape[:2] + (heads, k.shape[-1]))


def latent_norm(c: jax.Array, g: jax.Array, eps: float) -> jax.Array:
    return R.rmsnorm(c, g, eps)


def score_scale(dims: Dict[str, Any]) -> float:
    """Over the whole q·k head, its rotary part included."""
    return 1.0 / math.sqrt(dims["nope"] + dims["rope_dim"])


def attention(q, k, v, scale: float, lowp: bool):
    """Causal softmax attention, q and k (B, S, H, dqk), v (B, S, H, dv),
    ``_Q_BLOCK`` queries at a time against all keys."""
    B, S, H, dqk = q.shape
    blk = min(_Q_BLOCK, S)
    nb = -(-S // blk)
    qp = jnp.pad(q, ((0, 0), (0, nb * blk - S), (0, 0), (0, 0)))
    qb = qp.reshape(B, nb, blk, H, dqk).transpose(1, 0, 2, 3, 4)
    j = jnp.arange(S)[None, :]

    def one(args):
        qi, i0 = args
        i = i0 + jnp.arange(blk)[:, None]
        s = R.mm("bqhd,bkhd->bhqk", qi, k, lowp) * scale
        p = jax.nn.softmax(jnp.where((j <= i)[None, None], s, -jnp.inf), axis=-1)
        return R.mm("bhqk,bkhd->bqhd", p, v, lowp)

    o = jax.lax.map(one, (qb, jnp.arange(nb) * blk))  # (nb, B, blk, H, dv)
    return o.transpose(1, 0, 2, 3, 4).reshape(B, nb * blk, H, -1)[:, :S]


def latent_attention(a, leaf, dims: Dict[str, Any], lowp: bool):
    """The attention layer over its normed input a (B, S, D), materialised:
    keys and values of every head from the latent, then plain attention."""
    r, dn, H, eps = dims["lora"], dims["nope"], dims["heads"], dims["norm_eps"]
    q = R.mm("bsd,dhk->bshk", a, leaf("wq"), lowp)
    ckr = R.mm("bsd,dc->bsc", a, leaf("wkv_a"), lowp)
    c = latent_norm(ckr[..., :r], leaf("kv_g"), eps)
    kv = R.mm("bsc,hck->bshk", c, leaf("wkv_b"), lowp)
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], dims["rope_theta"])], -1)
    k = jnp.concatenate([kv[..., :dn], shared_key(ckr[..., r:], dims["rope_theta"], H)], -1)
    o = attention(q, k, kv[..., dn:], score_scale(dims), lowp)
    return R.mm("bshk,hkd->bsd", o, leaf("wo"), lowp)


def swiglu(t, wi, wo2, lowp: bool):
    z = R.mm("td,cdf->tcf", t, wi, lowp)
    return R.mm("tf,fd->td", jax.nn.silu(z[:, 0]) * z[:, 1], wo2, lowp)


def route(t, wr, b, dims: Dict[str, Any], lowp: bool) -> jax.Array:
    """(T, D) -> (T, E) weights over ALL experts: ``scale * sigma_e /
    sum_{T} sigma`` for the chosen, zero for the rest."""
    sigma = jax.nn.sigmoid(R.mm("td,de->te", t, wr, lowp))
    _, top = jax.lax.top_k(sigma + b, dims["top_k"])
    chosen = jnp.zeros_like(sigma).at[jnp.arange(sigma.shape[0])[:, None], top].set(1.0)
    return dims["scale"] * sigma * chosen / jnp.sum(sigma * chosen, -1, keepdims=True)


def experts(t, w, wi, wo2, dims: Dict[str, Any], lowp: bool) -> jax.Array:
    """``sum_{e held} w_e FFN_e(t)``, an expert at a time (the weights
    arrive in the type they are held in and are widened one expert at a
    time)."""
    first, count = dims["experts_held"]

    def one(acc, args):
        w_e, wi_e, wo_e = args
        return acc + w_e[:, None] * swiglu(t, wi_e.astype(R.F32), wo_e.astype(R.F32), lowp), None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(t), (w[:, first:first + count].T, wi, wo2))
    return acc


def shared(t, wi, wo2, lowp: bool):
    """The shared experts, fused into one SwiGLU, on every token whole."""
    return swiglu(t, wi, wo2, lowp)


def expert_layer(h2, leaf, raw, dims: Dict[str, Any], lowp: bool):
    B, S, D = h2.shape
    t = h2.reshape(B * S, D)
    w = route(t, leaf("router"), leaf("router_bias"), dims, lowp)
    out = experts(t, w, raw("wi"), raw("wo2"), dims, lowp) + shared(t, leaf("shared_wi"), leaf("shared_wo2"), lowp)
    return out.reshape(B, S, D)


def logits(params: Dict[str, Any], tokens: jax.Array, dims: Dict[str, Any], lowp: bool = False) -> jax.Array:
    eps, blocks, n = dims["norm_eps"], params["blocks"], _counts(dims)
    x = params["wte"].astype(R.F32)[tokens]
    B, S, D = x.shape

    def held(prefix, i):
        """Layer ``i``'s leaves of one kind, by name, in the type they are held in."""
        return lambda name: blocks[f"{prefix}_{name}"][i]

    def wide(prefix, i):
        return lambda name: blocks[f"{prefix}_{name}"][i].astype(R.F32)

    for l in range(dims["layers"]):
        a = R.rmsnorm(x, blocks["ln1_g"][l].astype(R.F32), eps)
        x = x + latent_attention(a, wide("lat", l), dims, lowp)
        h2 = R.rmsnorm(x, blocks["ln2_g"][l].astype(R.F32), eps)
        if l < n["dense"]:
            leaf = wide("dense", l)
            x = x + swiglu(h2.reshape(B * S, D), leaf("wi"), leaf("wo2"), lowp).reshape(B, S, D)
        else:
            e = l - n["dense"]
            x = x + expert_layer(h2, wide("moe", e), held("moe", e), dims, lowp)
    x = R.rmsnorm(x, params["lnf_g"].astype(R.F32), eps)
    return R.mm("bsd,vd->bsv", x, params["lm_head"].astype(R.F32), lowp)


# -- what the algorithm needs, from shapes -------------------------------------------
def _attn_params(dims: Dict[str, Any]) -> int:
    d, H, dn, dr, dv, r = dims["d"], dims["heads"], dims["nope"], dims["rope_dim"], dims["v_head_dim"], dims["lora"]
    return d * H * (dn + dr) + d * (r + dr) + r * H * (dn + dv) + H * dv * d


def expert_params(dims: Dict[str, Any]) -> int:
    """One routed expert's three matrices."""
    return 3 * dims["d"] * dims["expert_ff"]


def always_read_params(dims: Dict[str, Any]) -> int:
    """Matmul parameters a decode step reads whatever the routing:
    attention, the dense MLP, each expert layer's router and shared
    expert, and the head."""
    n, d = _counts(dims), dims["d"]
    return (dims["layers"] * _attn_params(dims) + n["dense"] * 3 * d * dims["ff"]
            + n["moe"] * (d * dims["experts"] + 3 * d * dims["shared_ff"]) + dims["vocab"] * d)


def matmul_params(dims: Dict[str, Any]) -> int:
    """Every matmul parameter held here: what a token's path could touch."""
    return always_read_params(dims) + _counts(dims)["moe"] * dims["experts_held"][1] * expert_params(dims)


def total_params(dims: Dict[str, Any]) -> int:
    return matmul_params(dims) + dims["vocab"] * dims["d"]


def attn_flops_per_token_fwd(dims: Dict[str, Any], seq: int) -> float:
    """Scores and values of the materialised form (what a prefill needs);
    the projections to and from the latent are matmul parameters."""
    return dims["layers"] * 2.0 * dims["heads"] * (dims["nope"] + dims["rope_dim"] + dims["v_head_dim"]) * (seq + 1) / 2.0


def kv_bytes_per_token(dims: Dict[str, Any], kv_bytes: int = 2) -> int:
    """What the cache keeps of one position in all layers: the latent and
    the rotary key, no V."""
    return dims["layers"] * (dims["lora"] + dims["rope_dim"]) * kv_bytes


def sparse_decode_step_bytes(dims: Dict[str, Any], live_positions: float, window_positions: float,
                             experts_hit_per_layer: float, weight_bytes: int = 2, kv_bytes: int = 2) -> float:
    """HBM bytes one decode token step has to read: attention, dense,
    shared, router and head weights once; one expert's weights for each
    held expert that a token hit, in each expert layer; the cached rows of
    the live positions (``window_positions`` is the sparse step's reader's
    and counts nothing here: no layer has a window)."""
    return (always_read_params(dims) * weight_bytes
            + _counts(dims)["moe"] * experts_hit_per_layer * expert_params(dims) * weight_bytes
            + live_positions * kv_bytes_per_token(dims, kv_bytes))


# -- what the program counted, over the window ---------------------------------------
def moe_window(program: Dict[str, Any]) -> Any:
    """The window's share of the replica's ``stats()["moe"]`` (monotone
    totals, so the difference of the two calls that bracket the window is
    exactly the window): ``{"expert_layers", "decode": {...}, "prefill":
    {...}}``, or None from a program that has no such counters."""
    m0 = (program.get("stats0") or {}).get("moe")
    m1 = (program.get("stats1") or {}).get("moe")
    if not m0 or not m1:
        return None
    return {"expert_layers": int(m1["expert_layers"]),
            **{ph: {k: m1[ph][k] - m0[ph].get(k, 0) for k in m1[ph]} for ph in ("decode", "prefill")}}


def experts_hit_per_step(program: Dict[str, Any]) -> Any:
    """Held experts that got a token, per expert layer and decode token
    step of the window; None without counters or without a step."""
    w = moe_window(program)
    if w is None or w["decode"]["token_steps"] <= 0:
        return None
    # a run whose program counted no expert layer (a hand-made one) reads its hits as they are
    return w["decode"]["experts_hit"] / (w["decode"]["token_steps"] * max(1, w["expert_layers"]))
