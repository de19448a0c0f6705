"""The ``mimo_v2_flash`` family (XiaomiMiMo/MiMo-V2-Flash): layers of two
attention kinds and two MLP kinds, as one chip's share of a deployment
that divides the experts and the vocabulary. The equations, per layer
``l`` with attention kind ``t(l)`` of ``hybrid_layer_pattern`` (0 full, 1
window) and MLP kind of ``moe_layer_freq`` (0 dense, 1 experts):

- ``h = RMSNorm(x)``; ``q = h Wq`` (H heads of ``head_dim``), ``k = h Wk``
  (Hkv heads of ``head_dim``), ``v = h Wv`` (Hkv heads of ``v_head_dim``);
  Hkv is ``num_key_value_heads`` in full layers and
  ``swa_num_key_value_heads`` in window layers. No biases.
- Rotary on the first ``int(partial_rotary_factor * head_dim)`` dims of
  every q and k head (half-split pairs), the rest pass; base ``rope_theta``
  in full layers, ``swa_rope_theta`` in window layers.
- ``v <- attention_value_scale * v``, before attention (ASSUMED place: the
  config gives the number only; it commutes with the softmax sum).
- ``s_ij = q_i . k_j / sqrt(head_dim)``, causal; window layers keep only
  ``i - sliding_window < j <= i``.
- Full layers: softmax over j. Window layers
  (``add_swa_attention_sink_bias``): with the learnable logit ``b_h`` of
  query head h, ``p_ij = exp(s_ij) / (exp(b_h) + sum_j' exp(s_ij'))``: the
  sink takes probability and gives no value.
- ``x <- x + concat(o) Wo``; ``h2 = RMSNorm(x)``.
- Dense: ``x <- x + (silu(h2 Wg) * (h2 Wu)) Wd``.
- Experts: ``sigma = sigmoid(h2 Wr)`` over ALL published experts; the
  chosen set T is the top ``num_experts_per_tok`` of ``sigma + c`` (``c``
  the correction bias, for the choice only; one group, no group limit);
  ``w_e = sigma_e / sum_{e' in T} sigma_e'``; ``x <- x + sum_{e in T and
  held} w_e FFN_e(h2)``: the sum runs over the experts THIS share holds,
  the choice and the normalisation over all. What the absent experts
  would add is left out, and the partial result goes on.
- Final RMSNorm, logits over the held rows of the vocabulary.

Departures: the three multi-token-prediction layers that the model's
card mentions have no key in the config and are draft heads, not part of
this forward pass: left out.

``logits`` is the repo's plain reference for this family: ``jax.numpy``
in float32 at the highest precision, no cache, no kernel, attention a
block of queries at a time so that the scores fit, the experts one at a
time. It imports nothing of the program. Its pieces (``route``,
``attention``) are module-level functions so that a test can put a
deliberately wrong one in their place.

See ``families/gpt2.py`` for what a family file is.
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from pb import reference as R

_Q_BLOCK = 256


def dims(cfg: Dict[str, Any]) -> Dict[str, Any]:
    pub = cfg.get("published", {})
    held = cfg.get("experts_held") or [0, int(cfg["n_routed_experts"])]
    hd = int(cfg["head_dim"])
    return {
        "vocab": int(cfg["vocab_size"]), "layers": int(cfg["num_hidden_layers"]),
        "d": int(cfg["hidden_size"]), "heads": int(cfg["num_attention_heads"]),
        "head_dim": hd, "v_head_dim": int(cfg["v_head_dim"]),
        "kv_heads": int(cfg["num_key_value_heads"]),
        "swa_kv_heads": int(cfg["swa_num_key_value_heads"]),
        "rope_dim": int(float(cfg["partial_rotary_factor"]) * hd),
        "rope_theta": float(cfg["rope_theta"]), "swa_rope_theta": float(cfg["swa_rope_theta"]),
        "window": int(cfg["sliding_window"]),
        "swa_sink": bool(cfg["add_swa_attention_sink_bias"]),
        "full_sink": bool(cfg["add_full_attention_sink_bias"]),
        "value_scale": float(cfg["attention_value_scale"]),
        "attn_kinds": [int(x) for x in cfg["hybrid_layer_pattern"]],
        "mlp_kinds": [int(x) for x in cfg["moe_layer_freq"]],
        "ff": int(cfg["intermediate_size"]), "expert_ff": int(cfg["moe_intermediate_size"]),
        # the router keeps its published width; this share holds some of them
        "experts": int(pub.get("n_routed_experts", cfg["n_routed_experts"])),
        "experts_held": [int(held[0]), int(held[1])],
        "top_k": int(cfg["num_experts_per_tok"]),
        "norm_eps": float(cfg["layernorm_epsilon"]),
    }


def _counts(dims: Dict[str, Any]) -> Dict[str, int]:
    a, m = dims["attn_kinds"], dims["mlp_kinds"]
    return {"full": a.count(0), "swa": a.count(1), "dense": m.count(0), "moe": m.count(1)}


def param_shapes(dims: Dict[str, Any], max_seq: int) -> Dict[str, Any]:
    """The program's tree for mixed layers (``models/mixed.py``): leaves of
    one kind of layer stacked over the layers of that kind, gate and up of
    a SwiGLU as two (D, F) matrices side by side. Kinds: every
    term moves the logits — the sink logits lie near one (``g``), the
    correction biases are as wide as the gaps between neighbouring
    scores (``w``)."""
    L, D, H, V = dims["layers"], dims["d"], dims["heads"], dims["vocab"]
    dqk, dv, n = dims["head_dim"], dims["v_head_dim"], _counts(dims)
    blocks: Dict[str, Any] = {"ln1_g": ((L, D), "g"), "ln2_g": ((L, D), "g")}
    for p, hkv, sink in (("full", dims["kv_heads"], dims["full_sink"]),
                         ("swa", dims["swa_kv_heads"], dims["swa_sink"])):
        if not n[p]:
            continue
        blocks.update({
            f"{p}_wq": ((n[p], D, H, dqk), "w"), f"{p}_wk": ((n[p], D, hkv, dqk), "w"),
            f"{p}_wv": ((n[p], D, hkv, dv), "w"), f"{p}_wo": ((n[p], H, dv, D), "r"),
        })
        if sink:
            blocks[f"{p}_sink"] = ((n[p], H), "g")
    if n["dense"]:
        blocks.update({"dense_wi": ((n["dense"], 2, D, dims["ff"]), "w"),
                       "dense_wo2": ((n["dense"], dims["ff"], D), "r")})
    if n["moe"]:
        held, F = dims["experts_held"][1], dims["expert_ff"]
        blocks.update({
            "moe_router": ((n["moe"], D, dims["experts"]), "w"),
            "moe_router_bias": ((n["moe"], dims["experts"]), "w"),
            "moe_wi": ((n["moe"], held, 2, D, F), "w"), "moe_wo2": ((n["moe"], held, F, D), "r"),
        })
    return {"wte": ((V, D), "w"), "lm_head": ((V, D), "w"), "lnf_g": ((D,), "g"), "blocks": blocks}


SPLIT = {"blocks/dense_wi": (1, ("gate", "up")), "blocks/moe_wi": (2, ("gate", "up"))}


# -- the reference's pieces --------------------------------------------------------
def rope(x: jax.Array, theta: float, width: int) -> jax.Array:
    """Rotate the first ``width`` dims of (B, S, H, hd) by position,
    half-split pairs ``(i, i + width/2)``; the rest pass."""
    return jnp.concatenate([R.rope(x[..., :width], theta), x[..., width:]], -1)


def attention(q, k, v, window: int, sink, lowp: bool):
    """Causal softmax attention, q (B,S,H,dqk), k (B,S,Hkv,dqk), v
    (B,S,Hkv,dv), ``_Q_BLOCK`` queries at a time against all keys;
    ``sink`` (H,) or None joins the normalisation and gives no value."""
    B, S, H, dqk = q.shape
    rep = H // k.shape[2]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    blk = min(_Q_BLOCK, S)
    nb = -(-S // blk)
    qp = jnp.pad(q, ((0, 0), (0, nb * blk - S), (0, 0), (0, 0)))
    qb = qp.reshape(B, nb, blk, H, dqk).transpose(1, 0, 2, 3, 4)
    j = jnp.arange(S)[None, :]

    def one(args):
        qi, i0 = args
        i = i0 + jnp.arange(blk)[:, None]
        s = R.mm("bqhd,bkhd->bhqk", qi, k, lowp) / math.sqrt(dqk)
        ok = j <= i
        if window:
            ok = ok & (j > i - window)
        s = jnp.where(ok[None, None], s, -jnp.inf)
        if sink is not None:
            s = jnp.concatenate(
                [s, jnp.broadcast_to(sink[None, :, None, None], s.shape[:3] + (1,))], -1)
        p = jax.nn.softmax(s, axis=-1)[..., :S]
        return R.mm("bhqk,bkhd->bqhd", p, v, lowp)

    o = jax.lax.map(one, (qb, jnp.arange(nb) * blk))  # (nb, B, blk, H, dv)
    return o.transpose(1, 0, 2, 3, 4).reshape(B, nb * blk, H, -1)[:, :S]


def route(h2, wr, c, dims: Dict[str, Any], lowp: bool) -> jax.Array:
    """(T, D) -> (T, E) weights over ALL experts: ``sigma_e / sum_{T}
    sigma`` for the chosen, zero for the rest."""
    sigma = jax.nn.sigmoid(R.mm("td,de->te", h2, wr, lowp))
    _, top = jax.lax.top_k(sigma + c, dims["top_k"])
    chosen = jnp.zeros_like(sigma).at[jnp.arange(sigma.shape[0])[:, None], top].set(1.0)
    return sigma * chosen / jnp.sum(sigma * chosen, -1, keepdims=True)


def experts(h2, w, wi, wo2, dims: Dict[str, Any], lowp: bool) -> jax.Array:
    """``sum_{e held} w_e FFN_e(h2)``, an expert at a time (the weights
    arrive in the type they are held in and are widened one expert at a
    time)."""
    first, count = dims["experts_held"]

    def one(acc, args):
        w_e, wi_e, wo_e = args
        z = R.mm("td,cdf->tcf", h2, wi_e.astype(R.F32), lowp)
        y = R.mm("tf,fd->td", jax.nn.silu(z[:, 0]) * z[:, 1], wo_e.astype(R.F32), lowp)
        return acc + w_e[:, None] * y, None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(h2), (w[:, first:first + count].T, wi, wo2))
    return acc


def logits(params: Dict[str, Any], tokens: jax.Array, dims: Dict[str, Any], lowp: bool = False) -> jax.Array:
    eps, blocks = dims["norm_eps"], params["blocks"]
    x = params["wte"].astype(R.F32)[tokens]
    B, S, D = x.shape
    seen = {"full": 0, "swa": 0, "dense": 0, "moe": 0}

    def leaf(prefix: str, name: str):
        return blocks[f"{prefix}_{name}"][seen[prefix]]

    for l in range(dims["layers"]):
        p = "swa" if dims["attn_kinds"][l] else "full"
        a = R.rmsnorm(x, blocks["ln1_g"][l].astype(R.F32), eps)
        q = R.mm("bsd,dhk->bshk", a, leaf(p, "wq").astype(R.F32), lowp)
        k = R.mm("bsd,dhk->bshk", a, leaf(p, "wk").astype(R.F32), lowp)
        v = R.mm("bsd,dhk->bshk", a, leaf(p, "wv").astype(R.F32), lowp) * dims["value_scale"]
        theta = dims["swa_rope_theta"] if p == "swa" else dims["rope_theta"]
        q, k = rope(q, theta, dims["rope_dim"]), rope(k, theta, dims["rope_dim"])
        sink = leaf(p, "sink").astype(R.F32) if dims[f"{p}_sink"] else None
        o = attention(q, k, v, dims["window"] if p == "swa" else 0, sink, lowp)
        x = x + R.mm("bshk,hkd->bsd", o, leaf(p, "wo").astype(R.F32), lowp)
        seen[p] += 1
        h2 = R.rmsnorm(x, blocks["ln2_g"][l].astype(R.F32), eps)
        if dims["mlp_kinds"][l]:
            t = h2.reshape(B * S, D)
            w = route(t, leaf("moe", "router").astype(R.F32), leaf("moe", "router_bias").astype(R.F32), dims, lowp)
            x = x + experts(t, w, leaf("moe", "wi"), leaf("moe", "wo2"), dims, lowp).reshape(B, S, D)
            seen["moe"] += 1
        else:
            z = R.mm("bsd,cdf->bscf", h2, leaf("dense", "wi").astype(R.F32), lowp)
            x = x + R.mm("bsf,fd->bsd", jax.nn.silu(z[:, :, 0]) * z[:, :, 1], leaf("dense", "wo2").astype(R.F32), lowp)
            seen["dense"] += 1
    x = R.rmsnorm(x, params["lnf_g"].astype(R.F32), eps)
    return R.mm("bsd,vd->bsv", x, params["lm_head"].astype(R.F32), lowp)


# -- what the algorithm needs, from shapes -------------------------------------------
def _attn_params(dims: Dict[str, Any], p: str) -> int:
    hkv = dims["swa_kv_heads"] if p == "swa" else dims["kv_heads"]
    d, H, dqk, dv = dims["d"], dims["heads"], dims["head_dim"], dims["v_head_dim"]
    return d * H * dqk + d * hkv * (dqk + dv) + H * dv * d


def expert_params(dims: Dict[str, Any]) -> int:
    """One expert's three matrices."""
    return 3 * dims["d"] * dims["expert_ff"]


def always_read_params(dims: Dict[str, Any]) -> int:
    """Matmul parameters a decode step reads whatever the routing:
    attention, the dense MLPs, the routers and the head."""
    n = _counts(dims)
    return (n["full"] * _attn_params(dims, "full") + n["swa"] * _attn_params(dims, "swa")
            + n["dense"] * 3 * dims["d"] * dims["ff"] + n["moe"] * dims["d"] * dims["experts"]
            + dims["vocab"] * dims["d"])


def matmul_params(dims: Dict[str, Any]) -> int:
    """Every matmul parameter held here: what a token's path could touch."""
    return always_read_params(dims) + _counts(dims)["moe"] * dims["experts_held"][1] * expert_params(dims)


def total_params(dims: Dict[str, Any]) -> int:
    return matmul_params(dims) + dims["vocab"] * dims["d"]


def attn_flops_per_token_fwd(dims: Dict[str, Any], seq: int) -> float:
    n, per = _counts(dims), 2.0 * dims["heads"] * (dims["head_dim"] + dims["v_head_dim"])
    return per * (n["full"] * (seq + 1) / 2.0 + n["swa"] * min((seq + 1) / 2.0, float(dims["window"])))


def kv_bytes_per_token(dims: Dict[str, Any], kv_bytes: int = 2, kind: str = "full") -> int:
    """K and V of one position in all layers of one attention kind."""
    n = _counts(dims)
    hkv = dims["swa_kv_heads"] if kind == "swa" else dims["kv_heads"]
    return n[kind] * hkv * (dims["head_dim"] + dims["v_head_dim"]) * kv_bytes


def sparse_decode_step_bytes(dims: Dict[str, Any], live_positions: float, window_positions: float,
                             experts_hit_per_layer: float, weight_bytes: int = 2, kv_bytes: int = 2) -> float:
    """HBM bytes one decode token step has to read: attention, dense, router
    and head weights once; one expert's weights for each held expert that
    a token hit, in each expert layer; the full layers' K/V of the live
    positions; the window layers' K/V of ``window_positions``, the sum
    over live requests of ``min(positions, window)``."""
    return (always_read_params(dims) * weight_bytes
            + _counts(dims)["moe"] * experts_hit_per_layer * expert_params(dims) * weight_bytes
            + live_positions * kv_bytes_per_token(dims, kv_bytes, "full")
            + window_positions * kv_bytes_per_token(dims, kv_bytes, "swa"))


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
    return w["decode"]["experts_hit"] / (w["decode"]["token_steps"] * w["expert_layers"])
