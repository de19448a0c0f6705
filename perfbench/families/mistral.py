"""The ``mistral`` family (Llama's architecture with a sliding window):
rotary positions in the half-split convention (HF ``rotate_half``),
RMSNorm, grouped-query attention without biases, SwiGLU, an untied
output head unless the configuration ties it.

The program holds the family in its GPT tree, whose bias leaves this
family does not use: they are zeros (kind ``z``) and take no part in the
forward pass below. See ``families/gpt2.py`` for what a family file is.
"""
from __future__ import annotations

from typing import Any, Dict

import jax

from pb import reference as R


def dims(cfg: Dict[str, Any]) -> Dict[str, Any]:
    d, h = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    return {
        "vocab": int(cfg["vocab_size"]), "layers": int(cfg["num_hidden_layers"]), "d": d,
        "heads": h, "kv_heads": int(cfg.get("num_key_value_heads", h)),
        "head_dim": int(cfg.get("head_dim") or d // h), "ff": int(cfg["intermediate_size"]),
        "max_pos": int(cfg["max_position_embeddings"]), "norm_eps": float(cfg.get("rms_norm_eps", 1e-5)),
        "tied": bool(cfg.get("tie_word_embeddings", False)),
        "window": int(cfg.get("sliding_window") or 0), "rope_theta": float(cfg.get("rope_theta", 10000.0)),
    }


def param_shapes(dims: Dict[str, Any], max_seq: int) -> Dict[str, Any]:
    L, D, H, hd, F, V = (dims[k] for k in ("layers", "d", "heads", "head_dim", "ff", "vocab"))
    Hkv = dims["kv_heads"]
    blocks: Dict[str, Any] = {
        "ln1_g": ((L, D), "g"), "ln1_b": ((L, D), "z"),
        "wo": ((L, H, hd, D), "r"), "bo": ((L, D), "z"),
        "ln2_g": ((L, D), "g"), "ln2_b": ((L, D), "z"),
        "wi": ((L, D, 2, F), "w"), "bi": ((L, 2, F), "z"),
        "wo2": ((L, F, D), "r"), "bo2": ((L, D), "z"),
    }
    if Hkv == H:  # the program fuses q, k and v when the heads are as many
        blocks.update({"wqkv": ((L, D, 3, H, hd), "w"), "bqkv": ((L, 3, H, hd), "z")})
    else:
        blocks.update({
            "wq": ((L, D, H, hd), "w"), "bq": ((L, H, hd), "z"),
            "wkv": ((L, D, 2, Hkv, hd), "w"), "bkv": ((L, 2, Hkv, hd), "z"),
        })
    out: Dict[str, Any] = {"wte": ((V, D), "w"), "lnf_g": ((D,), "g"), "lnf_b": ((D,), "z"), "blocks": blocks}
    if not dims["tied"]:
        out["lm_head"] = ((V, D), "w")
    return out


SPLIT = {
    "blocks/wqkv": (2, ("q", "k", "v")), "blocks/wkv": (2, ("k", "v")), "blocks/wi": (2, ("gate", "up")),
}


def logits(params: Dict[str, Any], tokens: jax.Array, dims: Dict[str, Any], lowp: bool = False) -> jax.Array:
    eps, theta = dims["norm_eps"], dims["rope_theta"]
    x = params["wte"].astype(R.F32)[tokens]

    def layer(x, lp):
        lp = jax.tree_util.tree_map(lambda a: a.astype(R.F32), lp)
        a = R.rmsnorm(x, lp["ln1_g"], eps)
        if "wqkv" in lp:
            qkv = R.mm("bsd,dthk->bsthk", a, lp["wqkv"], lowp)
            q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        else:
            q = R.mm("bsd,dhk->bshk", a, lp["wq"], lowp)
            kv = R.mm("bsd,dthk->bsthk", a, lp["wkv"], lowp)
            k, v = kv[:, :, 0], kv[:, :, 1]
        o = R.attention(R.rope(q, theta), R.rope(k, theta), v, dims["window"], lowp)
        x = x + R.mm("bshk,hkd->bsd", o, lp["wo"], lowp)
        z = R.mm("bsd,dcf->bscf", R.rmsnorm(x, lp["ln2_g"], eps), lp["wi"], lowp)
        h = jax.nn.silu(z[:, :, 0]) * z[:, :, 1]
        return x + R.mm("bsf,fd->bsd", h, lp["wo2"], lowp), None

    x, _ = jax.lax.scan(jax.checkpoint(layer), x, params["blocks"])
    x = R.rmsnorm(x, params["lnf_g"].astype(R.F32), eps)
    head = params["wte"] if dims["tied"] else params["lm_head"]
    return R.mm("bsd,vd->bsv", x, head.astype(R.F32), lowp)


# -- what the algorithm needs, from shapes ---------------------------------
def matmul_params(dims: Dict[str, Any]) -> int:
    d, hd = dims["d"], dims["head_dim"]
    attn = 2 * d * dims["heads"] * hd + 2 * d * dims["kv_heads"] * hd
    return dims["layers"] * (attn + 3 * d * dims["ff"]) + dims["vocab"] * d


def total_params(dims: Dict[str, Any]) -> int:
    return matmul_params(dims) + (0 if dims["tied"] else dims["vocab"] * dims["d"])


def attn_flops_per_token_fwd(dims: Dict[str, Any], seq: int) -> float:
    """Causal, and no key further back than the window."""
    span = (seq + 1) / 2.0
    if dims["window"]:
        span = min(span, float(dims["window"]))
    return 4.0 * dims["layers"] * dims["heads"] * dims["head_dim"] * span


def kv_bytes_per_token(dims: Dict[str, Any], kv_bytes: int = 2) -> int:
    return 2 * dims["layers"] * dims["kv_heads"] * dims["head_dim"] * kv_bytes
