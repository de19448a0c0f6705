"""The ``gpt2`` family (``model_type`` of the published config): learned
positions, LayerNorm, one fused qkv projection with biases, GELU in the
tanh form (GPT-2's ``gelu_new``), the output head tied to the embedding.

A family file is everything the benchmark knows about an architecture:
the sizes it reads from a configuration, the leaves of its weight tree,
its plain forward pass (float32 ``highest``; the shared pieces are in
``pb/reference.py``), and the operations and bytes its algorithm needs.
The harness finds it by the configuration's ``model_type``; a new
architecture is a new file here and edits none.
"""
from __future__ import annotations

from typing import Any, Dict

import jax

from pb import reference as R


def dims(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the benchmark's arithmetic needs, from the published keys."""
    d, h = int(cfg["n_embd"]), int(cfg["n_head"])
    return {
        "vocab": int(cfg["vocab_size"]), "layers": int(cfg["n_layer"]), "d": d,
        "heads": h, "kv_heads": h, "head_dim": d // h, "ff": int(cfg.get("n_inner") or 4 * d),
        "max_pos": int(cfg["n_positions"]), "norm_eps": float(cfg.get("layer_norm_epsilon", 1e-5)),
        "tied": True, "window": 0, "rope_theta": 0.0,
    }


def param_shapes(dims: Dict[str, Any], max_seq: int) -> Dict[str, Any]:
    """``name -> (shape, kind)``; kinds as in ``pb/weights.py``."""
    L, D, H, hd, F, V = (dims[k] for k in ("layers", "d", "heads", "head_dim", "ff", "vocab"))
    return {
        "wte": ((V, D), "w"), "wpe": ((max_seq, D), "w"),
        "lnf_g": ((D,), "g"), "lnf_b": ((D,), "b"),
        "blocks": {
            "ln1_g": ((L, D), "g"), "ln1_b": ((L, D), "b"),
            "wqkv": ((L, D, 3, H, hd), "w"), "bqkv": ((L, 3, H, hd), "b"),
            "wo": ((L, H, hd, D), "r"), "bo": ((L, D), "b"),
            "ln2_g": ((L, D), "g"), "ln2_b": ((L, D), "b"),
            "wi": ((L, D, F), "w"), "bi": ((L, F), "b"),
            "wo2": ((L, F, D), "r"), "bo2": ((L, D), "b"),
        },
    }


#: Fused leaves, as the parts the parameter-change comparison looks at
#: one by one: ``leaf -> (axis, names)``. The key bias has a gradient of
#: exactly zero (a softmax does not see a shift of all its scores), so
#: in any precision what Adam makes of it is noise; only apart from the
#: query and value biases can it be left out.
SPLIT = {"blocks/bqkv": (1, ("q", "k", "v")), "blocks/wqkv": (2, ("q", "k", "v"))}


def logits(params: Dict[str, Any], tokens: jax.Array, dims: Dict[str, Any], lowp: bool = False) -> jax.Array:
    """tokens (B, S) -> logits (B, S, V), float32."""
    eps = dims["norm_eps"]
    x = params["wte"].astype(R.F32)[tokens] + params["wpe"].astype(R.F32)[: tokens.shape[1]]

    def layer(x, lp):
        lp = jax.tree_util.tree_map(lambda a: a.astype(R.F32), lp)
        a = R.layernorm(x, lp["ln1_g"], lp["ln1_b"], eps)
        qkv = R.mm("bsd,dthk->bsthk", a, lp["wqkv"], lowp) + lp["bqkv"]
        o = R.attention(qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2], 0, lowp)
        x = x + R.mm("bshk,hkd->bsd", o, lp["wo"], lowp) + lp["bo"]
        m = R.layernorm(x, lp["ln2_g"], lp["ln2_b"], eps)
        h = jax.nn.gelu(R.mm("bsd,df->bsf", m, lp["wi"], lowp) + lp["bi"], approximate=True)
        return x + R.mm("bsf,fd->bsd", h, lp["wo2"], lowp) + lp["bo2"], None

    x, _ = jax.lax.scan(jax.checkpoint(layer), x, params["blocks"])
    x = R.layernorm(x, params["lnf_g"].astype(R.F32), params["lnf_b"].astype(R.F32), eps)
    return R.mm("bsd,vd->bsv", x, params["wte"].astype(R.F32), lowp)


# -- what the algorithm needs, from shapes ---------------------------------
def matmul_params(dims: Dict[str, Any]) -> int:
    """Parameters that sit in a matrix multiplication on a token's path:
    every block's projections and MLP, and the output head. Embedding
    lookups and norm gains do no multiply-accumulate per parameter."""
    d = dims["d"]
    return dims["layers"] * (4 * d * dims["heads"] * dims["head_dim"] + 2 * d * dims["ff"]) + dims["vocab"] * d


def total_params(dims: Dict[str, Any]) -> int:
    """All parameters held (the few gains and biases left out: under 0.1%)."""
    return matmul_params(dims) + dims["max_pos"] * dims["d"]


def attn_flops_per_token_fwd(dims: Dict[str, Any], seq: int) -> float:
    """Forward attention FLOPs per token at length ``seq``, causal: a
    query at position i meets i+1 keys, (seq+1)/2 on average, in two
    matmuls (scores, values) of 2 FLOPs per multiply-add."""
    return 4.0 * dims["layers"] * dims["heads"] * dims["head_dim"] * (seq + 1) / 2.0


def kv_bytes_per_token(dims: Dict[str, Any], kv_bytes: int = 2) -> int:
    return 2 * dims["layers"] * dims["kv_heads"] * dims["head_dim"] * kv_bytes
