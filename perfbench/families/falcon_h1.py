"""The ``falcon_h1`` family (tiiuae/Falcon-H1-34B-Instruct): every block a
Mamba-2 mixer and a rotary grouped-query attention SIDE BY SIDE under one
norm, then a SwiGLU MLP under a second, with the family's muP scalars
where each part meets the residual stream. All layers are alike
(``attn_layer_indices`` null). The equations, with ``h`` the residual and
``eps`` = ``rms_norm_eps``; no biases but the conv's::

    h = E[token] * embedding_multiplier
    for each layer:
      u = RMSNorm(h; g_in)
      # attention, on a = u * attention_in_multiplier
      q = a Wq (H x hd);  k = (a Wk) * key_multiplier (Hkv x hd);  v = a Wv
      q, k rotated over all hd dims, half-split pairs (i, i + hd/2), base rope_theta
      A = (softmax_causal(q k^T / sqrt(hd)) v) Wo * attention_out_multiplier
      # Mamba-2, on m = u * ssm_in_multiplier;  s = ssm_multipliers
      [z | x | B | C | dt] = (m W_in) * [s0 | s1 | s2 | s3 | s4]
      [x | B | C] <- silu(conv_causal([x | B | C]) + b)
      dt = softplus(dt + dt_bias);  a_h = -exp(A_log_h)
      S_t = exp(dt a) S_{t-1} + dt x_t B_t^T;  y_t = S_t C_t + D x_t    head h reads group h // (Hs / G)
      y = RMSNorm_per_group(y * silu(z); g_m)          (mamba_norm_before_gate false: the gate first)
      M = (y W_out) * ssm_out_multiplier
      h = h + A + M
      f = RMSNorm(h; g_ff)
      h = h + ((silu((f W_gate) * mlp_multipliers[0]) * (f W_up)) W_down) * mlp_multipliers[1]
    logits = (RMSNorm(h; g_final) W_head) * lm_head_multiplier

``W_in`` is held as its parts (z; x, B and C together, the channels the
conv runs over; dt), as the program holds it: the same numbers, no slice
of a fused result. Where each scalar sits, the half-split rotation and
the norm after the gate are the family's published modelling code as the
builder knows it, not keys of ``config.json`` (listed under ``assumed`` in
the configuration); ``mamba_expand`` is carried and unused
(``mamba_d_ssm`` sets the inner width). The running state is float32.

``logits`` is the repo's plain reference for this family: ``jax.numpy`` in
float32 at the highest precision, no cache, no kernel, no chunked scan —
the recurrence is evaluated as written, one position at a time. It
imports nothing of the program and nothing of another family's file (the
conv and the scan below are this family's own copies). What it does for
memory alone, and changes no number: the layers — all alike — run under
one ``lax.scan`` over the stacked leaves, so that one layer's weights are
widened to float32 at a time; the MLP is summed over blocks of its
``intermediate_size`` columns and the head's product is taken in blocks of
vocabulary rows, so that no float32 operand the size of the 261,120 x
5,120 head (5.35 GB) exists beside 10.5 GB of weights.

See ``families/gpt2.py`` for what a family file is.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from pb import reference as R

#: The muP scalars by name, as ``dims["mult"]`` holds them.
MULTIPLIERS = (
    "embedding", "lm_head", "attn_in", "attn_out", "key", "ssm_in", "ssm_out",
    "ssm_z", "ssm_x", "ssm_b", "ssm_c", "ssm_dt", "mlp_gate", "mlp_out",
)
#: Rows of the vocabulary / columns of the MLP a block of the reference takes at most.
_HEAD_BLOCK = 8192
_FF_BLOCK = 8192


def dims(cfg: Dict[str, Any]) -> Dict[str, Any]:
    if cfg.get("attn_layer_indices") is not None:
        raise ValueError("falcon_h1 with attn_layer_indices: this family file knows every layer alike")
    if not cfg.get("mamba_rms_norm", True) or cfg.get("mamba_norm_before_gate", False):
        raise ValueError("falcon_h1: this family file knows the gated norm after the gate (mamba_rms_norm, not before)")
    heads, hd = int(cfg["mamba_n_heads"]), int(cfg["mamba_d_head"])
    if heads * hd != int(cfg["mamba_d_ssm"]):
        raise ValueError(f"mamba_d_ssm {cfg['mamba_d_ssm']} is not mamba_n_heads x mamba_d_head ({heads} x {hd})")
    scalars = [cfg["embedding_multiplier"], cfg["lm_head_multiplier"], cfg["attention_in_multiplier"],
               cfg["attention_out_multiplier"], cfg["key_multiplier"], cfg["ssm_in_multiplier"],
               cfg["ssm_out_multiplier"], *cfg["ssm_multipliers"], *cfg["mlp_multipliers"]]
    if len(scalars) != len(MULTIPLIERS):
        raise ValueError(f"falcon_h1 has five ssm_multipliers and two mlp_multipliers ({len(scalars)} scalars read)")
    return {
        "vocab": int(cfg["vocab_size"]), "layers": int(cfg["num_hidden_layers"]), "d": int(cfg["hidden_size"]),
        "heads": int(cfg["num_attention_heads"]), "kv_heads": int(cfg["num_key_value_heads"]),
        "head_dim": int(cfg["head_dim"]), "rope_theta": float(cfg["rope_theta"]),
        "ssm_heads": heads, "ssm_head_dim": hd, "ssm_groups": int(cfg["mamba_n_groups"]),
        "ssm_state": int(cfg["mamba_d_state"]), "conv": int(cfg["mamba_d_conv"]), "chunk": int(cfg["mamba_chunk_size"]),
        "ff": int(cfg["intermediate_size"]), "norm_eps": float(cfg["rms_norm_eps"]),
        "mult": {name: float(v) for name, v in zip(MULTIPLIERS, scalars)},
    }


def _inner(dims: Dict[str, Any]) -> int:
    return dims["ssm_heads"] * dims["ssm_head_dim"]


def _conv_dim(dims: Dict[str, Any]) -> int:
    return _inner(dims) + 2 * dims["ssm_groups"] * dims["ssm_state"]


def param_shapes(dims: Dict[str, Any], max_seq: int) -> Dict[str, Any]:
    """The program's tree for mixed layers (``models/mixed.py``) with every
    layer a parallel one: a layer has an index among the full layers'
    leaves AND among the state layers', one ``ln1_g`` and one ``ln2_g``;
    gate and up of the SwiGLU side by side, ``(2, D, F)``.

    Kinds (``pb/weights.py``: w std 0.02, r the residual std, g near one,
    b small), chosen so that at the PUBLISHED scalars every part moves the
    logits: GPT-2's initialisation would not do — ``key_multiplier`` 0.011
    makes every score about 0.02 and the softmax uniform, so a wrong
    rotation or a dropped scalar would pass any tolerance.

    - ``full_wk`` is ``g``: a key is then ``key_multiplier x (sum of the
      normed input's 5,120 values) x (1 + small)`` in every dim — about
      0.011 x 72 = 0.8 — and a score ``0.8 x sum_i q_i / sqrt(128)`` has a
      standard deviation near 1 (``q`` of kind ``w``: 1.43 a dim). The
      keys of a position are alike across dims and KV heads, the rotation
      still turns them by position and the values (``w``) differ by head.
      ``full_wo`` is ``w``: with scores that peaked the heads' output is
      0.1-0.4 a dim and ``A`` about 0.004-0.015 in the residual.
    - The conv's taps lie near one (``g``), its bias small (``b``), ``A_log``
      and ``dt_bias`` near zero (``w``: ``dt`` about 0.7, a decay of one half
      a step) as in the other state family. ``ssm_D`` is ``b``, not ``g``: the
      scalars make x, B and C 0.09, 0.06 and 0.18, so the scan's part of
      ``y`` — ``dt x (B . C)``, a seventh of ``x`` — would be an eighth of
      ``D x`` at ``D`` near one and a wrong carry would pass; at ``D`` near
      0.02 the scan is seven eighths of ``y``. ``ssm_wo`` is ``r``: ``M``
      about 0.008.
    - ``dense_wo2`` is ``w`` (``mlp_multipliers[1]`` 0.011 makes the MLP's
      write the smallest of the three: about 0.006).

    ``part_sizes`` reads the sizes; the limits file has them as read on
    the chip."""
    D, H, V, hd, hkv = dims["d"], dims["heads"], dims["vocab"], dims["head_dim"], dims["kv_heads"]
    n, di, C, Hs, F = dims["layers"], _inner(dims), _conv_dim(dims), dims["ssm_heads"], dims["ff"]
    blocks: Dict[str, Any] = {
        "ln1_g": ((n, D), "g"), "ln2_g": ((n, D), "g"),
        "full_wq": ((n, D, H, hd), "w"), "full_wk": ((n, D, hkv, hd), "g"),
        "full_wv": ((n, D, hkv, hd), "w"), "full_wo": ((n, H, hd, D), "w"),
        "ssm_wz": ((n, D, di), "w"), "ssm_wx": ((n, D, C), "w"), "ssm_wdt": ((n, D, Hs), "w"),
        "ssm_conv_w": ((n, dims["conv"], C), "g"), "ssm_conv_b": ((n, C), "b"),
        "ssm_dt_bias": ((n, Hs), "w"), "ssm_A_log": ((n, Hs), "w"), "ssm_D": ((n, Hs), "b"),
        "ssm_norm_g": ((n, di), "g"), "ssm_wo": ((n, di, D), "r"),
        "dense_wi": ((n, 2, D, F), "w"), "dense_wo2": ((n, F, D), "w"),
    }
    return {"wte": ((V, D), "w"), "lm_head": ((V, D), "w"), "lnf_g": ((D,), "g"), "blocks": blocks}


SPLIT = {"blocks/dense_wi": (1, ("gate", "up"))}


# -- the reference's pieces --------------------------------------------------------
def rope(x: jax.Array, theta: float) -> jax.Array:
    """Rotate all dims of (B, S, H, hd) by position, half-split pairs."""
    return R.rope(x, theta)


def conv(xbc, w, b):
    """Causal depthwise conv over (B, S, C), zeros before row 0; ``w`` (K,
    C) taps-major, the last tap on the row itself."""
    K, S = w.shape[0], xbc.shape[1]
    front = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    return b + sum(w[j] * front[:, j:j + S] for j in range(K))


def head_groups(bc, dims: Dict[str, Any]):
    """B or C (B, S, G, N) -> (B, S, Hs, N): head h reads group h // (Hs / G)."""
    return jnp.repeat(bc, dims["ssm_heads"] // dims["ssm_groups"], axis=2)


def scan(x, dt, A, bh, ch, lowp: bool):
    """The recurrence as written, one position at a time from a zero
    state: x (B, S, Hs, P), dt (B, S, Hs), A (Hs,), bh and ch (B, S, Hs,
    N) -> ``S_t C_t`` (B, S, Hs, P)."""
    def step(state, args):
        x_t, dt_t, b_t, c_t = args
        state = jnp.exp(dt_t * A)[..., None, None] * state + R.mm(
            "bhp,bhn->bhpn", dt_t[..., None] * x_t, b_t, lowp)
        return state, R.mm("bhpn,bhn->bhp", state, c_t, lowp)

    B, _, H, P = x.shape
    _, y = jax.lax.scan(
        step, jnp.zeros((B, H, P, bh.shape[-1]), R.F32),
        tuple(jnp.moveaxis(a, 1, 0) for a in (x, dt, bh, ch)),
    )
    return jnp.moveaxis(y, 0, 1)


def gate_norm(y, z, g, dims: Dict[str, Any]):
    """The gate, then RMSNorm over each group's channels on its own."""
    B, S, _ = y.shape
    y = (y * jax.nn.silu(z)).reshape(B, S, dims["ssm_groups"], -1)
    y = y / jnp.sqrt((y * y).mean(-1, keepdims=True) + dims["norm_eps"])
    return y.reshape(B, S, -1) * g


def mamba(u, leaf, dims: Dict[str, Any], lowp: bool):
    """``M`` of the equations, on the layer's normed input ``u``."""
    B, S, _ = u.shape
    Hs, P, G, N, di = dims["ssm_heads"], dims["ssm_head_dim"], dims["ssm_groups"], dims["ssm_state"], _inner(dims)
    s = dims["mult"]
    m = u * s["ssm_in"]
    z = R.mm("bsd,de->bse", m, leaf("ssm_wz"), lowp) * s["ssm_z"]
    xbc = R.mm("bsd,de->bse", m, leaf("ssm_wx"), lowp)
    xbc = jnp.concatenate([
        xbc[..., :di] * s["ssm_x"], xbc[..., di:di + G * N] * s["ssm_b"], xbc[..., di + G * N:] * s["ssm_c"]], -1)
    dt = R.mm("bsd,dh->bsh", m, leaf("ssm_wdt"), lowp) * s["ssm_dt"]
    xbc = jax.nn.silu(conv(xbc, leaf("ssm_conv_w"), leaf("ssm_conv_b")))
    dt = jax.nn.softplus(dt + leaf("ssm_dt_bias"))
    x = xbc[..., :di].reshape(B, S, Hs, P)
    bh = head_groups(xbc[..., di:di + G * N].reshape(B, S, G, N), dims)
    ch = head_groups(xbc[..., di + G * N:].reshape(B, S, G, N), dims)
    y = scan(x, dt, -jnp.exp(leaf("ssm_A_log")), bh, ch, lowp) + leaf("ssm_D")[:, None] * x
    y = gate_norm(y.reshape(B, S, di), z, leaf("ssm_norm_g"), dims)
    return R.mm("bse,ed->bsd", y, leaf("ssm_wo"), lowp) * s["ssm_out"]


def attention(u, leaf, dims: Dict[str, Any], lowp: bool):
    """``A`` of the equations, on the layer's normed input ``u``."""
    s = dims["mult"]
    a = u * s["attn_in"]
    q = R.mm("bsd,dhk->bshk", a, leaf("full_wq"), lowp)
    k = R.mm("bsd,dhk->bshk", a, leaf("full_wk"), lowp) * s["key"]
    v = R.mm("bsd,dhk->bshk", a, leaf("full_wv"), lowp)
    q, k = rope(q, dims["rope_theta"]), rope(k, dims["rope_theta"])
    return R.mm("bshk,hkd->bsd", R.attention(q, k, v, 0, lowp), leaf("full_wo"), lowp) * s["attn_out"]


def _blocks_of(n: int, most: int) -> int:
    """The fewest equal blocks of ``n`` that hold at most ``most`` each."""
    return next(k for k in range(-(-n // most), n + 1) if n % k == 0)


def mlp(f, raw, dims: Dict[str, Any], lowp: bool):
    """The SwiGLU of the equations, summed over blocks of its columns (each
    column's term is its own: the blocks change no number)."""
    s, F = dims["mult"], dims["ff"]
    nb = _blocks_of(F, _FF_BLOCK)
    wi = raw("dense_wi").reshape(2, -1, nb, F // nb)  # (2, D, nb, F / nb)
    wo = raw("dense_wo2").reshape(nb, F // nb, -1)

    def one(acc, args):
        wi_b, wo_b = args  # (2, D, F / nb), (F / nb, D)
        gate = R.mm("bsd,df->bsf", f, wi_b[0].astype(R.F32), lowp) * s["mlp_gate"]
        up = R.mm("bsd,df->bsf", f, wi_b[1].astype(R.F32), lowp)
        return acc + R.mm("bsf,fd->bsd", jax.nn.silu(gate) * up, wo_b.astype(R.F32), lowp), None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(f), (jnp.moveaxis(wi, 2, 0), wo))
    return acc * s["mlp_out"]


def head(x, w, dims: Dict[str, Any], lowp: bool):
    """``(x W_head^T) * lm_head_multiplier`` in blocks of vocabulary rows,
    each widened on its own and written into the one result where it
    belongs (no second array of all the logits)."""
    V = w.shape[0]
    nb = _blocks_of(V, _HEAD_BLOCK)
    rows = V // nb

    def one(out, args):
        i, w_b = args
        block = R.mm("bsd,vd->bsv", x, w_b.astype(R.F32), lowp) * dims["mult"]["lm_head"]
        return jax.lax.dynamic_update_slice_in_dim(out, block, i * rows, axis=2), None

    out, _ = jax.lax.scan(one, jnp.zeros(x.shape[:2] + (V,), R.F32), (jnp.arange(nb), w.reshape(nb, rows, -1)))
    return out


def layer(h, lp: Dict[str, Any], dims: Dict[str, Any], lowp: bool):
    """One block over the residual ``h``: ``(h, (A, M, F))``, the layer's
    three writes as they entered the residual."""
    def leaf(name):
        return lp[name].astype(R.F32)

    u = R.rmsnorm(h, leaf("ln1_g"), dims["norm_eps"])
    a, m = attention(u, leaf, dims, lowp), mamba(u, leaf, dims, lowp)
    h = h + a + m
    f = mlp(R.rmsnorm(h, leaf("ln2_g"), dims["norm_eps"]), lp.__getitem__, dims, lowp)
    return h + f, (a, m, f)


def embed(params: Dict[str, Any], tokens: jax.Array, dims: Dict[str, Any]) -> jax.Array:
    return params["wte"][tokens].astype(R.F32) * dims["mult"]["embedding"]


def logits(params: Dict[str, Any], tokens: jax.Array, dims: Dict[str, Any], lowp: bool = False) -> jax.Array:
    h, _ = jax.lax.scan(lambda h, lp: (layer(h, lp, dims, lowp)[0], None), embed(params, tokens, dims), params["blocks"])
    x = R.rmsnorm(h, params["lnf_g"].astype(R.F32), dims["norm_eps"])
    return head(x, params["lm_head"], dims, lowp)


def part_sizes(params: Dict[str, Any], tokens: jax.Array, dims: Dict[str, Any]) -> Dict[str, float]:
    """What the seeded leaves' kinds are judged by (``param_shapes``), over
    tokens (B, S) in the first layer: the standard deviation of the scores
    before the softmax (over the allowed pairs), and the root mean square
    of the embedding's rows and of ``A``, ``M`` and the MLP's write as they
    enter the residual."""
    lp = {k: v[0] for k, v in params["blocks"].items()}
    h = embed(params, tokens, dims)
    u = R.rmsnorm(h, lp["ln1_g"].astype(R.F32), dims["norm_eps"]) * dims["mult"]["attn_in"]
    q = rope(R.mm("bsd,dhk->bshk", u, lp["full_wq"].astype(R.F32), False), dims["rope_theta"])
    k = rope(R.mm("bsd,dhk->bshk", u, lp["full_wk"].astype(R.F32), False) * dims["mult"]["key"], dims["rope_theta"])
    k = jnp.repeat(k, dims["heads"] // dims["kv_heads"], axis=2)
    s = R.mm("bqhd,bkhd->bhqk", q, k, False) / jnp.sqrt(float(dims["head_dim"]))
    S = tokens.shape[1]
    ok = jnp.tril(jnp.ones((S, S), bool), -1)  # a query's own key aside
    _, (a, m, f) = layer(h, lp, dims, False)
    rms = lambda x: float(jnp.sqrt(jnp.mean(x * x)))  # noqa: E731
    return {"score_std": float(jnp.std(s[:, :, ok])), "embedding": rms(h), "A": rms(a), "M": rms(m), "mlp": rms(f)}


# -- what the algorithm needs, from shapes -------------------------------------------
def attn_params(dims: Dict[str, Any]) -> int:
    d, H, hkv, hd = dims["d"], dims["heads"], dims["kv_heads"], dims["head_dim"]
    return 2 * d * H * hd + 2 * d * hkv * hd


def state_layer_params(dims: Dict[str, Any]) -> int:
    """One state mixer's in- and out-projections (the conv's taps, the norm
    and the per-head vectors are a two-thousandth of them and not counted)."""
    d, di = dims["d"], _inner(dims)
    return d * (di + _conv_dim(dims) + dims["ssm_heads"]) + di * d


def mlp_params(dims: Dict[str, Any]) -> int:
    return 3 * dims["d"] * dims["ff"]


def matmul_params(dims: Dict[str, Any]) -> int:
    """Every matmul parameter on a token's path: the layers and the head."""
    return dims["layers"] * (attn_params(dims) + state_layer_params(dims) + mlp_params(dims)) + dims["vocab"] * dims["d"]


def total_params(dims: Dict[str, Any]) -> int:
    return matmul_params(dims) + dims["vocab"] * dims["d"]


def attn_flops_per_token_fwd(dims: Dict[str, Any], seq: int) -> float:
    return dims["layers"] * 4.0 * dims["heads"] * dims["head_dim"] * (seq + 1) / 2.0


def kv_bytes_per_token(dims: Dict[str, Any], kv_bytes: int = 2) -> int:
    """K and V of one position, over the layers (each keeps both)."""
    return dims["layers"] * 2 * dims["kv_heads"] * dims["head_dim"] * kv_bytes


def state_bytes_per_slot(dims: Dict[str, Any], state_bytes: int = 4, tail_bytes: int = 2) -> int:
    """What the state mixers keep for one request, whatever its length: the
    recurrent state (Hs x P x N) and the conv's last K - 1 rows, a layer."""
    return dims["layers"] * (
        _inner(dims) * dims["ssm_state"] * state_bytes + (dims["conv"] - 1) * _conv_dim(dims) * tail_bytes)


def state_weight_bytes(dims: Dict[str, Any], weight_bytes: int = 2) -> int:
    """The state mixers' own weights, which a decode step reads once."""
    return dims["layers"] * state_layer_params(dims) * weight_bytes


def hybrid_decode_step_bytes(dims: Dict[str, Any], live_slots: float, live_positions: float,
                             experts_hit_per_layer: float = 0.0, weight_bytes: int = 2, kv_bytes: int = 2) -> float:
    """HBM bytes one decode token step has to move: every layer's weights
    and the head once; the state and conv tail of the LIVE requests read
    and written; the K/V of the live positions. There are no experts
    (``experts_hit_per_layer`` is taken and unused, so that the readers of
    the other state family serve this one). The state of an idle slot,
    which the program's step moves too, is not work the algorithm needs."""
    return (matmul_params(dims) * weight_bytes
            + 2.0 * live_slots * state_bytes_per_slot(dims)
            + live_positions * kv_bytes_per_token(dims, kv_bytes))


# -- what the program counted, over the window ---------------------------------------
def ssm_window(program: Dict[str, Any]) -> Any:
    """``{"decode": {slot_steps, slot_steps_live}, "prefill": {rows_scanned,
    rows_real}}`` of ``stats()["ssm"]`` over the window (monotone totals:
    the difference of the two calls that bracket it); None from a program
    that has no such counters."""
    s0 = (program.get("stats0") or {}).get("ssm")
    s1 = (program.get("stats1") or {}).get("ssm")
    if not s0 or not s1:
        return None
    return {ph: {k: s1[ph][k] - s0[ph].get(k, 0) for k in s1[ph]} for ph in ("decode", "prefill")}


def experts_hit_per_step(program: Dict[str, Any]) -> float:
    """No experts: nothing is hit."""
    return 0.0
