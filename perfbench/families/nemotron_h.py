"""The ``nemotron_h`` family (nvidia/NVIDIA-Nemotron-3-Super-120B-A12B): a
stack of blocks that are ONE part each — a Mamba-2 state layer (``M``), a
layer of routed experts in a latent beside a shared expert (``E``), or
grouped-query attention without positions (``*``) —, as one chip's share
of a deployment that divides the experts and the vocabulary. The
equations, with ``x`` the residual, layer ``l`` of kind
``hybrid_override_pattern[l]`` and ``u = RMSNorm(x; g_l)`` (eps
``layer_norm_epsilon``); every layer is ``x <- x + Mixer_l(u)``; no biases
but the conv's:

- ``M`` (H = ``mamba_num_heads``, P = ``mamba_head_dim``, G = ``n_groups``,
  N = ``ssm_state_size``, K = ``conv_kernel``): ``z = u W_z`` (H P), ``xBC
  = u W_x`` (H P + 2 G N), ``dt = u W_dt`` (H) — the published
  in-projection, held as its three parts. Causal depthwise conv, zeros
  before the sequence: ``xBC_t <- silu(b + sum_j w[j] * xBC_{t-K+1+j})``
  (``w`` held taps-major: ``w[j]`` is the tap on the row ``K-1-j`` back).
  ``xBC_t -> x_t`` (H x P), ``B_t``, ``C_t`` (G x N); head h reads group
  ``h // (H / G)``. ``dt_t = softplus(dt_t + dt_bias)``, ``A = -exp(A_log)``
  per head (``time_step_*`` shape the published initialisation only).
  Per head, from ``S = 0``: ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``,
  ``y_t = S_t C_t + D x_t``. Then the gate, then the norm: ``y_t <- y_t *
  silu(z_t)``, RMSNorm over each of the G groups of H P / G channels on
  its own with gain ``g_n``; out ``y_t W_o``. Here the recurrence is
  evaluated as written, one position at a time (``chunk_size`` is the
  block of the chunked evaluation the program uses: it changes no result).
- ``*``: ``q = u W_q`` (``num_attention_heads`` x ``head_dim``), ``k``, ``v``
  (``num_key_value_heads``); NO rotary and no other position term; ``s_ij
  = q_i . k_j / sqrt(head_dim)``, causal softmax; out ``concat(o) W_o``.
- ``E``: ``sigma = sigmoid(u W_r)`` over ALL published experts; T = the
  ``num_experts_per_tok`` largest of ``sigma + c`` (``c`` for the choice
  only; one group); ``w_e = routed_scaling_factor * sigma_e / sum_{e' in
  T} sigma_e'``. ``a = u W_down`` (``moe_latent_size``); ``FFN_e(a) =
  relu(a W_up,e)^2 W_dn,e``; ``r = sum_{e in T and held} w_e FFN_e(a)``:
  the sum over the experts THIS share holds, the choice, the
  normalisation and the scale over all. Shared expert on the WHOLE
  input: ``s = relu(u V_up)^2 V_dn``. Out ``r W_up_latent + s``. What the
  absent experts would add is left out, and the partial result goes on.
- Final RMSNorm, logits over the held rows of the vocabulary.

Departures (also under ``assumed`` in the configuration): the running
state is float32; ``rope_theta`` / ``partial_rotary_factor`` are carried
and unused (``model_type`` ``nemotron_h`` applies no positions in
attention); the multi-token-prediction module (``num_nextn_predict_layers``,
``mtp_hybrid_override_pattern``) is a draft head, not part of the pass
that yields the next token: left out.

``logits`` is the repo's plain reference for this family: ``jax.numpy``
in float32 at the highest precision, no cache, no kernel, no chunking. It
imports nothing of the program. Its pieces are module-level functions so
that a test can put a deliberately wrong one in their place.

See ``families/gpt2.py`` for what a family file is.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from pb import reference as R


def dims(cfg: Dict[str, Any]) -> Dict[str, Any]:
    pub = cfg.get("published", {})
    held = cfg.get("experts_held") or [0, int(cfg["n_routed_experts"])]
    return {
        "vocab": int(cfg["vocab_size"]), "layers": int(cfg["num_hidden_layers"]),
        "pattern": str(cfg["hybrid_override_pattern"]),
        "d": int(cfg["hidden_size"]), "heads": int(cfg["num_attention_heads"]),
        "head_dim": int(cfg["head_dim"]), "kv_heads": int(cfg["num_key_value_heads"]),
        "ssm_heads": int(cfg["mamba_num_heads"]), "ssm_head_dim": int(cfg["mamba_head_dim"]),
        "ssm_groups": int(cfg["n_groups"]), "ssm_state": int(cfg["ssm_state_size"]),
        "conv": int(cfg["conv_kernel"]), "chunk": int(cfg["chunk_size"]),
        # the router keeps its published width; this share holds some of them
        "experts": int(pub.get("n_routed_experts", cfg["n_routed_experts"])),
        "experts_held": [int(held[0]), int(held[1])],
        "top_k": int(cfg["num_experts_per_tok"]), "scale": float(cfg["routed_scaling_factor"]),
        "latent": int(cfg["moe_latent_size"]), "expert_ff": int(cfg["moe_intermediate_size"]),
        "shared_ff": int(cfg["moe_shared_expert_intermediate_size"]) * int(cfg["n_shared_experts"]),
        "norm_eps": float(cfg["layer_norm_epsilon"]),
    }


def _counts(dims: Dict[str, Any]) -> Dict[str, int]:
    p = dims["pattern"]
    if len(p) != dims["layers"] or set(p) - set("ME*"):
        raise ValueError(f"hybrid_override_pattern {p!r} does not name {dims['layers']} layers of M, E, *")
    return {"ssm": p.count("M"), "moe": p.count("E"), "full": p.count("*")}


def _inner(dims: Dict[str, Any]) -> int:
    return dims["ssm_heads"] * dims["ssm_head_dim"]


def _conv_dim(dims: Dict[str, Any]) -> int:
    return _inner(dims) + 2 * dims["ssm_groups"] * dims["ssm_state"]


def param_shapes(dims: Dict[str, Any], max_seq: int) -> Dict[str, Any]:
    """The program's tree for mixed layers (``models/mixed.py``): leaves of
    one kind of layer stacked over the layers of that kind, ``ln1_g`` over
    the layers that have a mixer (M, *), ``ln2_g`` over the E layers; an
    MLP's input side is ``(1, D, F)`` (relu2: no gate). Kinds: every term
    moves the logits, at the published widths and at a test's toy widths
    alike. The conv's taps lie near one (``g``) and its bias is small
    (``b``): its output is about twice a row of the in-projection, so x, B
    and C are of order one and the scan's part of ``y`` — which grows with
    ``x B C`` where ``D x`` grows with ``x`` — is the larger part; with small
    taps it would be a hundredth of ``D x`` and a wrong carry would pass any
    tolerance. ``D`` lies near one (``g``): ``D x`` is some 3% of ``y`` at the
    published widths, a half at toy widths. ``A_log`` and ``dt_bias`` lie
    near zero (``w``): ``dt`` about 0.7, a decay of about one half a step,
    some ten tokens of memory. An expert's down-projection is a residual
    write (``r``) and the latent's up-projection ``w``: with the routed
    weights summing to the scale of 5 the routed experts' part of an E
    layer is then about a third of the layer's output beside the shared
    expert's (``r``). With both ``w`` it was twice the shared expert's,
    and one expert exchanged for its neighbour at the edge of the top 22
    — which bfloat16 rounding of the router's input does to about one
    token in three a layer — moved a logit by more than the float8
    control moves it: the served tokens read 1.1-1.9 under the
    reference's best against the control's 2.0-2.5, where they now read
    0.17-0.25 against 0.83-0.96 (my chip runs, PR 32)."""
    D, H, V, hd = dims["d"], dims["heads"], dims["vocab"], dims["head_dim"]
    n, di, C, Hs = _counts(dims), _inner(dims), _conv_dim(dims), dims["ssm_heads"]
    blocks: Dict[str, Any] = {"ln1_g": ((n["ssm"] + n["full"], D), "g"), "ln2_g": ((n["moe"], D), "g")}
    if n["full"]:
        blocks.update({
            "full_wq": ((n["full"], D, H, hd), "w"), "full_wk": ((n["full"], D, dims["kv_heads"], hd), "w"),
            "full_wv": ((n["full"], D, dims["kv_heads"], hd), "w"), "full_wo": ((n["full"], H, hd, D), "r"),
        })
    if n["ssm"]:
        m = n["ssm"]
        blocks.update({
            "ssm_wz": ((m, D, di), "w"), "ssm_wx": ((m, D, C), "w"), "ssm_wdt": ((m, D, Hs), "w"),
            "ssm_conv_w": ((m, dims["conv"], C), "g"), "ssm_conv_b": ((m, C), "b"),
            "ssm_dt_bias": ((m, Hs), "w"), "ssm_A_log": ((m, Hs), "w"), "ssm_D": ((m, Hs), "g"),
            "ssm_norm_g": ((m, di), "g"), "ssm_wo": ((m, di, D), "r"),
        })
    if n["moe"]:
        e, held, F, Dl = n["moe"], dims["experts_held"][1], dims["expert_ff"], dims["latent"]
        blocks.update({
            "moe_router": ((e, D, dims["experts"]), "w"), "moe_router_bias": ((e, dims["experts"]), "w"),
            "moe_latent_down": ((e, D, Dl), "w"), "moe_latent_up": ((e, Dl, D), "w"),
            "moe_wi": ((e, held, 1, Dl, F), "w"), "moe_wo2": ((e, held, F, Dl), "r"),
            "moe_shared_wi": ((e, 1, D, dims["shared_ff"]), "w"), "moe_shared_wo2": ((e, dims["shared_ff"], D), "r"),
        })
    return {"wte": ((V, D), "w"), "lm_head": ((V, D), "w"), "lnf_g": ((D,), "g"), "blocks": blocks}


# -- the reference's pieces --------------------------------------------------------
def conv(xbc, w, b):
    """Causal depthwise conv over (B, S, C), zeros before row 0; ``w`` (K,
    C) taps-major, the last tap on the row itself."""
    K, S = w.shape[0], xbc.shape[1]
    front = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    return b + sum(w[j] * front[:, j:j + S] for j in range(K))


def head_groups(bc, dims: Dict[str, Any]):
    """B or C (B, S, G, N) -> (B, S, H, N): head h reads group h // (H / G)."""
    return jnp.repeat(bc, dims["ssm_heads"] // dims["ssm_groups"], axis=2)


def scan(x, dt, A, bh, ch, dims: Dict[str, Any], lowp: bool):
    """The recurrence as written, one position at a time from a zero
    state: x (B, S, H, P), dt (B, S, H), A (H,), bh and ch (B, S, H, N) ->
    ``S_t C_t`` (B, S, H, P)."""
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
    B, S, _ = u.shape
    H, P, G, N, di = dims["ssm_heads"], dims["ssm_head_dim"], dims["ssm_groups"], dims["ssm_state"], _inner(dims)
    z = R.mm("bsd,de->bse", u, leaf("wz"), lowp)
    xbc = jax.nn.silu(conv(R.mm("bsd,de->bse", u, leaf("wx"), lowp), leaf("conv_w"), leaf("conv_b")))
    dt = jax.nn.softplus(R.mm("bsd,dh->bsh", u, leaf("wdt"), lowp) + leaf("dt_bias"))
    x = xbc[..., :di].reshape(B, S, H, P)
    bh = head_groups(xbc[..., di:di + G * N].reshape(B, S, G, N), dims)
    ch = head_groups(xbc[..., di + G * N:].reshape(B, S, G, N), dims)
    y = scan(x, dt, -jnp.exp(leaf("A_log")), bh, ch, dims, lowp) + leaf("D")[:, None] * x
    return R.mm("bse,ed->bsd", gate_norm(y.reshape(B, S, di), z, leaf("norm_g"), dims), leaf("wo"), lowp)


def attention(u, leaf, dims: Dict[str, Any], lowp: bool):
    """Grouped-query causal attention, no position term."""
    q = R.mm("bsd,dhk->bshk", u, leaf("wq"), lowp)
    k = R.mm("bsd,dhk->bshk", u, leaf("wk"), lowp)
    v = R.mm("bsd,dhk->bshk", u, leaf("wv"), lowp)
    return R.mm("bshk,hkd->bsd", R.attention(q, k, v, 0, lowp), leaf("wo"), lowp)


def route(t, wr, c, dims: Dict[str, Any], lowp: bool) -> jax.Array:
    """(T, D) -> (T, E) weights over ALL experts: ``scale * sigma_e /
    sum_{T} sigma`` for the chosen, zero for the rest."""
    sigma = jax.nn.sigmoid(R.mm("td,de->te", t, wr, lowp))
    _, top = jax.lax.top_k(sigma + c, dims["top_k"])
    chosen = jnp.zeros_like(sigma).at[jnp.arange(sigma.shape[0])[:, None], top].set(1.0)
    return dims["scale"] * sigma * chosen / jnp.sum(sigma * chosen, -1, keepdims=True)


def act(z):
    return jnp.square(jax.nn.relu(z))


def latent_in(t, w_down, lowp: bool):
    """What the routed experts read: the input projected into the latent."""
    return R.mm("td,de->te", t, w_down, lowp)


def shared_in(t, a):
    """What the shared expert reads: the whole input (``a`` is the latent)."""
    return t


def experts(a, w, wi, wo2, dims: Dict[str, Any], lowp: bool) -> jax.Array:
    """``sum_{e held} w_e FFN_e(a)`` in the latent, an expert at a time
    (the weights arrive in the type they are held in and are widened one
    expert at a time)."""
    first, count = dims["experts_held"]

    def one(acc, args):
        w_e, wi_e, wo_e = args
        h = act(R.mm("te,ef->tf", a, wi_e[0].astype(R.F32), lowp))
        return acc + w_e[:, None] * R.mm("tf,fe->te", h, wo_e.astype(R.F32), lowp), None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(a), (w[:, first:first + count].T, wi, wo2))
    return acc


def expert_layer(u, leaf, raw, dims: Dict[str, Any], lowp: bool):
    B, S, D = u.shape
    t = u.reshape(B * S, D)
    a = latent_in(t, leaf("latent_down"), lowp)
    w = route(t, leaf("router"), leaf("router_bias"), dims, lowp)
    r = experts(a, w, raw("wi"), raw("wo2"), dims, lowp)
    h = act(R.mm("td,df->tf", shared_in(t, a), leaf("shared_wi")[0], lowp))
    out = R.mm("te,ed->td", r, leaf("latent_up"), lowp) + R.mm("tf,fd->td", h, leaf("shared_wo2"), lowp)
    return out.reshape(B, S, D)


_PREFIX = {"M": "ssm", "E": "moe", "*": "full"}


def logits(params: Dict[str, Any], tokens: jax.Array, dims: Dict[str, Any], lowp: bool = False) -> jax.Array:
    _counts(dims)
    eps, blocks = dims["norm_eps"], params["blocks"]
    x = params["wte"].astype(R.F32)[tokens]
    seen = {"ssm": 0, "moe": 0, "full": 0, "ln1_g": 0, "ln2_g": 0}
    for kind in dims["pattern"]:
        p, norm = _PREFIX[kind], "ln2_g" if kind == "E" else "ln1_g"

        def raw(name, p=p):
            return blocks[f"{p}_{name}"][seen[p]]

        def leaf(name, p=p):
            return raw(name, p).astype(R.F32)

        u = R.rmsnorm(x, blocks[norm][seen[norm]].astype(R.F32), eps)
        if kind == "M":
            x = x + mamba(u, leaf, dims, lowp)
        elif kind == "*":
            x = x + attention(u, leaf, dims, lowp)
        else:
            x = x + expert_layer(u, leaf, raw, dims, lowp)
        seen[p] += 1
        seen[norm] += 1
    x = R.rmsnorm(x, params["lnf_g"].astype(R.F32), eps)
    return R.mm("bsd,vd->bsv", x, params["lm_head"].astype(R.F32), lowp)


# -- what the algorithm needs, from shapes -------------------------------------------
def _mamba_params(dims: Dict[str, Any]) -> int:
    """The in- and out-projections of one state layer (the conv's taps and
    the per-head vectors are a thousandth of them and not counted)."""
    d, di = dims["d"], _inner(dims)
    return d * (di + _conv_dim(dims) + dims["ssm_heads"]) + di * d


def _attn_params(dims: Dict[str, Any]) -> int:
    d, H, hkv, hd = dims["d"], dims["heads"], dims["kv_heads"], dims["head_dim"]
    return 2 * d * H * hd + 2 * d * hkv * hd


def expert_params(dims: Dict[str, Any]) -> int:
    """One routed expert's two matrices, in the latent."""
    return 2 * dims["latent"] * dims["expert_ff"]


def always_read_params(dims: Dict[str, Any]) -> int:
    """Matmul parameters a decode step reads whatever the routing: the
    state layers, attention, and of each expert layer the router, the
    latent's two projections and the shared expert; the head."""
    n, d = _counts(dims), dims["d"]
    own = d * dims["experts"] + 2 * d * dims["latent"] + 2 * d * dims["shared_ff"]
    return n["ssm"] * _mamba_params(dims) + n["full"] * _attn_params(dims) + n["moe"] * own + dims["vocab"] * d


def matmul_params(dims: Dict[str, Any]) -> int:
    """Every matmul parameter held here: what a token's path could touch."""
    return always_read_params(dims) + _counts(dims)["moe"] * dims["experts_held"][1] * expert_params(dims)


def total_params(dims: Dict[str, Any]) -> int:
    return matmul_params(dims) + dims["vocab"] * dims["d"]


def attn_flops_per_token_fwd(dims: Dict[str, Any], seq: int) -> float:
    return _counts(dims)["full"] * 4.0 * dims["heads"] * dims["head_dim"] * (seq + 1) / 2.0


def kv_bytes_per_token(dims: Dict[str, Any], kv_bytes: int = 2) -> int:
    """K and V of one position in the attention layers."""
    return _counts(dims)["full"] * 2 * dims["kv_heads"] * dims["head_dim"] * kv_bytes


def state_bytes_per_slot(dims: Dict[str, Any], state_bytes: int = 4, tail_bytes: int = 2) -> int:
    """What the state layers keep for one request, whatever its length: the
    recurrent state (H x P x N) and the conv's last K - 1 rows, a layer."""
    return _counts(dims)["ssm"] * (
        _inner(dims) * dims["ssm_state"] * state_bytes + (dims["conv"] - 1) * _conv_dim(dims) * tail_bytes)


def hybrid_decode_step_bytes(dims: Dict[str, Any], live_slots: float, live_positions: float,
                             experts_hit_per_layer: float, weight_bytes: int = 2, kv_bytes: int = 2) -> float:
    """HBM bytes one decode token step has to move: the weights outside
    the routed experts once; one expert's weights for each held expert
    that a token hit, in each expert layer; the state and conv tail of
    the LIVE requests read and written; the attention layers' K/V of the
    live positions. (The state of an idle slot, which the program's step
    moves too, is not work the algorithm needs.)"""
    return (always_read_params(dims) * weight_bytes
            + _counts(dims)["moe"] * experts_hit_per_layer * expert_params(dims) * weight_bytes
            + 2.0 * live_slots * state_bytes_per_slot(dims)
            + live_positions * kv_bytes_per_token(dims, kv_bytes))


# -- what the program counted, over the window ---------------------------------------
def _window(program: Dict[str, Any], key: str) -> Any:
    """The window's share of the replica's ``stats()[key]`` (monotone
    totals, so the difference of the two calls that bracket the window is
    exactly the window); None from a program that has no such counters."""
    s0 = (program.get("stats0") or {}).get(key)
    s1 = (program.get("stats1") or {}).get(key)
    if not s0 or not s1:
        return None
    return {ph: {k: s1[ph][k] - s0[ph].get(k, 0) for k in s1[ph]} for ph in ("decode", "prefill")}


def moe_window(program: Dict[str, Any]) -> Any:
    """``{"expert_layers", "decode": {...}, "prefill": {...}}`` of
    ``stats()["moe"]`` over the window."""
    w = _window(program, "moe")
    return w and dict(w, expert_layers=int(program["stats1"]["moe"]["expert_layers"]))


def ssm_window(program: Dict[str, Any]) -> Any:
    """``{"decode": {slot_steps, slot_steps_live}, "prefill": {rows_scanned,
    rows_real}}`` of ``stats()["ssm"]`` over the window."""
    return _window(program, "ssm")


def experts_hit_per_step(program: Dict[str, Any]) -> Any:
    """Held experts that got a token, per expert layer and decode token
    step of the window; None without counters or without a step."""
    w = moe_window(program)
    if w is None or w["decode"]["token_steps"] <= 0:
        return None
    return w["decode"]["experts_hit"] / (w["decode"]["token_steps"] * w["expert_layers"])
