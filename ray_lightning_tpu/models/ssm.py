"""The Mamba-2 state layer of a model with mixed layer kinds
(``GPTConfig.layer_types`` kind "ssm"; models/mixed.py holds the block).

With ``u`` the layer's normed input, H heads of P channels, B and C in G
groups of N (head h reads group ``h // (H / G)``) and K conv taps::

    [z | xBC | dt] = u W_z | u W_x | u W_dt          (H P | H P + 2 G N | H)
    xBC_t <- silu(b + sum_j w[j] * xBC_{t-K+1+j})    causal, zeros before row 0
    dt_t = softplus(dt_t + dt_bias),  A = -exp(A_log)            per head
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T,  y_t = S_t C_t + D x_t
    out = RMSNorm_per_group(y_t * silu(z_t); g) W_o

A model with muP scalars (``GPTConfig.multipliers``) takes ``u`` times
``ssm_in``, multiplies z, x, B, C and dt by a scalar each before the conv
and before ``dt_bias``, and ``out`` by ``ssm_out`` (:func:`_in_scales`; the
one recurrence below serves both).

Two evaluations of the one recurrence:

- :func:`ssm_rows` (forward, prefill): over S rows in chunks of
  ``ssm_chunk`` — matmuls within a chunk, a carried state between chunks —
  and the state after the last REAL row and the last K - 1 real pre-conv
  rows come out (``valid`` marks the real rows of a right-padded prompt:
  a row that is not real has ``dt = 0``, which is decay one and input
  zero, so it leaves the state as it was).
- :func:`ssm_step` (decode): one row a slot; the slots' state and conv
  tail go in and come out, each a whole array that a donating caller has
  updated in place. A slot that is not live keeps its state bit for bit:
  on a TPU the update is a kernel that walks the live slots and reads and
  writes no other's state (``ops/ssm_step.py``), elsewhere one XLA pass
  over every slot's.

The state is float32 whatever the compute dtype (it is summed into for
every token of a request); the matmuls take their operands in the compute
dtype and accumulate in float32.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def d_inner(cfg: Any) -> int:
    return cfg.ssm_heads * cfg.ssm_head_dim


def conv_dim(cfg: Any) -> int:
    """Channels the conv runs over: x, then B, then C."""
    return d_inner(cfg) + 2 * cfg.ssm_groups * cfg.ssm_state


def param_shapes(cfg: Any, n: int) -> Dict[str, Tuple[int, ...]]:
    """The leaves of ``n`` state layers, stacked. The conv's taps lie
    taps-major (``w[j]`` is one row of channels)."""
    D, H, di, C = cfg.d_model, cfg.ssm_heads, d_inner(cfg), conv_dim(cfg)
    return {
        "ssm_wz": (n, D, di), "ssm_wx": (n, D, C), "ssm_wdt": (n, D, H),
        "ssm_conv_w": (n, cfg.ssm_conv, C), "ssm_conv_b": (n, C),
        "ssm_dt_bias": (n, H), "ssm_A_log": (n, H), "ssm_D": (n, H),
        "ssm_norm_g": (n, di), "ssm_wo": (n, di, D),
    }


def empty_state(cfg: Any, slots: int, dtype: Any) -> Tuple[jax.Array, jax.Array]:
    """One layer's per-slot state: the recurrent state (slots, H, P, N)
    float32 and the conv tail (K - 1, slots, channels), the slots on the
    second-minor axis so that K - 1 rows are not padded to a tile."""
    return (
        jnp.zeros((slots, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state), F32),
        jnp.zeros((cfg.ssm_conv - 1, slots, conv_dim(cfg)), dtype),
    )


# -- pieces shared by the two modes ------------------------------------------------
def _in_scales(cfg: Any):
    """What each part of the in-projection is multiplied by where the
    model has muP scalars (``GPTConfig.multipliers``): the input's
    ``ssm_in`` folded into the part's own, ``(u s_in) W s_part = (u W)
    (s_in s_part)``. ``(z, xBC, dt)``: a float, or for xBC — whose x, B
    and C differ — one value a channel; None where nothing is multiplied,
    decided while the program is traced."""
    s_in = cfg.multiplier("ssm_in")
    z, x, b, c, dt = (s_in * cfg.multiplier("ssm_" + part) for part in ("z", "x", "b", "c", "dt"))
    gn = cfg.ssm_groups * cfg.ssm_state
    xbc = None if x == b == c == 1.0 else np.repeat(np.float32([x, b, c]), [d_inner(cfg), gn, gn])
    return (None if z == 1.0 else z), xbc, (None if dt == 1.0 else dt)


def _in_proj(u: jax.Array, lp: Dict[str, Any], cfg: Any, cdt: Any):
    with jax.named_scope("ssm_in_proj"):
        z = jnp.einsum("bsd,de->bse", u, lp["wz"].astype(cdt))
        xbc = jnp.einsum("bsd,de->bse", u, lp["wx"].astype(cdt))
        dt = jnp.einsum("bsd,dh->bsh", u, lp["wdt"].astype(cdt))
        z, xbc, dt = (
            a if s is None else a * jnp.asarray(s, a.dtype)
            for a, s in zip((z, xbc, dt), _in_scales(cfg))
        )
    return z, xbc, dt


def _split(xbc: jax.Array, cfg: Any):
    """(..., channels) -> x (..., G, H/G, P), B and C (..., G, N)."""
    G, N, di = cfg.ssm_groups, cfg.ssm_state, d_inner(cfg)
    lead = xbc.shape[:-1]
    x = xbc[..., :di].reshape(lead + (G, cfg.ssm_heads // G, cfg.ssm_head_dim))
    b = xbc[..., di:di + G * N].reshape(lead + (G, N))
    c = xbc[..., di + G * N:].reshape(lead + (G, N))
    return x, b, c


def _per_head(v: jax.Array, cfg: Any) -> jax.Array:
    """(..., H) -> (..., G, H/G) float32."""
    G = cfg.ssm_groups
    return v.astype(F32).reshape(v.shape[:-1] + (G, cfg.ssm_heads // G))


def _step_sizes(dt: jax.Array, lp: Dict[str, Any], cfg: Any):
    """Raw dt (..., H) -> dt = softplus(dt + dt_bias) and A, by head."""
    dt = jax.nn.softplus(_per_head(dt, cfg) + _per_head(lp["dt_bias"], cfg))
    return dt, -jnp.exp(_per_head(lp["A_log"], cfg))


def _gate_out(y: jax.Array, z: jax.Array, lp: Dict[str, Any], cfg: Any, cdt: Any) -> jax.Array:
    """y (B, S, G, H/G, P) float32 and z (B, S, H P): the gate, then the
    norm over each group's channels on its own, then the out-projection."""
    with jax.named_scope("ssm_gate_out"):
        B, S, G = y.shape[:3]
        y = y.reshape(B, S, G, -1) * jax.nn.silu(z.astype(F32)).reshape(B, S, G, -1)
        y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + cfg.norm_eps)
        y = y.reshape(B, S, -1) * lp["norm_g"].astype(F32)
        out = jnp.einsum("bse,ed->bsd", y.astype(cdt), lp["wo"].astype(cdt))
        s_out = cfg.multiplier("ssm_out")
        return out if s_out == 1.0 else out * jnp.asarray(s_out, out.dtype)


# -- rows: forward and prefill -------------------------------------------------------
def _scan_rows(x, dt, A, bm, cm, chunk: int, cdt: Any):
    """The recurrence over S rows from a zero state, in chunks: x (B, S,
    G, R, P), dt (B, S, G, R) (zero on rows that are not real), A (G, R),
    bm and cm (B, S, G, N), all float32 -> y (B, S, G, R, P) without the
    D term, and the state after the last row (B, G, R, P, N)."""
    B, S = x.shape[:2]
    Q = min(int(chunk), S)
    pad = -S % Q
    if pad:
        x, dt, bm, cm = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2)) for a in (x, dt, bm, cm))
    nc = (S + pad) // Q
    x, dt, bm, cm = (a.reshape((B, nc, Q) + a.shape[2:]) for a in (x, dt, bm, cm))
    xc, bc, cc = x.astype(cdt), bm.astype(cdt), cm.astype(cdt)
    dt_t = jnp.moveaxis(dt, 2, -1)  # (B, nc, G, R, Q)
    cum = jnp.cumsum(dt_t * A[..., None], axis=-1)  # log of the decay from the chunk's start
    # within a chunk: y_q += sum_{s <= q} exp(cum_q - cum_s) dt_s (C_q . B_s) x_s
    cb = jnp.einsum("bcqgn,bcsgn->bcgqs", cc, bc, preferred_element_type=F32)
    q = jnp.arange(Q)
    diff = jnp.where(q[None, :] <= q[:, None], cum[..., :, None] - cum[..., None, :], -jnp.inf)
    m = jnp.exp(diff) * cb[:, :, :, None] * dt_t[..., None, :]
    y = jnp.einsum("bcgrqs,bcsgrp->bcqgrp", m.astype(cdt), xc, preferred_element_type=F32)
    # what a chunk adds to the state, and what it leaves of the state before it
    w = jnp.exp(cum[..., -1:] - cum) * dt_t  # (B, nc, G, R, Q)
    xw = (x * jnp.moveaxis(w, -1, 2)[..., None]).astype(cdt)
    added = jnp.einsum("bcsgrp,bcsgn->bcgrpn", xw, bc, preferred_element_type=F32)
    kept = jnp.exp(cum[..., -1])  # (B, nc, G, R)

    def over_chunks(state, args):
        keep, add = args
        return keep[..., None, None] * state + add, state

    final, before = jax.lax.scan(
        over_chunks, jnp.zeros(added.shape[:1] + added.shape[2:], F32),
        (jnp.moveaxis(kept, 1, 0), jnp.moveaxis(added, 1, 0)),
    )
    before = jnp.moveaxis(before, 0, 1)  # (B, nc, G, R, P, N): the state a chunk starts from
    carried = jnp.einsum("bcqgn,bcgrpn->bcqgrp", cc, before.astype(cdt), preferred_element_type=F32)
    y = y + carried * jnp.moveaxis(jnp.exp(cum), -1, 2)[..., None]
    return y.reshape((B, nc * Q) + y.shape[3:])[:, :S], final


def ssm_rows(
    u: jax.Array, lp: Dict[str, Any], cfg: Any, valid: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The state layer over u (B, S, D), the normed rows of a sequence
    from its start -> (out (B, S, D), state (B, H, P, N) float32, conv
    tail (K - 1, B, channels)). ``valid`` (B, S) bool marks the real rows
    (the first ``n`` of a right-padded prompt): state and tail are those
    after the last real row, zeros in the tail where the prompt is shorter
    than K - 1."""
    cdt = jnp.dtype(cfg.compute_dtype)
    B, S, _ = u.shape
    K = cfg.ssm_conv
    z, xbc, dt = _in_proj(u, lp, cfg, cdt)
    with jax.named_scope("ssm_conv"):
        n_real = jnp.full((B,), S, jnp.int32) if valid is None else valid.sum(-1).astype(jnp.int32)
        front = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))  # row t of xbc is row t + K - 1
        rows = n_real[:, None] + jnp.arange(K - 1, dtype=jnp.int32)[None]  # the last K - 1 real rows
        tail = jnp.moveaxis(jnp.take_along_axis(front, rows[:, :, None], axis=1), 1, 0)
        w = lp["conv_w"].astype(F32)
        conv = lp["conv_b"].astype(F32) + sum(
            w[j] * front[:, j:j + S].astype(F32) for j in range(K)
        )
        x, bm, cm = _split(jax.nn.silu(conv), cfg)
    with jax.named_scope("ssm_scan"):
        dt, A = _step_sizes(dt, lp, cfg)
        if valid is not None:
            dt = jnp.where(valid[:, :, None, None], dt, 0.0)
        y, state = _scan_rows(x, dt, A, bm, cm, cfg.ssm_chunk, cdt)
        y = y + _per_head(lp["D"], cfg)[..., None] * x
    state = state.reshape(B, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state)
    return _gate_out(y, z, lp, cfg, cdt), state, tail


# -- one row a slot: decode ------------------------------------------------------------
def _step_heads(state: jax.Array, groups: int, backend: Optional[str] = None) -> int:
    """Which update a decode token step takes, from what it can observe: the
    heads of a block of the kernel that walks the live slots
    (``ops/ssm_step.py``), or 0 for the XLA lines — off a TPU (there the
    kernel would run interpreted) and at a shape the kernel takes no block
    of (``ops/ssm_step.py:step_heads``)."""
    from ray_lightning_tpu.ops.ssm_step import step_heads

    if (backend or jax.default_backend()) != "tpu":
        return 0
    return step_heads(*state.shape[1:], groups)


def _update_all(state, decay, dtx, bm, cm, active=None):
    """The recurrence's one row in XLA, over every slot: ``state`` (B, G,
    R, P, N), ``decay`` (B, G, R), ``dtx`` (B, G, R, P), ``bm`` and ``cm``
    (B, G, N) -> ``(state, y (B, G, R, P))``. One elementwise pass reads
    and writes the state of all B slots; a slot that is not ``active``
    keeps its state bit for bit and has zeros for y."""
    s = decay[..., None, None] * state + dtx[..., None] * bm[:, :, None, None, :]
    y = jnp.sum(s * cm[:, :, None, None, :], -1)
    if active is not None:
        at = active[:, None, None, None]
        s, y = jnp.where(at[..., None], s, state), jnp.where(at, y, 0.0)
    return s, y


def ssm_step(
    u: jax.Array, lp: Dict[str, Any], cfg: Any, state: jax.Array, tail: jax.Array,
    active: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One token a slot, u (B, 1, D), through the slots' ``state`` (B, H,
    P, N) and conv ``tail`` (K - 1, B, channels) -> (out (B, 1, D), state,
    tail), each slot advanced by its row. ``active`` (B,) bool, default
    all: **a slot that is not active keeps its state bit for bit** (its
    ``out`` is not read; its tail moves on, and the next admission into the
    slot overwrites both).

    The state's update is chosen while the program is traced
    (:func:`_step_heads`): on a TPU the kernel that walks the active slots
    and neither reads nor writes another slot's state, in place in the
    donated array (``ops/ssm_step.py``); elsewhere one elementwise XLA pass
    over every slot's state, which a donating caller has updated in place
    too. Same products in the same order; the sum over N in another."""
    cdt = jnp.dtype(cfg.compute_dtype)
    B = u.shape[0]
    z, xbc, dt = _in_proj(u, lp, cfg, cdt)
    with jax.named_scope("ssm_conv"):
        rows = jnp.concatenate([tail, xbc[:, 0][None].astype(tail.dtype)], axis=0)  # (K, B, channels)
        conv = lp["conv_b"].astype(F32) + jnp.sum(lp["conv_w"].astype(F32)[:, None] * rows.astype(F32), 0)
        x, bm, cm = _split(jax.nn.silu(conv), cfg)  # (B, G, R, P), (B, G, N)
    with jax.named_scope("ssm_scan"):
        dt, A = _step_sizes(dt[:, 0], lp, cfg)  # (B, G, R)
        decay, dtx = jnp.exp(dt * A), dt[..., None] * x
        heads = _step_heads(state, cfg.ssm_groups)
        if heads:
            from ray_lightning_tpu.ops.ssm_step import ssm_step_update

            H, P = state.shape[1:3]
            s, y = ssm_step_update(
                state, decay.reshape(B, H), dtx.reshape(B, H, P), bm, cm,
                jnp.ones((B,), jnp.bool_) if active is None else active, heads=heads,
            )
            y = y.reshape(x.shape)
        else:
            s, y = _update_all(state.reshape((B,) + x.shape[1:] + (cfg.ssm_state,)), decay, dtx, bm, cm, active)
        y = y + _per_head(lp["D"], cfg)[..., None] * x
    return _gate_out(y[:, None], z, lp, cfg, cdt), s.reshape(state.shape), rows[1:]
