"""Zigzag (load-balanced) causal ring attention.

Plain contiguous-sharded ring attention (ops/ring_attention.py) wastes ~half
its FLOPs on causal masks: rank i's query shard may only attend to key
shards j <= i, yet the SPMD program computes (and masks away) every (i, j)
block. Zigzag sharding fixes the imbalance structurally: the global sequence
is cut into 2P chunks and rank i owns chunks (i, 2P-1-i) — one early, one
late. Then at every ring step each rank has exactly TWO fully-unmasked
C x C blocks to compute (the late-query x early-key block, plus either an
early x early or late x late block depending on ring distance), and the two
diagonal blocks appear only in the prologue step that every rank executes
simultaneously. No masked work inside the steady-state loop at all —
~2x fewer attention FLOPs than the contiguous ring at large P, with every
rank doing identical work every tick (no stragglers between ppermutes).

This is the balancing used by context-parallel trainers for causal LMs
(e.g. the "zigzag"/"striped" variants of Ring Attention). Built from
``lax.scan`` + ``ppermute`` so autodiff transposes it into the reverse
ring, like the plain ring op.

Layout contract: callers keep activations in zigzag order end-to-end for
zero-cost integration (permute token/position ids once at the input);
:func:`zigzag_ring_self_attention` is the global-view wrapper that instead
permutes internally — convenient, but the permutation resharding is paid
per call, so models should prefer the layout contract.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map

_NEG_INF = float("-inf")


def zigzag_permutation(seq: int, ring: int) -> np.ndarray:
    """Natural order -> zigzag order indices.

    Chunk order becomes [0, 2P-1, 1, 2P-2, ...]; shard p of the permuted
    array then holds exactly global chunks (p, 2P-1-p).
    """
    if seq % (2 * ring):
        raise ValueError(f"seq {seq} must divide by 2*ring ({2 * ring})")
    c = seq // (2 * ring)
    chunks = np.arange(seq).reshape(2 * ring, c)
    order = []
    for p in range(ring):
        order.append(chunks[p])
        order.append(chunks[2 * ring - 1 - p])
    return np.concatenate(order)


def inverse_permutation(perm: np.ndarray) -> np.ndarray:
    inv = np.empty_like(perm)
    inv[perm] = np.arange(len(perm))
    return inv


def _online_merge(m, l, acc, s, v):
    """Merge one unmasked score block into (m, l, acc) accumulators."""
    m_cur = jnp.max(s, axis=-1)
    m_new = jnp.maximum(m, m_cur)
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    alpha = jnp.exp(m - m_safe)
    p = jnp.exp(s - m_safe[..., None])
    l_new = l * alpha + jnp.sum(p, axis=-1)
    acc_new = acc * alpha[..., None] + jnp.einsum(
        "bhqk,bkhd->bhqd", p, v.astype(jnp.float32)
    )
    return m_new, l_new, acc_new


def zigzag_ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    sm_scale: Optional[float] = None,
) -> jax.Array:
    """Per-rank zigzag ring attention; call inside ``shard_map``.

    q/k/v: (B, 2C, H, D) local shards in ZIGZAG layout — rows [0:C] are
    global chunk ``i`` (early), rows [C:2C] are global chunk ``2P-1-i``
    (late). Causal only (that is the point of the balancing).
    Returns the local output shard in the same layout.
    """
    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    ring = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    batch, s_local, heads, head_dim = q.shape
    if s_local % 2:
        raise ValueError("zigzag local shard must hold two chunks")
    C = s_local // 2
    qf = q.astype(jnp.float32) * sm_scale
    qe, ql = qf[:, :C], qf[:, C:]

    def scores(qc, kc):
        return jnp.einsum(
            "bqhd,bkhd->bhqk",
            qc,
            kc.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )

    from ray_lightning_tpu.ops.attention import causal_mask_allowed

    diag = causal_mask_allowed(C, C)  # aligned diagonal mask

    def empty_acc():
        return (
            jnp.full((batch, heads, C), _NEG_INF, jnp.float32),
            jnp.zeros((batch, heads, C), jnp.float32),
            jnp.zeros((batch, heads, C, head_dim), jnp.float32),
        )

    # ---- prologue (ring distance 0: own K/V) --------------------------
    ke, kl = k[:, :C], k[:, C:]
    ve, vl = v[:, :C], v[:, C:]
    # early q x early k: diagonal block of chunk i.
    s_ee = jnp.where(diag[None, None], scores(qe, ke), _NEG_INF)
    m_e, l_e, acc_e = _online_merge(*empty_acc(), s_ee, ve)
    # late q x late k: diagonal block of chunk 2P-1-i.
    s_ll = jnp.where(diag[None, None], scores(ql, kl), _NEG_INF)
    m_l, l_l, acc_l = _online_merge(*empty_acc(), s_ll, vl)
    # late q x early k: always fully allowed (late positions come after
    # every early position).
    m_l, l_l, acc_l = _online_merge(m_l, l_l, acc_l, scores(ql, ke), ve)

    # Unlike ring_attention (whose fresh-zeros carry needs explicit vma
    # annotation), the carry here derives entirely from the device-varying
    # inputs, so no vary_axes plumbing is needed.
    perm = [(r, (r + 1) % ring) for r in range(ring)]

    def tick(carry, t):
        k_cur, v_cur, m_e, l_e, acc_e, m_l, l_l, acc_l = carry
        k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
        v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
        src = (my - t) % ring  # origin rank of the held K/V
        early_branch = src < my  # else: late x late block
        ke_c, kl_c = k_cur[:, :C], k_cur[:, C:]
        ve_c, vl_c = v_cur[:, :C], v_cur[:, C:]

        # Selected unmasked block: early-q x early-k(src) when src < my
        # (those keys precede our early chunk), otherwise late-q x
        # late-k(2P-1-src) (those keys precede our late chunk). Exactly one
        # einsum pair either way — no masked compute in the loop.
        q_sel = jnp.where(early_branch, qe, ql)
        k_sel = jnp.where(early_branch, ke_c, kl_c)
        v_sel = jnp.where(early_branch, ve_c, vl_c)
        s_sel = scores(q_sel, k_sel)
        m_tgt = jnp.where(early_branch, m_e, m_l)
        l_tgt = jnp.where(early_branch, l_e, l_l)
        acc_tgt = jnp.where(early_branch, acc_e, acc_l)
        m2, l2, acc2 = _online_merge(m_tgt, l_tgt, acc_tgt, s_sel, v_sel)
        m_e = jnp.where(early_branch, m2, m_e)
        l_e = jnp.where(early_branch, l2, l_e)
        acc_e = jnp.where(early_branch, acc2, acc_e)
        m_l = jnp.where(early_branch, m_l, m2)
        l_l = jnp.where(early_branch, l_l, l2)
        acc_l = jnp.where(early_branch, acc_l, acc2)

        # Late-q x early-k(src): always fully allowed.
        m_l, l_l, acc_l = _online_merge(m_l, l_l, acc_l, scores(ql, ke_c), ve_c)
        return (k_cur, v_cur, m_e, l_e, acc_e, m_l, l_l, acc_l), None

    init = (k, v, m_e, l_e, acc_e, m_l, l_l, acc_l)
    (_, _, m_e, l_e, acc_e, m_l, l_l, acc_l), _ = jax.lax.scan(
        tick, init, jnp.arange(1, ring), length=ring - 1
    )

    def finalize(l, acc):
        l_safe = jnp.where(l == 0.0, 1.0, l)
        return (acc / l_safe[..., None]).transpose(0, 2, 1, 3)

    out = jnp.concatenate([finalize(l_e, acc_e), finalize(l_l, acc_l)], axis=1)
    return out.astype(q.dtype)


def _seq_specs(
    mesh: jax.sharding.Mesh, axis_name: Optional[str], n_heads: int
):
    """(PartitionSpec, vary_axes) for (B, S, H, D) activations on this mesh
    — shared by the ring, zigzag and flash wrappers (``axis_name=None``:
    the sequence stays whole)."""
    from jax.sharding import PartitionSpec as P

    dp_axes = tuple(
        ax
        for ax in ("data", "fsdp")
        if ax != axis_name and mesh.shape.get(ax, 1) > 1
    )
    head_axis = None
    model_size = mesh.shape.get("model", 1)
    if "model" != axis_name and model_size > 1 and n_heads % model_size == 0:
        head_axis = "model"
    spec = P(dp_axes or None, axis_name, head_axis, None)
    vary = tuple(
        ax for ax in (axis_name, *dp_axes, head_axis) if ax is not None
    )
    return spec, vary


def zigzag_self_attention_zlayout(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: jax.sharding.Mesh,
    axis_name: str = "seq",
    sm_scale: Optional[float] = None,
) -> jax.Array:
    """Wrapper for inputs ALREADY in zigzag layout (the zero-cost model
    integration contract): no permutes, just the balanced per-rank program
    under ``shard_map``. Output stays in zigzag layout."""
    spec, _ = _seq_specs(mesh, axis_name, q.shape[2])
    fn = functools.partial(
        zigzag_ring_attention, axis_name=axis_name, sm_scale=sm_scale
    )
    return shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec
    )(q, k, v)


def zigzag_ring_self_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: jax.sharding.Mesh,
    axis_name: str = "seq",
    sm_scale: Optional[float] = None,
) -> jax.Array:
    """Global-view wrapper over naturally-ordered (B, S, H, D) inputs.

    Permutes to zigzag layout, runs the balanced per-rank program under
    ``shard_map``, and un-permutes the output. The permutation is a
    resharding collective each call — models integrating zigzag should keep
    activations in zigzag order end-to-end instead (see module docstring
    and :func:`zigzag_self_attention_zlayout`).
    """
    ring = mesh.shape[axis_name]
    S = q.shape[1]
    perm_np = zigzag_permutation(S, ring)  # static (host) indices
    perm = jnp.asarray(perm_np)
    inv = jnp.asarray(inverse_permutation(perm_np))

    spec, _ = _seq_specs(mesh, axis_name, q.shape[2])
    qz, kz, vz = (x[:, perm] for x in (q, k, v))
    out = zigzag_self_attention_zlayout(
        qz, kz, vz, mesh, axis_name=axis_name, sm_scale=sm_scale
    )
    out = out[:, inv]
    # The un-permute gather would otherwise leave the result replicated;
    # pin the caller-facing sharding so downstream layers stay seq-sharded.
    try:
        from jax.sharding import NamedSharding

        out = jax.lax.with_sharding_constraint(out, NamedSharding(mesh, spec))
    except ValueError:
        pass  # eager call outside any mesh context
    return out
