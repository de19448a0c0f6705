"""Ring attention: sequence-parallel attention over a mesh axis.

Long-context support (SURVEY.md notes the reference has none — this is a
capability the TPU build adds as first-class): the sequence dimension is
sharded across devices on a mesh axis; each device keeps its local Q shard
resident and K/V shards rotate around the ring via ``lax.ppermute`` (ICI
neighbor exchange), with online-softmax accumulation so the full (S, S)
score matrix never exists on any chip and per-chip memory stays
O(S_local * S_local) per step. This is the blockwise/ring formulation of
attention (Liu et al., Ring Attention) expressed as an SPMD per-rank
program under ``shard_map``.

Differentiable: built from ``lax.scan`` + ``ppermute``, both of which have
transposes, so ``jax.grad`` works through it (the backward pass rotates
gradients the opposite way around the ring).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map

_NEG_INF = float("-inf")


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    vary_axes: Optional[tuple] = None,
    window: int = 0,
    sinks: int = 0,
) -> jax.Array:
    """Per-rank ring attention; call inside ``shard_map``/``pmap``.

    Args:
      q, k, v: local sequence shards, (B, S_local, H, D). The global
        sequence is the concatenation over the ``axis_name`` ring order.
      axis_name: mesh axis the sequence is sharded over.
      causal: apply a causal mask in *global* positions.
      vary_axes: every mesh axis the inputs are sharded (device-varying)
        over — needed to type the scan carry when batch/heads ride dp/tp
        axes in addition to the ring axis. Defaults to (axis_name,).
      window: sliding-window width W > 0 restricts each query to its W most
        recent positions. The ring becomes BAND-LIMITED: only
        ``ceil((W-1)/S_local) + 1`` K/V rotations run instead of the full
        ring — out-of-window source shards are never even received, so the
        window is a communication *and* FLOPs win, not just a mask.
      sinks: StreamingLLM attention sinks — the first ``sinks`` global
        positions stay visible to every query. Handled as one extra
        (B, sinks) block all-gathered from the ring once (sink tokens live
        on the rank holding the sequence start), NOT by widening the band.
        Exactly partitions the dense mask: band steps own ``col > row - W``,
        the sink block owns ``col < sinks and col <= row - W``.

    Returns the local output shard (B, S_local, H, D).
    """
    from ray_lightning_tpu.ops.attention import causal_mask_allowed

    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    if window and not causal:
        raise ValueError("window attention requires causal=True")
    if sinks and not window:
        raise ValueError("sinks only apply with a sliding window")
    axis_size = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    batch, s_local, heads, head_dim = q.shape
    if sinks > s_local:
        raise ValueError(
            f"sinks ({sinks}) must fit in one sequence shard ({s_local})"
        )
    qf = q.astype(jnp.float32) * sm_scale

    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    def step(carry, step_idx):
        k_cur, v_cur, m_prev, l_prev, acc_prev = carry
        # The K/V shard currently held originated on rank (my_idx - step).
        src_idx = (my_idx - step_idx) % axis_size
        s = jnp.einsum(
            "bqhd,bkhd->bhqk",
            qf,
            k_cur.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )  # (B, H, Sq_local, Sk_local)
        if causal:
            allowed = causal_mask_allowed(
                s_local, s_local,
                row_offset=my_idx * s_local,
                col_offset=src_idx * s_local,
                window=window,
            )
            s = jnp.where(allowed[None, None], s, _NEG_INF)
        m_cur = jnp.max(s, axis=-1)  # (B, H, Sq)
        m_new = jnp.maximum(m_prev, m_cur)
        # Fully-masked-so-far rows have m_new == -inf; substitute 0 in the
        # exponent shifts (exp(-inf - 0) = 0) to avoid (-inf) - (-inf) NaNs.
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        alpha = jnp.exp(m_prev - m_safe)  # (B, H, Sq)
        p = jnp.exp(s - m_safe[..., None])  # (B, H, Sq, Sk)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1)
        acc_new = acc_prev * alpha[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, v_cur.astype(jnp.float32)
        )
        # Rotate K/V to the next rank (ICI neighbor exchange). The final
        # rotation returns the shards home, keeping the scan carry uniform.
        k_next = jax.lax.ppermute(k_cur, axis_name, perm)
        v_next = jax.lax.ppermute(v_cur, axis_name, perm)
        return (k_next, v_next, m_new, l_new, acc_new), None

    axes = tuple(vary_axes) if vary_axes else (axis_name,)

    def _varying(x):
        # shard_map's vma type system requires the scan carry to be marked
        # device-varying over every axis the inputs are sharded on (the
        # accumulators genuinely differ per rank on each of them).
        return jax.lax.pcast(x, axes, to="varying")

    init = (
        k,
        v,
        _varying(jnp.full((batch, heads, s_local), _NEG_INF, jnp.float32)),
        _varying(jnp.zeros((batch, heads, s_local), jnp.float32)),
        _varying(jnp.zeros((batch, heads, s_local, head_dim), jnp.float32)),
    )
    # Band limit: a query's window spans at most ceil((W-1)/S_local) shards
    # before its own, so later rotations would deliver only fully-masked
    # shards — skip them entirely.
    if window:
        n_steps = min(axis_size, (window + s_local - 2) // s_local + 1)
    else:
        n_steps = axis_size
    (_, _, m, l, acc), _ = jax.lax.scan(
        step, init, jnp.arange(n_steps), length=n_steps
    )
    if sinks:
        # One extra block for the always-visible sequence start. The sink
        # K/V live on the rank holding global positions [0, sinks); the
        # all-gather is tiny (B, sinks, H, D) and happens once per call.
        sink_k = jax.lax.all_gather(k[:, :sinks], axis_name, tiled=False)[0]
        sink_v = jax.lax.all_gather(v[:, :sinks], axis_name, tiled=False)[0]
        s = jnp.einsum(
            "bqhd,bkhd->bhqk",
            qf,
            sink_k.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )  # (B, H, Sq_local, sinks)
        # Only the part of the mask the band steps did NOT cover:
        # col < sinks AND col <= row - W (outside the window, but a sink).
        row = (
            jax.lax.broadcasted_iota(jnp.int32, (s_local, sinks), 0)
            + my_idx * s_local
        )
        col = jax.lax.broadcasted_iota(jnp.int32, (s_local, sinks), 1)
        s = jnp.where((col <= row - window)[None, None], s, _NEG_INF)
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m, m_cur)
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        alpha = jnp.exp(m - m_safe)
        p = jnp.exp(s - m_safe[..., None])
        l = l * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, sink_v.astype(jnp.float32)
        )
    l_safe = jnp.where(l == 0.0, 1.0, l)
    out = acc / l_safe[..., None]  # (B, H, Sq, D)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def ring_self_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: jax.sharding.Mesh,
    axis_name: str = "seq",
    causal: bool = True,
    sm_scale: Optional[float] = None,
    window: int = 0,
    sinks: int = 0,
) -> jax.Array:
    """Global-view wrapper: shards (B, S, H, D) over ``axis_name`` and runs
    the per-rank ring program under ``shard_map``.

    The batch dim stays sharded over any nontrivial data-parallel mesh axes
    (otherwise shard_map would declare it replicated and XLA would
    all-gather activations over the dp axes at every layer)."""
    # Shared (B, S, H, D) spec policy with the zigzag wrapper: batch rides
    # dp axes, heads ride the tensor-parallel axis when they divide it
    # (matches the GSPMD qkv sharding).
    from ray_lightning_tpu.ops.zigzag_attention import _seq_specs

    spec, vary = _seq_specs(mesh, axis_name, q.shape[2])
    fn = functools.partial(
        ring_attention,
        axis_name=axis_name,
        causal=causal,
        sm_scale=sm_scale,
        vary_axes=vary,
        window=window,
        sinks=sinks,
    )
    return shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec
    )(q, k, v)
