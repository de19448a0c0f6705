"""Flash attention: Pallas online-softmax kernels for the TPU MXU.

The forward pass is a Pallas kernel (one grid cell per (batch*head,
q-block); K/V stream through an online-softmax ``fori_loop`` so the (Sq, Sk)
score matrix never materializes in HBM). The backward pass is two Pallas
kernels using the flash-attention gradient identities on block-recomputed
scores — a dk/dv kernel gridded over key blocks and a dq kernel gridded
over query blocks — so the backward never materializes (Sq, Sk) either
(the naive recompute costs B*H*S^2*4 bytes of HBM: 400 MB at B=8, H=12,
S=1024).

The kernels multiply in the dtype of their inputs and accumulate in float32:
bf16 q / k / v / do tiles reach the MXU as they are, and the tiles a kernel
computes (p, ds) are rounded to that dtype once, at their product, as
``attention_reference`` rounds p. Scores, softmax statistics (m, l, lse,
delta), the exponentials and the acc / dq / dk / dv accumulators are float32
whatever the inputs; float32 inputs multiply float32.

The forward kernel also takes what the backward kernels do not, and is
then forward-only (differentiating says so): a v of its own width (the
accumulator and the output are as wide as v, not q), K and V with fewer
heads than q (``(B * Hkv, S, d)``; the block's index map sends query head
``h`` to KV head ``h // (H // Hkv)``, so no KV head is repeated in HBM and
the heads of a group, consecutive grid steps, reuse the block that is
there), and ``true_len``, the count of real rows of a right-padded prompt,
as a prefetched scalar: a grid cell whose first row is at or past it visits
no key block and writes zeros. That is the read of a mixed layer's prefill
attention — the full and latent kinds, q·k 192 / v 128, 64 heads on 4 —
from the bucket rows up at which it beats the blocked XLA read, whose
scores go through HBM (``models/mixed.py:prefill_kernel`` says when). With
equal widths, a KV head a query head and no ``true_len`` the kernel lowers
to what it lowered to before it could: the train step's three kernels and
the dense engine's prefill keep their programs.

On non-TPU backends the same kernels run in Pallas interpret mode (tests).
Shapes the kernels cannot tile go to ``attention_reference``, with one
warning per shape naming the rule that rejected it.

On a mesh of more than one device the kernels run under ``shard_map``: a
Pallas kernel is a custom call that XLA's SPMD partitioner cannot split
(jax refuses to lower one inside a partitioned program), so the batch and
head shards are handed to it explicitly.
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ray_lightning_tpu.ops.attention import attention_reference, band_allowed
from ray_lightning_tpu.utils.rank_zero import rank_zero_warn

_NEG_INF = float("-inf")

#: (reason, shapes) already warned about: one warning per distinct cause,
#: not one per traced layer.
_warned: set = set()


def _default_block(seq: int) -> int:
    """The tile along a sequence of ``seq`` positions when the caller names
    none: the largest of 512 / 256 that divides it, else 128 (which is
    clipped to a shorter sequence, or sends a ragged one to the reference,
    as a named block is).

    Why the length alone (the chip, PERF.md §6, PR 31): a kernel's time does
    not follow the head width (64 and 128 read the same) or the operands'
    dtype. The VPU's work on the (block_q, block_k) score tile sets the pace,
    and a loop trip pays the latency of its reductions and products once
    whatever the tile: 512 x 512 is 2.8x faster than 128 x 128 at S 1024 and
    within 5% of the best tile from S 256 to 8192, though a causal call then
    computes 3/4 of the square, not 9/16. The chip's compiler takes 512
    wherever it takes 128 (head width to 256, bf16 and float32, S to 32k);
    1024 it refuses earlier."""
    for tile in (512, 256):
        if seq % tile == 0:
            return tile
    return 128


def _warn_once(key: Any, msg: str) -> None:
    if key not in _warned:
        _warned.add(key)
        rank_zero_warn(msg)


def _fwd_kernel(
    *refs,
    block_k: int, causal: bool, sm_scale: float, window: int, sinks: int,
    bounded: bool = False,
):
    # Block shapes: q (1, block_q, d); k (1, Sk, d); v (1, Sk, dv); o
    # (1, block_q, dv); lse (1, block_q, 8) — the stats row is padded to 8
    # lanes because TPU block shapes must have their last two dims
    # (8, 128)-conformant; the wrapper slices lane 0 back out. ``bounded``:
    # a prefetched scalar leads the refs, the count of real rows.
    len_ref, (q_ref, k_ref, v_ref, o_ref, lse_ref) = (
        (refs[0], refs[1:]) if bounded else (None, refs)
    )
    block_q = q_ref.shape[1]
    seq_k = k_ref.shape[1]
    head_dim = v_ref.shape[2]
    iq = pl.program_id(1)
    q = q_ref[0]  # (bq, d)

    q_offset = iq * block_q
    if causal:
        # Only key blocks at or below this q block's diagonal contribute.
        num_kb = jax.lax.div(q_offset + block_q + block_k - 1, block_k)
    else:
        num_kb = seq_k // block_k
    if window:
        # Sliding window: the earliest in-band column for ANY row in this
        # q block is row_min - window + 1 = q_offset - window + 1; key
        # blocks entirely before it contribute nothing. (row_min, not
        # row_max — later rows still need these blocks' columns.) Sink
        # blocks are visited by a separate prefix loop below, so the
        # S*W scaling survives sinks.
        first_kb = jnp.maximum(0, q_offset - window + 1) // block_k
    else:
        first_kb = 0
    if bounded:
        # A query block wholly past the prompt's end belongs to no request:
        # it visits no key block, and the lines below write zeros for it.
        num_kb = jnp.where(q_offset < len_ref[0], num_kb, 0)

    def body(i, carry):
        m_prev, l_prev, acc_prev = carry
        k = k_ref[0, pl.ds(i * block_k, block_k), :]
        v = v_ref[0, pl.ds(i * block_k, block_k), :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale  # (bq, bk)
        if causal:
            row = q_offset + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            col = i * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            s = jnp.where(band_allowed(row, col, window, sinks), s, _NEG_INF)
        m_cur = jnp.max(s, axis=-1, keepdims=True)  # (bq, 1)
        m_new = jnp.maximum(m_prev, m_cur)
        # -inf - -inf = nan: a row can be FULLY masked in a visited block
        # when a sliding window is narrower than the block (its stats are
        # still the init values then, so 0 is the correct contribution).
        alpha = jnp.where(
            m_prev == _NEG_INF, 0.0, jnp.exp(m_prev - m_new)
        )
        p = jnp.where(s == _NEG_INF, 0.0, jnp.exp(s - m_new))
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc_prev * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return m_new, l_new, acc_new

    init = (
        jnp.full((block_q, 1), _NEG_INF, jnp.float32),
        jnp.zeros((block_q, 1), jnp.float32),
        jnp.zeros((block_q, head_dim), jnp.float32),
    )
    if window and sinks:
        # Visit the sink block(s) not already covered by the band loop
        # (online softmax is order-agnostic, so two loops compose).
        n_sink_kb = jnp.minimum((sinks + block_k - 1) // block_k, first_kb)
        if bounded:
            n_sink_kb = jnp.minimum(n_sink_kb, num_kb)
        init = jax.lax.fori_loop(0, n_sink_kb, body, init)
    m, l, acc = jax.lax.fori_loop(first_kb, num_kb, body, init)
    # Rows with no unmasked keys (can't happen for causal self-attention with
    # aligned blocks, but keep the kernel total) produce l=0 -> output 0.
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o_ref[0] = (acc / l_safe).astype(o_ref.dtype)
    lse = (m + jnp.log(l_safe)).astype(jnp.float32)  # (bq, 1)
    lse_ref[0] = jnp.broadcast_to(lse, (block_q, 8))


def _flash_fwd(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool,
    sm_scale: float,
    block_q: int,
    block_k: int,
    interpret: bool,
    window: int = 0,
    sinks: int = 0,
    true_len: Optional[jax.Array] = None,
):
    """Run the kernel on q (B, S, H, D), k (B, Sk, Hkv, D), v (B, Sk, Hkv,
    Dv); returns (out (B, S, H, Dv), lse). Query head ``h`` reads KV head
    ``h // (H // Hkv)``: the block's index map names it, so no KV head is
    repeated in HBM and the query heads of a group, consecutive grid
    steps, reuse the K/V block that is there. ``true_len`` (int32 scalar)
    goes in as a prefetched scalar: query blocks wholly at or past it come
    out zeros and visit no key block."""
    batch, seq_q, heads, head_dim = q.shape
    seq_k, kv_heads, v_dim = k.shape[1], k.shape[2], v.shape[3]
    rep = heads // kv_heads
    block_q = min(block_q, seq_q)
    block_k = min(block_k, seq_k)
    if seq_q % block_q or seq_k % block_k:
        raise ValueError(
            f"sequence lengths ({seq_q}, {seq_k}) must be divisible by the "
            f"block sizes ({block_q}, {block_k})"
        )
    if causal and seq_q != seq_k:
        raise ValueError("causal flash kernel requires Sq == Sk (self-attention)")
    # Fold heads into the grid's batch dimension: (B*H, S, D).
    qf = q.transpose(0, 2, 1, 3).reshape(batch * heads, seq_q, head_dim)
    kf = k.transpose(0, 2, 1, 3).reshape(batch * kv_heads, seq_k, head_dim)
    vf = v.transpose(0, 2, 1, 3).reshape(batch * kv_heads, seq_k, v_dim)

    grid = (batch * heads, seq_q // block_q)
    bounded = true_len is not None
    kernel = functools.partial(
        _fwd_kernel,
        block_k=block_k,
        causal=causal,
        sm_scale=sm_scale,
        window=window,
        sinks=sinks,
        bounded=bounded,
    )
    # ``*_``: the prefetched scalar, where there is one. One KV head a
    # query head is today's map, letter for letter.
    rows = lambda b, i, *_: (b, i, 0)  # noqa: E731
    if rep == 1:
        whole = lambda b, i, *_: (b, 0, 0)  # noqa: E731
    else:
        whole = lambda b, i, *_: (b // rep, 0, 0)  # noqa: E731
    specs = dict(
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, head_dim), rows),
            pl.BlockSpec((1, seq_k, head_dim), whole),
            pl.BlockSpec((1, seq_k, v_dim), whole),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, v_dim), rows),
            pl.BlockSpec((1, block_q, 8), rows),
        ],
    )
    if bounded:
        from jax.experimental.pallas import tpu as pltpu

        specs = dict(
            grid_spec=pltpu.PrefetchScalarGridSpec(num_scalar_prefetch=1, **specs)
        )
    out, lse = pl.pallas_call(
        kernel,
        **specs,
        out_shape=[
            jax.ShapeDtypeStruct((batch * heads, seq_q, v_dim), q.dtype),
            jax.ShapeDtypeStruct((batch * heads, seq_q, 8), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(*((jnp.reshape(true_len, (1,)).astype(jnp.int32),) if bounded else ()), qf, kf, vf)
    out = out.reshape(batch, heads, seq_q, v_dim).transpose(0, 2, 1, 3)
    lse = lse[:, :, 0].reshape(batch, heads, seq_q)
    return out, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash(q, k, v, causal, sm_scale, block_q, block_k, interpret, window, sinks):
    out, _ = _flash_fwd(
        q, k, v, causal, sm_scale, block_q, block_k, interpret, window, sinks
    )
    return out


def _flash_vjp_fwd(
    q, k, v, causal, sm_scale, block_q, block_k, interpret, window, sinks
):
    out, lse = _flash_fwd(
        q, k, v, causal, sm_scale, block_q, block_k, interpret, window, sinks
    )
    return out, (q, k, v, out, lse)


def _dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    *, block_q: int, causal: bool, sm_scale: float, window: int, sinks: int,
):
    """One (batch*head, k-block) cell: accumulate dk/dv over q blocks.

    Causal skips q blocks strictly above this k block's diagonal.
    """
    seq_q = q_ref.shape[1]
    block_k = k_ref.shape[1]
    ik = pl.program_id(1)
    k = k_ref[0]  # (bk, d)
    v = v_ref[0]
    k_offset = ik * block_k
    start_qb = k_offset // block_q if causal else 0
    end_qb = seq_q // block_q
    if window:
        # Rows beyond col_max + window - 1 can't see any key in this block
        # — except blocks holding sink columns, which every row sees.
        banded = jnp.minimum(
            end_qb, (k_offset + block_k - 1 + window - 1) // block_q + 1
        )
        end_qb = (
            jnp.where(k_offset < sinks, end_qb, banded) if sinks else banded
        )

    def body(i, carry):
        dk, dv = carry
        qs = q_ref[0, pl.ds(i * block_q, block_q), :]
        dos = do_ref[0, pl.ds(i * block_q, block_q), :]
        lse = lse_ref[0, pl.ds(i * block_q, block_q), 0][:, None]
        delta = delta_ref[0, pl.ds(i * block_q, block_q), 0][:, None]
        s = jax.lax.dot_general(
            qs, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale
        if causal:
            row = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            col = k_offset + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            s = jnp.where(band_allowed(row, col, window, sinks), s, _NEG_INF)
        p = jnp.exp(s - lse)  # (bq, bk), rows of the full P sum to 1
        dv2 = dv + jax.lax.dot_general(
            p.astype(dos.dtype), dos, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            dos, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta) * sm_scale
        dk2 = dk + jax.lax.dot_general(
            ds.astype(qs.dtype), qs, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return dk2, dv2

    init = (
        jnp.zeros((block_k, k.shape[1]), jnp.float32),
        jnp.zeros((block_k, v.shape[1]), jnp.float32),
    )
    dk, dv = jax.lax.fori_loop(start_qb, end_qb, body, init)
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
    *, block_k: int, causal: bool, sm_scale: float, window: int, sinks: int,
):
    """One (batch*head, q-block) cell: accumulate dq over k blocks."""
    block_q = q_ref.shape[1]
    seq_k = k_ref.shape[1]
    iq = pl.program_id(1)
    q = q_ref[0]
    do = do_ref[0]
    lse = lse_ref[0, :, 0][:, None]
    delta = delta_ref[0, :, 0][:, None]
    q_offset = iq * block_q
    if causal:
        num_kb = jax.lax.div(q_offset + block_q + block_k - 1, block_k)
    else:
        num_kb = seq_k // block_k
    first_kb = (
        jnp.maximum(0, q_offset - window + 1) // block_k if window else 0
    )

    def body(i, dq):
        ks = k_ref[0, pl.ds(i * block_k, block_k), :]
        vs = v_ref[0, pl.ds(i * block_k, block_k), :]
        s = jax.lax.dot_general(
            q, ks, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale
        if causal:
            row = q_offset + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            col = i * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            s = jnp.where(band_allowed(row, col, window, sinks), s, _NEG_INF)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(
            do, vs, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta) * sm_scale
        return dq + jax.lax.dot_general(
            ds.astype(ks.dtype), ks, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    dq0 = jnp.zeros((block_q, q.shape[1]), jnp.float32)
    if window and sinks:
        n_sink_kb = (sinks + block_k - 1) // block_k
        dq0 = jax.lax.fori_loop(0, jnp.minimum(n_sink_kb, first_kb), body, dq0)
    dq = jax.lax.fori_loop(first_kb, num_kb, body, dq0)
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _flash_vjp_bwd(
    causal, sm_scale, block_q, block_k, interpret, window, sinks, res, do
):
    """Flash-attention backward: two Pallas kernels over recomputed score
    blocks (never the full (Sq, Sk) matrix). delta = rowsum(do * o) is the
    softmax-jacobian correction term."""
    q, k, v, out, lse = res
    batch, seq_q, heads, head_dim = q.shape
    seq_k = k.shape[1]
    bq, bk = min(block_q, seq_q), min(block_k, seq_k)

    qf = q.transpose(0, 2, 1, 3).reshape(batch * heads, seq_q, head_dim)
    kf = k.transpose(0, 2, 1, 3).reshape(batch * heads, seq_k, head_dim)
    vf = v.transpose(0, 2, 1, 3).reshape(batch * heads, seq_k, head_dim)
    dof = do.transpose(0, 2, 1, 3).reshape(batch * heads, seq_q, head_dim)
    delta = jnp.sum(
        do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
    )  # (B, Sq, H)
    delta = delta.transpose(0, 2, 1).reshape(batch * heads, seq_q)
    lsef = lse.reshape(batch * heads, seq_q)
    # Stats rows padded to 8 lanes (TPU block-shape conformance, as in fwd).
    lse8 = jnp.broadcast_to(lsef[..., None], (batch * heads, seq_q, 8))
    delta8 = jnp.broadcast_to(delta[..., None], (batch * heads, seq_q, 8))

    dk, dv = pl.pallas_call(
        functools.partial(
            _dkv_kernel,
            block_q=bq,
            causal=causal,
            sm_scale=sm_scale,
            window=window,
            sinks=sinks,
        ),
        grid=(batch * heads, seq_k // bk),
        in_specs=[
            pl.BlockSpec((1, seq_q, head_dim), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, bk, head_dim), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, bk, head_dim), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, seq_q, head_dim), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, seq_q, 8), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, seq_q, 8), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, head_dim), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, bk, head_dim), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((batch * heads, seq_k, head_dim), k.dtype),
            jax.ShapeDtypeStruct((batch * heads, seq_k, head_dim), v.dtype),
        ],
        interpret=interpret,
        name="flash_dkv",
    )(qf, kf, vf, dof, lse8, delta8)

    dq = pl.pallas_call(
        functools.partial(
            _dq_kernel,
            block_k=bk,
            causal=causal,
            sm_scale=sm_scale,
            window=window,
            sinks=sinks,
        ),
        grid=(batch * heads, seq_q // bq),
        in_specs=[
            pl.BlockSpec((1, bq, head_dim), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, seq_k, head_dim), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, seq_k, head_dim), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, bq, head_dim), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, bq, 8), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, bq, 8), lambda b, i: (b, i, 0)),
        ],
        out_specs=[pl.BlockSpec((1, bq, head_dim), lambda b, i: (b, i, 0))],
        out_shape=[
            jax.ShapeDtypeStruct((batch * heads, seq_q, head_dim), q.dtype)
        ],
        interpret=interpret,
        name="flash_dq",
    )(qf, kf, vf, dof, lse8, delta8)[0]

    unflatten = lambda x, s: x.reshape(  # noqa: E731
        batch, heads, s, head_dim
    ).transpose(0, 2, 1, 3)
    return (
        unflatten(dq, seq_q).astype(q.dtype),
        unflatten(dk, seq_k).astype(k.dtype),
        unflatten(dv, seq_k).astype(v.dtype),
    )


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10))
def _flash_forward_only(
    q, k, v, true_len, causal, sm_scale, block_q, block_k, interpret, window, sinks
):
    """The forward kernel for what the backward kernels do not take: a v
    of its own width, grouped KV heads, a count of real rows."""
    return _flash_fwd(
        q, k, v, causal, sm_scale, block_q, block_k, interpret, window, sinks,
        true_len=true_len,
    )[0]


def _forward_only_refuses(*_):
    raise NotImplementedError(
        "flash_attention is forward-only with unequal q·k / v widths, grouped "
        "KV heads or true_len: flash_dkv and flash_dq take one head width, "
        "one KV head a query head and every row (repeat the KV heads and "
        "pad v to differentiate through the kernels)"
    )


_flash_forward_only.defvjp(_forward_only_refuses, _forward_only_refuses)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
    window: int = 0,
    sinks: int = 0,
    mesh: Optional[jax.sharding.Mesh] = None,
    true_len: Optional[jax.Array] = None,
) -> jax.Array:
    """Pallas flash attention on (B, S, H, D) tensors.

    Forward only (module docstring), k and v may have fewer heads than q
    — ``(B, Sk, Hkv, D)`` with ``H % Hkv == 0``, query head ``h`` reading KV
    head ``h // (H // Hkv)`` — v a width of its own, ``(B, Sk, Hkv, Dv)``
    for an output ``(B, S, H, Dv)``, and ``true_len`` (int32 scalar) may say
    that only the first ``true_len`` rows are real (a right-padded prompt):
    the rows at or past it come out unspecified — zeros where a whole
    query block lies past it, which is then not computed.

    Products take their operands in the dtype of ``q`` / ``k`` / ``v`` and
    accumulate in float32 (module docstring). ``block_q`` / ``block_k`` left
    at ``None`` follow the sequence lengths (``_default_block``).

    ``interpret=None`` auto-selects: compiled kernel on TPU, interpret mode
    elsewhere (so the same code path is testable on CPU). ``window=W > 0``
    is causal sliding-window (local) attention: each query sees its W most
    recent positions; whole key blocks outside the band are skipped, so
    compute scales with S*W instead of S^2. ``sinks=N`` keeps the first N
    positions visible to every query (StreamingLLM attention sinks; the
    block-skip optimization is disabled since early blocks stay live).
    Falls back to ``attention_reference`` for shapes the kernel cannot
    tile, warning once per shape.

    ``mesh``: the mesh of the enclosing partitioned program, when there is
    one. With more than one device the kernel runs per shard under
    ``shard_map`` — batch over the data-parallel axes, heads over
    ``"model"`` (the layout GSPMD gives q/k/v) — instead of meeting the
    partitioner as an unsplittable custom call.
    """
    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    if window and not causal:
        raise ValueError("window attention requires causal=True")
    if window < 0:
        raise ValueError(f"window must be >= 0, got {window}")
    if sinks and not window:
        raise ValueError("sinks only apply with a sliding window")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    heads, kv_heads = q.shape[2], k.shape[2]
    if k.shape[-1] != q.shape[-1] or v.shape[:3] != k.shape[:3] or heads % kv_heads:
        raise ValueError(
            f"flash_attention: q {q.shape} / k {k.shape} / v {v.shape}: k takes "
            "q's head width, v k's rows and heads, and the KV heads divide q's"
        )
    forward_only = (
        true_len is not None or v.shape[-1] != q.shape[-1] or kv_heads != heads
    )
    if forward_only and mesh is not None and mesh.size > 1:
        raise ValueError(
            "flash_attention: the forward-only kernel (unequal widths, grouped "
            "KV heads, true_len) runs on one device, not under a mesh"
        )
    seq_q, seq_k = q.shape[1], k.shape[1]
    if block_q is None:
        block_q = _default_block(seq_q)
    if block_k is None:
        block_k = _default_block(seq_k)
    bq, bk = min(block_q, seq_q), min(block_k, seq_k)
    # TPU tiling wants the blocks' second-minor dim 8-aligned (the kernel's
    # own lse row is padded to 8 lanes for the same reason); a clipped
    # block like bq=65 (ViT's n_patches+1) would otherwise reach Mosaic
    # unaligned. Interpret mode doesn't tile, but keep ONE rule so CPU
    # tests exercise the same path selection as TPU.
    if seq_q % bq or seq_k % bk:
        rejected = f"sequence lengths not divisible by blocks ({bq}, {bk})"
    elif causal and seq_q != seq_k:
        rejected = "causal kernel needs Sq == Sk"
    elif bq % 8 or bk % 8:
        rejected = f"blocks ({bq}, {bk}) not 8-aligned"
    else:
        rejected = None
    if rejected:
        _warn_once(
            ("reference", q.shape, k.shape, causal),
            f"flash_attention: q {q.shape} / k {k.shape} causal={causal} "
            f"runs attention_reference, not the Pallas kernel: {rejected}",
        )
        if kv_heads != heads:
            k, v = (jnp.repeat(x, heads // kv_heads, axis=2) for x in (k, v))
        return attention_reference(
            q, k, v, causal=causal, sm_scale=sm_scale, window=int(window),
            sinks=int(sinks),
        )
    if forward_only:
        return _flash_forward_only(
            q, k, v, true_len, causal, sm_scale, block_q, block_k, interpret,
            int(window), int(sinks),
        )

    def kernel(q, k, v):
        return _flash(
            q, k, v, causal, sm_scale, block_q, block_k, interpret,
            int(window), int(sinks),
        )

    if mesh is None or mesh.size == 1:
        return kernel(q, k, v)
    # Shared (B, S, H, D) spec policy with the ring/zigzag wrappers.
    from ray_lightning_tpu.ops.zigzag_attention import _seq_specs

    spec, vary = _seq_specs(mesh, None, q.shape[2])
    dp = 1
    for ax in vary:
        if ax != "model":
            dp *= mesh.shape[ax]
    if q.shape[0] % dp:
        # Every device then attends over the whole batch: correct, but
        # the work is replicated instead of split.
        _warn_once(
            ("replicated", q.shape, dp),
            f"flash_attention: batch {q.shape[0]} does not divide the "
            f"{dp} data-parallel shards of the mesh; the kernel runs on "
            "the full batch on every device",
        )
        spec = jax.sharding.PartitionSpec(None, *spec[1:])
    # check_vma=False: pallas_call's outputs carry no varying-axes type.
    return jax.shard_map(
        kernel, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )(q, k, v)
