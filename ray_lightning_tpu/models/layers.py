"""The parts of a decoder layer that every block is built from.

``models/gpt.py`` (layers all alike) and ``models/mixed.py`` (layers that
differ in kind) both take their norms, the rotary table and rotation, the
head, a decode step's write into a stacked cache and the choice of the
read that follows from here; this module imports neither of them, so the
arrows between the three run one way: ``layers.py <- gpt.py -> mixed.py``,
``layers.py <- mixed.py``.

A configuration arrives as any object with ``GPTConfig``'s fields
(``norm_impl``, ``norm_eps``, ``attn_impl``, ``attn_sink_logit``).
"""
from __future__ import annotations

from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_lightning_tpu.utils.quantize import dequant


def _layernorm(
    x: jax.Array, g: jax.Array, b: jax.Array, eps: float = 1e-5
) -> jax.Array:
    x32 = x.astype(jnp.float32)
    mu = x32.mean(-1, keepdims=True)
    var = x32.var(-1, keepdims=True)
    return ((x32 - mu) * jax.lax.rsqrt(var + eps) * g + b).astype(x.dtype)


def _rmsnorm(x: jax.Array, g: jax.Array, eps: float = 1e-5) -> jax.Array:
    x32 = x.astype(jnp.float32)
    ms = jnp.mean(x32 * x32, -1, keepdims=True)
    return (x32 * jax.lax.rsqrt(ms + eps) * g).astype(x.dtype)


def _make_norm(cfg: Any) -> Callable[[jax.Array, jax.Array, Any], jax.Array]:
    """The block-norm function for the config: ``fn(x, g, b)``. RMSNorm
    ignores the bias leaf (kept in the tree so the layout is uniform)."""
    if cfg.norm_impl == "rmsnorm":
        return lambda x, g, b: _rmsnorm(x, g, cfg.norm_eps)
    return lambda x, g, b: _layernorm(x, g, b, cfg.norm_eps)


def _lm_head(h: jax.Array, wte: jax.Array) -> jax.Array:
    """Tied LM head: ``(..., D) x (V, D) -> (..., V)`` logits.

    Operands stay in the hidden states' compute dtype — TPU matmul units
    consume bf16 anyway, and fp32 operands only double the HBM read
    traffic on the V-by-D table (which also bounds per-token decode) —
    while ``preferred_element_type`` keeps accumulation/logits in fp32.
    The single definition keeps the dense, chunked, and decode heads on
    one precision scheme (their grad/value equality is asserted in
    tests/test_gpt.py).
    """
    return jnp.einsum(
        "...d,vd->...v",
        h,
        dequant(wte, h.dtype),
        preferred_element_type=jnp.float32,
    )


def _rope_tables(
    pos: jax.Array, theta: float, head_dim: int
) -> Tuple[jax.Array, jax.Array]:
    """cos/sin tables (S, hd/2) for explicit positions (S,) (any leading
    shape of positions gives tables of that shape + (hd/2,)).

    Positions are passed (not implied by index) so permuted layouts —
    zigzag sequence parallelism — rotate by the TRUE token position.
    Computed ONCE per forward and closed over by the layer scan: the trig
    is position-only, recomputing it per layer (and again under remat)
    would be pure waste at long context.
    """
    half = head_dim // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[..., None] * freqs  # (S, half)
    return jnp.cos(ang), jnp.sin(ang)


def _rope(x: jax.Array, tables: Tuple[jax.Array, jax.Array], interleave: bool = False) -> jax.Array:
    """Rotate the first ``2 * half`` dims of x (..., H, d) by position
    (half-split pairs ``(i, i + half)``, NeoX-style); the dims after them
    pass. The tables (..., half) are :func:`_rope_tables`' of the rows'
    positions and line up with x's axes before its heads from the right:
    (S, half) for rows (B, S, H, d) that share their positions, (B, half)
    for one row a slot (B, H, d), (B, S, half) where every row has its
    own. Float32 compute, x.dtype out. With ``interleave`` pair ``i`` is
    the neighbours ``(2i, 2i + 1)``, and the rotated dims come out
    half-split (every first member, then every second): a permutation that
    queries and keys share, so no score sees it."""
    cos, sin = tables
    half = cos.shape[-1]
    cos, sin = cos[..., None, :], sin[..., None, :]
    x32 = x.astype(jnp.float32)
    rest = x32[..., 2 * half:]
    if interleave:
        pairs = x32[..., :2 * half].reshape(x.shape[:-1] + (half, 2))
        x1, x2 = pairs[..., 0], pairs[..., 1]
    else:
        x1, x2 = x32[..., :half], x32[..., half:2 * half]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos, rest], axis=-1
    ).astype(x.dtype)


def _write_cache_rows(
    cache: jax.Array, li: int, new: jax.Array, pos: jax.Array
) -> jax.Array:
    """Write layer ``li``'s new rows ``new`` into the stacked cache at
    ``[li, b, pos[b]]`` and return the cache: ``new`` (B, Hkv, hd) into a
    cache of (L, B, S, Hkv, hd), or (B, Hkv * hd) into one of
    (L, B, S, Hkv * hd). B rows move, everything else stays where it
    lies, so a caller that donates the cache (or carries it through a
    scan) has it updated in place.

    A position past the end lands on the last row, ``S - 1``, as a
    ``dynamic_update_slice`` clamps its start; the scatter used here would
    drop such a row, so the clamp is explicit. Frozen slots and
    ``gpt_decode_step_paged`` rely on it.
    """
    B, S = new.shape[0], cache.shape[2]
    return cache.at[li, jnp.arange(B), jnp.clip(pos, 0, S - 1)].set(
        new,
        indices_are_sorted=True,
        unique_indices=True,
        mode="promise_in_bounds",
    )


def decode_rows_block(
    cfg: Any,
    q_len: int,
    k_cache: Any,
    v_cache: Any,
    kind: Optional[str] = None,
    backend: Optional[str] = None,
) -> int:
    """Which read a cached attention takes, from what it can observe: the
    rows of a block of the decode kernel (``ops/decode_attention.py``), or
    0 for the XLA read. The kernel wants a stacked cache of rows
    ``(L, B, S, Hkv * hd)``, one query row a slot, ``attn_impl="flash"``,
    a TPU (elsewhere it would run interpreted: the engine's token-identity
    tests compare two XLA reads in one order of sums) and shapes Mosaic
    takes (``decode_block``: row widths a multiple of 128, a block that
    divides S).

    The caches being the dicts by kind of mixed layers (models/mixed.py),
    ``kind`` names the one asked about, and the answer is that kind's:

    - ``"latent"``: the pair of latents ``(L, B, S, rank)`` and rotary
      keys ``(L, B, S, rope)`` (``latent_decode_attention``);
    - ``"full"``: K rows ``(L, B, S, Hkv * qk)`` and V rows ``(L, B, S,
      Hkv * v)``, row ``r`` position ``r`` (``decode_attention``; the two
      widths may differ) — unless a learnable sink logit joins that kind's
      softmax (``cfg.attn_sink_logit``), which the kernel's sums do not
      know;
    - ``"window"``: 0. Its rows are a ring (row ``pos mod R``), not
      positions ``0 .. pos``, and ``R`` rows a slot are all there is to
      read (models/mixed.py:_attend_cache);
    - a kind the model has no layer of, or one with no rows: 0.

    The one place this is decided: the layers ask here as they are traced,
    and ``models/gpt.py:decode_reads`` for the engine's counters."""
    from ray_lightning_tpu.ops.decode_attention import decode_block

    if isinstance(k_cache, dict):
        if kind not in k_cache or kind not in ("full", "latent") or kind in cfg.attn_sink_logit:
            return 0
        k_cache, v_cache = k_cache[kind], v_cache[kind]
    if (
        k_cache.ndim != 4
        or q_len != 1
        or cfg.attn_impl != "flash"
        or (backend or jax.default_backend()) != "tpu"
    ):
        return 0
    return decode_block(
        k_cache.shape[2], k_cache.shape[3], v_cache.shape[3], latent=kind == "latent"
    )
