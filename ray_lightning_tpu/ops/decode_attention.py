"""Decode attention over a cache of rows: a Pallas (Mosaic) kernel that
visits only the row blocks that hold a live position.

A decode token step attends ONE query row a slot against that slot's
cached positions ``0 .. pos``. The cache is allocated for the longest
request a slot may hold — ``(L, slots, S, Hkv * hd)``, a position's KV
heads side by side in one row (``models/gpt.py:_attend_layer_cache`` says
why rows) — and the plain XLA read multiplies against all ``S`` rows of
every slot and masks afterwards: at 64 slots x 2048 rows with 5.5k live
positions, 96% of the bytes read belong to no request (PERF.md §5).

The kernel is one program that walks the live slots. The slots' positions
reach it as prefetched scalars (-1 for a slot that is not live); the cache
stays in HBM and the kernel copies, block by block, the rows ``0 .. pos``
of each live slot into two VMEM buffers by turns: block ``i + 1`` — or,
after a slot's last block, the next live slot's first — is in flight
while block ``i`` is computed. A block past a slot's position is neither
fetched nor computed; a slot that is not live costs one scalar compare,
and its output is zeros. (A grid over (slot, row block) with clamped index
maps does the same with less code and was measured first: its steps cost
about 0.25 us each whether or not they fetch, 2.6x this form's time at a
quarter of the slots live; PERF.md §6, PR 33.)

The stacked cache goes in WHOLE, with the layer in the copies' source: a
slice ``cache[li]`` handed to a custom call is a copy of the layer a step
(a custom call fuses with nothing; PERF.md §6, PR 26).

Arithmetic, per element, is the XLA read's: q scaled before the product,
cache rows upcast to float32 in VMEM (exact), float32 scores, exact
``-inf`` masking by :func:`band_allowed`, float32 p for p·V, default
matmul precision. Only the order of the softmax's sums differs (blockwise
running max / sum / accumulator, as the flash kernel's against
``attention_reference``). Every query head is laid out over a whole row
with zeros under the other KV heads' dims, so one matmul a block gives all
heads' scores, and of p·V's ``(H, Hkv * hd_v)`` each head keeps its own KV
head's block: every added term is an exact zero, and the read is bound by
the cache's bytes either way. Widths come from the arguments' shapes, K's
and V's separately.

**Three callers, one walk** (:func:`_walk`: the slots, the blocks, the
copies in flight, the softmax's running sums). :func:`decode_attention`
reads K and V rows of ``Hkv`` heads side by side, as above: the dense
engine's uniform cache (``models/gpt.py:_attend_layer_cache``), and a mixed
configuration's full kind (``models/mixed.py:_attention_part``), whose K
rows and V rows differ in width — 4 KV heads of 192 against 128 are rows
of 768 and 512; a head of 192, one and a half lane tiles, is laid over
the row by the same concatenation along the lanes, which Mosaic takes
(``tests/test_latent_step_v5e.py``). A window kind's ring (row ``pos mod
R``) and a kind whose softmax a learnable sink logit joins keep the XLA
read (``models/layers.py:decode_rows_block`` says which, kind by kind).
:func:`latent_decode_attention` reads a latent layer's pair
(``models/mixed.py:_latent_part``'s absorbed decode): one latent row a
position, ``(L, B, S, rank)``, is the keys AND the values of every head, so
a block of latents is copied into VMEM and upcast once and both products
multiply that copy — ``s = (q_lat · c^T + q_rope · k_r) scale``, then
``p · c`` — where the XLA read takes two matmuls over all allocated rows and
reads every latent twice. All heads are query rows of one matmul; there is
no head map. The rotary keys the heads share come with the positions
minor, ``(L, B, rope, S)``: 64 of them are half a lane tile, Mosaic refuses
a 64-wide slice of rows, and the chip's compiler keeps the cache's
``(L, B, S, 64)`` with the positions minor anyway, so the caller's
``swapaxes`` is those bytes and no copy (PERF.md §6, PR 39;
``tests/test_latent_step_v5e.py``); a block of them is ``(rope, block)`` as
it lies, and its product needs no transpose.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_lightning_tpu.ops.attention import band_allowed

_NEG_INF = float("-inf")

#: Elements of one K or V block: 256 rows of 1024 (512 KiB in bf16, of
#: which the kernel holds four, and two float32 copies while it computes).
_BLOCK_ELEMS = 256 * 1024


def decode_block(seq: int, k_width: int, v_width: int, latent: bool = False) -> int:
    """Rows of a K / V block for a cache of ``seq`` rows of ``k_width`` /
    ``v_width`` elements, or 0 where the kernel takes no such cache: a row
    width that is not a multiple of the 128 lanes, rows so wide that 128
    of them outgrow a block, no candidate dividing ``seq``. ``latent``:
    the pair is a latent layer's — latents of ``k_width`` a row and the
    shared rotary keys of ``v_width``, which the kernel takes with the
    positions minor, so they may be half a lane tile of sublanes.

    The largest of 512, 256 and 128 rows whose wider block stays within
    ``_BLOCK_ELEMS``: the narrower the rows, the more rows a block, since a
    block's step costs about 0.35 us beside its bytes, which two buffers do
    not hide, while a larger block reads more dead rows behind a short
    request. From the chip, us a layer's call:

    - rows 1024 wide (4,096 B a position in K and V: the chat cell, PERF.md
      §6, PR 33; 64 slots x 2048, a quarter / half / all of the slots
      live): 128 rows 46 / 228 / 741, **256: 49 / 232 / 718**, 512: 58 /
      248 / 718, 1024: 98 / 280 / 719;
    - K rows 768 and V rows 512 wide (2,560 B: the mixedlen cell's full
      layers, PR 46; 64 slots x 5120, a quarter at the mix's lengths /
      half / all): 128 rows 175 / 471 / 1,602, **256: 130 / 340 / 1,133**,
      512: 135 / 349 / 1,119 — the XLA read 1,169 whatever is live;
    - K and V rows 256 wide (1,024 B: the shortchat cell's one attention
      layer, PR 46; 128 slots x 2048): 128 rows 85 / 294 / 985, 256: 59 /
      181 / 565, **512: 47 / 134 / 381** — the XLA read 399;
    - a latent pair (1,152 B: the docqa cell, PR 39; 64 slots x 6656 x
      (512 + 64)): 128 rows 226 / 489 / 1,648, 256: 157 / 331 / 1,081,
      **512: 124 / 253 / 785**."""
    width = max(k_width, v_width)
    if k_width % 128 or v_width % (64 if latent else 128):
        return 0
    for block in (512, 256, 128):
        if seq % block == 0 and block * width <= _BLOCK_ELEMS:
            return block
    return 0


def _last_block(pos, block: int, seq: int):
    """The block that holds position ``pos`` (clamped into the cache): the
    last one a live slot's walk fetches and computes. Its own function so
    that a test can plant the fault."""
    return jnp.minimum(jnp.maximum(pos, 0), seq - 1) // block


def _walk(
    pos_ref, next_ref, o_ref, *, block: int, seq: int, window: int, sinks: int,
    acc_width: int, copies, score_copies: int, query, scores, values, finish,
):
    """The walk both kernels share: over the slots, over a live slot's row
    blocks ``0 .. _last_block(pos)``, block ``i + 1`` — or the next live
    slot's first — in flight while block ``i`` is computed, a blockwise
    running max / sum / accumulator of the softmax. What differs is what a
    block is, told by the caller: ``copies(b, i, buf)`` the async copies of
    slot ``b``'s block ``i`` into buffer ``buf``; ``query(b)`` the slot's
    query operands; ``scores(q, buf)`` the float32 (H, block) scores of
    the block, read from the first ``score_copies`` of its copies (the
    others are waited for only before ``values``), and whatever ``values``
    wants kept; ``values(p, kept, buf)`` p · V, (H, acc_width);
    ``finish(b, acc, l)`` writes the slot's output."""
    B, H = o_ref.shape[:2]

    def fetch(b, i, buf):
        for c in copies(b, i, buf):
            c.start()

    o_ref[...] = jnp.zeros(o_ref.shape, jnp.float32)

    @pl.when(next_ref[0] >= 0)
    def _():
        fetch(next_ref[0], 0, 0)

    def slot(b, done):
        # ``done``: blocks computed so far, whose parity names the buffer
        # this slot's first block was fetched into.
        pos = pos_ref[b]
        n = jnp.where(pos >= 0, _last_block(pos, block, seq) + 1, 0)

        @pl.when(pos >= 0)
        def _():
            q = query(b)
            after = next_ref[b + 1]

            def step(i, carry):
                m_prev, l_prev, acc_prev = carry
                buf = jax.lax.rem(done + i, 2)

                @pl.when(i + 1 < n)
                def _():
                    fetch(b, i + 1, 1 - buf)

                @pl.when((i + 1 == n) & (after >= 0))
                def _():
                    fetch(after, 0, 1 - buf)

                cps = copies(b, i, buf)
                for c in cps[:score_copies]:
                    c.wait()
                s, kept = scores(q, buf)
                col = i * block + jax.lax.broadcasted_iota(
                    jnp.int32, s.shape, 1
                )
                s = jnp.where(
                    band_allowed(pos, col, window, sinks), s, _NEG_INF
                )
                m_new = jnp.maximum(
                    m_prev, jnp.max(s, axis=-1, keepdims=True)
                )
                # -inf - -inf = nan: with a window a visited block can lie
                # wholly before the band (the flash kernel's guard).
                alpha = jnp.where(
                    m_prev == _NEG_INF, 0.0, jnp.exp(m_prev - m_new)
                )
                p = jnp.where(s == _NEG_INF, 0.0, jnp.exp(s - m_new))
                l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
                for c in cps[score_copies:]:
                    c.wait()
                acc_new = acc_prev * alpha + values(p, kept, buf)
                return m_new, l_new, acc_new

            _, l, acc = jax.lax.fori_loop(0, n, step, (
                jnp.full((H, 1), _NEG_INF, jnp.float32),
                jnp.zeros((H, 1), jnp.float32),
                jnp.zeros((H, acc_width), jnp.float32),
            ))
            finish(b, acc, l)

        return done + n

    jax.lax.fori_loop(0, B, slot, jnp.int32(0))


def _block_copies(sources, sem, layer: int, block: int):
    """``copies(b, i, buf)`` of :func:`_walk`: positions ``i * block ..`` of
    slot ``b`` of layer ``layer``, out of each stacked cache where it lies
    in HBM, into its buffer ``buf``; semaphore ``sem[which cache, buf]``.
    ``sources``: ``(cache, buffers, lanes)`` each — the positions are the
    cache's rows, ``(L, B, S, width)`` into ``(2, block, width)``, or with
    ``lanes`` its lanes, ``(L, B, width, S)`` into ``(2, width, block)``."""
    def copies(b, i, buf):
        at = pl.ds(i * block, block)
        return tuple(
            pltpu.make_async_copy(
                src.at[layer, b, :, at] if lanes else src.at[layer, b, at],
                dst.at[buf], sem.at[j, buf],
            )
            for j, (src, dst, lanes) in enumerate(sources)
        )

    return copies


def _kernel(
    pos_ref, next_ref,  # prefetched scalars: (B,) and (B + 1,) int32
    q_ref, k_hbm, v_hbm, o_ref,
    k_buf, v_buf, sem,
    *, layer: int, block: int, rep: int, window: int, sinks: int,
):
    """K and V rows of ``Hkv`` heads side by side. ``q_ref`` (B, H, hd),
    every slot's query heads; ``k_hbm`` / ``v_hbm`` the stacked caches
    where they lie; ``o_ref`` (B, H, hd_v) float32; ``k_buf`` / ``v_buf``
    (2, block, width) with one copy semaphore each a buffer.
    ``pos_ref[b]`` is -1 for a slot that is not live; ``next_ref[b]`` is
    the first live slot at or after ``b``, -1 for none (``next_ref[B]`` is
    -1)."""
    B, H, hd = q_ref.shape
    hd_v = o_ref.shape[2]
    k_width, v_width = k_buf.shape[2], v_buf.shape[2]
    n_kv = k_width // hd

    def own(width: int, d: int):
        # (H, width) bool: the dims of head h's own KV head, h // rep
        shape = (H, width)
        head = jax.lax.div(
            jax.lax.broadcasted_iota(jnp.int32, shape, 0), jnp.int32(rep)
        )
        col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        return (col >= head * d) & (col < (head + 1) * d)

    own_k, own_v = own(k_width, hd), own(v_width, hd_v)

    def query(b):
        q = q_ref[b].astype(jnp.float32) * (1.0 / (hd ** 0.5))
        return jnp.where(own_k, jnp.concatenate([q] * n_kv, axis=1), 0.0)

    def scores(q_rows, buf):
        return jax.lax.dot_general(
            q_rows, k_buf[buf].astype(jnp.float32),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ), None  # (H, block)

    def values(p, _, buf):
        return jax.lax.dot_general(
            p, v_buf[buf].astype(jnp.float32),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    def finish(b, acc, l):
        o = jnp.where(own_v, acc, 0.0) / l
        out = o[:, :hd_v]
        for g in range(1, v_width // hd_v):
            out = out + o[:, g * hd_v:(g + 1) * hd_v]
        o_ref[b] = out

    _walk(
        pos_ref, next_ref, o_ref, block=block, seq=k_hbm.shape[2],
        window=window, sinks=sinks, acc_width=v_width,
        copies=_block_copies(((k_hbm, k_buf, False), (v_hbm, v_buf, False)), sem, layer, block),
        score_copies=1, query=query, scores=scores, values=values, finish=finish,
    )


def _latent_kernel(
    pos_ref, next_ref,
    ql_ref, qr_ref, c_hbm, r_hbm, o_ref,
    c_buf, r_buf, sem,
    *, layer: int, block: int, scale: float,
):
    """One latent row a position, the keys AND the values of every head:
    ``ql_ref`` (B, H, rank) the queries taken into the latent, ``qr_ref``
    (B, H, rope) their rotary parts, ``c_hbm`` the stacked latents, a
    position a row, ``r_hbm`` the shared rotary keys, the positions minor:
    ``(L, B, rope, S)``, so ``r_buf`` is (2, rope, block); ``o_ref``
    (B, H, rank) float32, the weighted latents. A latent block is copied into VMEM and upcast once:
    the scores and ``p · c`` both multiply that copy. Every head is a
    query row of one matmul against the block, so there is no head map."""

    def query(b):
        return (
            ql_ref[b].astype(jnp.float32) * scale,
            qr_ref[b].astype(jnp.float32) * scale,
        )

    def scores(q, buf):
        c = c_buf[buf].astype(jnp.float32)
        s = jax.lax.dot_general(
            q[0], c, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) + jax.lax.dot_general(
            q[1], r_buf[buf].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return s, c

    def values(p, c, buf):
        return jax.lax.dot_general(
            p, c, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    def finish(b, acc, l):
        o_ref[b] = acc / l

    _walk(
        pos_ref, next_ref, o_ref, block=block, seq=c_hbm.shape[2],
        window=0, sinks=0, acc_width=c_buf.shape[2],
        copies=_block_copies(((c_hbm, c_buf, False), (r_hbm, r_buf, True)), sem, layer, block),
        score_copies=2, query=query, scores=scores, values=values, finish=finish,
    )


def _call(kernel, queries, caches, out_width: int, pos, live, block: int, interpret):
    """One program over all slots: the live slots' positions and the
    next-live links as prefetched scalars, the queries whole in VMEM, the
    stacked caches left in HBM, two VMEM buffers a cache. ``caches``:
    ``(array, lanes)`` each, as :func:`_block_copies` reads them."""
    B, H = queries[0].shape[:2]
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if live is None:
        live = jnp.ones((B,), jnp.bool_)
    pos_live = jnp.where(live, pos.astype(jnp.int32), -1)
    # the first live slot at or after b, -1 for none; entry B closes it
    slots = jnp.arange(B, dtype=jnp.int32)
    at_or_after = jax.lax.cummin(jnp.where(live, slots, B), reverse=True)
    next_live = jnp.concatenate([
        jnp.where(at_or_after < B, at_or_after, -1),
        jnp.full((1,), -1, jnp.int32),
    ])
    widths = [c.shape[2] if lanes else c.shape[3] for c, lanes in caches]
    itemsize = max(c.dtype.itemsize for c, _ in caches)
    # two buffers a cache, their float32 copies, the queries and the output
    # (twice: the pipeline's own two buffers), the accumulator and tiles
    vmem = (
        (2 * itemsize + 4) * block * sum(widths)
        + 2 * B * H * (sum(q.shape[2] * q.dtype.itemsize for q in queries) + out_width * 4)
        + 8 * H * max(widths + [block]) * 4
    )

    def whole(shape):
        return pl.BlockSpec(shape, lambda i, *_: (0,) * len(shape))

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(1,),
            in_specs=[whole(q.shape) for q in queries]
            + [pl.BlockSpec(memory_space=pl.ANY) for _ in caches],
            out_specs=whole((B, H, out_width)),
            scratch_shapes=[
                pltpu.VMEM((2, w, block) if lanes else (2, block, w), c.dtype)
                for (c, lanes), w in zip(caches, widths)
            ] + [pltpu.SemaphoreType.DMA((len(caches), 2))],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, out_width), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=max(16 * 2**20, min(2 * vmem, 100 * 2**20)),
        ),
        interpret=interpret,
        name="decode_attention",
    )(pos_live, next_live, *queries, *(c for c, _ in caches))


def _checked_block(block, seq: int, widths, latent: bool) -> int:
    if block is None:
        block = decode_block(seq, *widths, latent=latent)
    if not block or seq % block:
        raise ValueError(
            f"decode_attention: no row block for a cache of {seq} rows of "
            f"{widths[0]} / {widths[1]} (block {block})"
        )
    return block


def decode_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    layer: int,
    pos: jax.Array,
    live: Optional[jax.Array] = None,
    *,
    window: int = 0,
    sinks: int = 0,
    block: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Attention of one query row a slot against layer ``layer`` of a
    stacked cache of rows, after that layer's write.

    ``q`` (B, H, hd); ``k_cache`` (L, B, S, Hkv * hd) and ``v_cache``
    (L, B, S, Hkv * hd_v), whole; ``pos`` (B,) int32, the position each
    slot's query stands on (it sees ``0 .. pos``, band-limited by
    ``window`` / ``sinks``); ``live`` (B,) bool, default all. Returns
    float32 (B, H, hd_v); zeros for a slot that is not live.

    ``block`` left at ``None`` follows the shapes (:func:`decode_block`);
    ``interpret=None`` compiles on a TPU and interprets elsewhere. A
    window shorter than the cache is masked, not skipped: blocks before
    the band are still visited.
    """
    B, H, hd = q.shape
    _, _, S, k_width = k_cache.shape
    v_width = v_cache.shape[3]
    n_kv = k_width // hd
    block = _checked_block(block, S, (k_width, v_width), latent=False)
    return _call(
        functools.partial(
            _kernel, layer=layer, block=block, rep=H // n_kv,
            window=int(window), sinks=int(sinks),
        ),
        (q,), ((k_cache, False), (v_cache, False)), v_width // n_kv, pos, live, block, interpret,
    )


def latent_decode_attention(
    q_lat: jax.Array,
    q_rope: jax.Array,
    c_cache: jax.Array,
    r_cache: jax.Array,
    layer: int,
    pos: jax.Array,
    live: Optional[jax.Array] = None,
    *,
    scale: float,
    block: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """The absorbed decode attention of a latent layer
    (``models/mixed.py:_latent_part``) against layer ``layer`` of the
    stacked latents and rotary keys, after that layer's write.

    ``q_lat`` (B, H, rank), each head's query taken into the latent;
    ``q_rope`` (B, H, rope), its rotated part; ``c_cache`` (L, B, S, rank)
    and ``r_cache`` (L, B, rope, S), whole; ``pos`` / ``live`` as
    :func:`decode_attention`'s; ``scale`` multiplies the scores. Returns
    float32 (B, H, rank): ``softmax((q_lat · c + q_rope · k_r) scale) · c``
    over the slot's positions ``0 .. pos``; zeros for a slot not live.
    """
    S, rank = c_cache.shape[2:]
    block = _checked_block(block, S, (rank, r_cache.shape[2]), latent=True)
    return _call(
        functools.partial(_latent_kernel, layer=layer, block=block, scale=float(scale)),
        (q_lat, q_rope), ((c_cache, False), (r_cache, True)), rank, pos, live, block, interpret,
    )
