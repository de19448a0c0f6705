"""The state layers' decode update over the live slots: a Pallas (Mosaic)
kernel that reads and writes the running state of the slots that hold a
request, in place, and of no other.

A decode token step advances a Mamba-2 layer's state by one row a slot
(``models/ssm.py``): ``S <- exp(dt A) S + (dt x) B^T``, ``y = S C``, with
``S`` a float32 ``(H, P, N)`` a slot — 4.19 MB a layer in both served
configurations (32 x 128 x 256 and 128 x 64 x 128). The state is allocated
for every slot the engine has, and the plain XLA update is one elementwise
fusion over all of them: at 64 slots with 24 live, 62% of the 537 MB a
layer it reads and writes belong to no request (PERF.md §5).

The kernel is one program that walks the live slots, the way
``ops/decode_attention.py:_walk`` walks live rows. The live slots' numbers
reach it as prefetched scalars (the live ones first, and their count); the
state stays in HBM and IS the output (``input_output_aliases``: a donating
caller has it updated where it lies, and nothing is copied). For each live
slot, a block of heads at a time: the block is copied into one of two VMEM
buffers while the block before it is computed, advanced, summed against
``C``, and copied back to where it came from out of one of two others. A
slot that is not live is never named: its state keeps its bits, its ``y``
is zeros.

Arithmetic, per element, is the XLA lines' (``models/ssm.py:_update_all``):
float32 throughout, ``decay * S + (dt x) * B`` with the same two products
and one sum, then ``sum_N(S * C)``. The sum over ``N`` is taken along the
lanes in float32 (VPU adds of the lane tiles, then the XLU's lane
reduction), so only its order may differ from XLA's. ``dt x`` comes one
value a LANE (``(heads, P)`` as XLA leaves it) and the state's tiles want
it one a SUBLANE; ``y`` leaves the lanes' sum one a sublane and goes back
one a lane: Mosaic makes both relayouts (``d[:, :, None]``, ``sum(-1)``)
and they are hidden behind the copies.

**What bounds it, from the chip** (``tools/state_step_check.py``; TPU v5e,
my chip runs, PR 48, calls 1 to 3; us a layer's call; the live slots'
state came out XLA's to the bit in every row, the others kept theirs). A
walk that only copies each live block in and out again takes what the
whole kernel takes: the copies run at **676 GB/s** (8.39 MB a slot in 12.4
us) whatever the block from 0.5 MiB up, with two, three or four buffers
and with a block's copy split in two or four. XLA's fusion, which reads
and writes each tile in place, moves every slot's bytes at 880-900 GB/s
where the tool runs these lines alone, and at 590-620 inside the cells'
token step (870 us a layer in the falcon cell's trace, about 1,750 in
nemotron's; PERF.md §5, §6). Against the tool's fusion the kernel wins
below about three quarters of the slots live; against the cells' own it
wins at any share:

==========================  ====  ==========  =====  =====  =========
shape (slots x H x P x N)   idle  cell        half   full   XLA (any)
==========================  ====  ==========  =====  =====  =========
64 x 32 x 128 x 256, G 2    30    314 (24)    417    825    593
128 x 128 x 64 x 128, G 8   35    1,104 (84)  844    1,676  1,226
==========================  ====  ==========  =====  =====  =========

By block, cell / full (24 / 64 live of falcon's shape; 84 / 128 of
nemotron's): 2 heads 343 / 901 and 2,274 / 3,458; 4 heads 315 / 826 and
1,697 / 2,579; **8 heads (1 MiB) 314 / 825** and 1,290 / 1,959; 16 heads
314 / 823 and **(0.5 MiB) 1,104 / 1,676**: falcon's 128 KiB heads reach
the copies' rate from half a MiB; nemotron's 32 KiB heads pay a head's
fixed work (a scalar read, its slices), which the larger block amortises —
a group has 16 heads in both shapes, and a block does not span groups.
**The reduction**: the lanes' sum and a float32 ``dot`` at highest
precision (``C`` as eight LHS rows against a head's ``(P, N)``) read the
same at falcon's shape (313 / 824 both). At nemotron's, with ``dt x``
re-laid and ``y`` summed head by head, the dot was the faster (1,091 /
1,657 against 1,327 / 2,012); re-laid and summed a block at once, as
below, the lanes' sum is within 2% of the copies' own 1,086 / 1,650. It was
kept: it is float32 adds and nothing else — ``y`` read 0.0 off XLA's at
nemotron's shape and 4.8e-6 at falcon's (of 26), where the dot's six
bfloat16 passes read 5.7e-6 and 5.2e-6.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32

#: Bytes of one block of heads, of which the kernel holds four.
_BLOCK_BYTES = 2**20


def step_heads(H: int, P: int, N: int, G: int) -> int:
    """Heads of a block for a state of ``H`` heads of ``(P, N)`` in ``G``
    groups, or 0 where the kernel takes no such state: ``N`` not a
    multiple of the 128 lanes, ``P`` not of the 8 sublanes, heads that do
    not divide into the groups.

    The largest of 16, 8, 4, 2 and 1 heads that divides a group (a block
    reads one row of B and one of C) and stays within ``_BLOCK_BYTES``: 8
    of falcon's 128 KiB heads, 16 of nemotron's 32 KiB — the module's
    docstring has the chip's timings by block."""
    if N % 128 or P % 8 or H % G:
        return 0
    for heads in (16, 8, 4, 2, 1):
        if (H // G) % heads == 0 and heads * P * N * 4 <= _BLOCK_BYTES:
            return heads
    return 0


def _steps(n_live, blocks: int):
    """Blocks the walk visits: every block of every live slot. Its own
    function so that a test can plant the fault."""
    return n_live * blocks


def _block(ids_ref, t, blocks: int, heads: int):
    """``(slot, first head)`` of the walk's step ``t``: the live slots in
    order, each one's blocks in order."""
    return ids_ref[t // blocks], jax.lax.rem(t, blocks) * heads


def _home(ids_ref, t, blocks: int, heads: int):
    """Where step ``t``'s block is written back: where it came from. Its
    own function so that a test can plant the fault."""
    return _block(ids_ref, t, blocks, heads)


def _advance(s_ref, out_ref, decay, d, bm, cm):
    """One block of heads a row on: ``s_ref`` (heads, P, N) the state as it
    was, ``decay`` a scalar a head, ``d`` (heads, P) its ``dt x``, ``bm``
    and ``cm`` (1, N) the group's B and C. Writes the new state into
    ``out_ref`` and returns ``y`` (heads, P)."""
    dn = d[:, :, None] * bm[None]  # (heads, P, N)
    for j, a in enumerate(decay):
        out_ref[j] = a * s_ref[j] + dn[j]
    return jnp.sum(out_ref[...] * cm[None], axis=-1)


def _kernel(
    ids_ref, n_ref,  # prefetched scalars: (B,) the live slots first, (1,) their count
    decay_ref, dtx_ref, b_ref, c_ref, s_hbm,
    s_out, y_ref,
    in_buf, out_buf, sem,
    *, heads: int,
):
    """``decay_ref`` (B * H,) in SMEM; ``dtx_ref`` (B, H, P), ``b_ref`` and
    ``c_ref`` (B, G, N) whole in VMEM; ``s_hbm`` and ``s_out`` the state
    (B, H, P, N) where it lies, one buffer under two names; ``y_ref`` (B,
    H, P); ``in_buf`` / ``out_buf`` (2, heads, P, N) with one copy
    semaphore each a buffer."""
    H = dtx_ref.shape[1]
    blocks, per_group = H // heads, H // b_ref.shape[1]

    def read(t, k):
        b, h0 = _block(ids_ref, t, blocks, heads)
        return pltpu.make_async_copy(s_hbm.at[b, pl.ds(h0, heads)], in_buf.at[k], sem.at[0, k])

    def write(t, k):
        b, h0 = _home(ids_ref, t, blocks, heads)
        return pltpu.make_async_copy(out_buf.at[k], s_out.at[b, pl.ds(h0, heads)], sem.at[1, k])

    y_ref[...] = jnp.zeros(y_ref.shape, F32)
    total = _steps(n_ref[0], blocks)

    @pl.when(total > 0)
    def _():
        read(0, 0).start()

    def step(t, carry):
        k = jax.lax.rem(t, 2)
        b, h0 = _block(ids_ref, t, blocks, heads)

        @pl.when(t + 1 < total)
        def _():
            read(t + 1, 1 - k).start()

        @pl.when(t >= 2)
        def _():
            write(t - 2, k).wait()  # the copy back that last used this buffer

        read(t, k).wait()
        g = h0 // per_group
        y_ref[b, pl.ds(h0, heads), :] = _advance(
            in_buf.at[k], out_buf.at[k],
            [decay_ref[b * H + h0 + j] for j in range(heads)],
            dtx_ref[b, pl.ds(h0, heads), :], b_ref[b, pl.ds(g, 1), :], c_ref[b, pl.ds(g, 1), :],
        )
        write(t, k).start()
        return carry

    jax.lax.fori_loop(0, total, step, 0)
    for back in (2, 1):  # the last two copies back are still in flight
        @pl.when(total >= back)
        def _(back=back):
            write(total - back, jax.lax.rem(total - back, 2)).wait()


def ssm_step_update(
    state: jax.Array,
    decay: jax.Array,
    dtx: jax.Array,
    bm: jax.Array,
    cm: jax.Array,
    active: jax.Array,
    *,
    heads: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """One row of the recurrence for the slots that are ``active``.

    ``state`` (B, H, P, N) float32, every slot's; ``decay`` (B, H) the
    heads' ``exp(dt A)``; ``dtx`` (B, H, P) their ``dt x``; ``bm`` and
    ``cm`` (B, G, N), head ``h`` reading group ``h // (H / G)``; ``active``
    (B,) bool. Returns ``(state, y)``: the state with ``decay S + dtx B^T``
    in the active slots and every other slot's as it was, bit for bit — in
    the argument's own buffer where the caller donates it — and ``y`` (B,
    H, P) float32, ``S C`` of the new state, zeros for a slot not active.

    ``heads`` left at ``None`` follows the shape (:func:`step_heads`);
    ``interpret=None`` compiles on a TPU and interprets elsewhere.
    """
    B, H, P, N = state.shape
    G = bm.shape[1]
    if heads is None:
        heads = step_heads(H, P, N, G)
    if not heads or H % G or (H // G) % heads:
        raise ValueError(
            f"ssm_step_update: no block of heads for a state of {H} x {P} x {N} in {G} groups (heads {heads})"
        )
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    # the live slots' numbers first, in order: entry j is the slot whose live rank is j
    slots = jnp.arange(B, dtype=jnp.int32)
    rank = jnp.cumsum(active.astype(jnp.int32)) - 1
    ids = jnp.sum(jnp.where(active[:, None] & (rank[:, None] == slots[None]), slots[:, None], 0), axis=0)
    n_live = active.sum().astype(jnp.int32)[None]
    block = heads * P * N * 4
    # four blocks, the small operands and y whole (twice: the pipeline's own two buffers), a block of temporaries
    vmem = 5 * block + 2 * 4 * B * (2 * H * max(P, 128) + 2 * max(G, 8) * N)

    def whole(shape):
        return pl.BlockSpec(shape, lambda i, *_: (0,) * len(shape))

    new_state, y = pl.pallas_call(
        functools.partial(_kernel, heads=heads),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(1,),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                whole((B, H, P)), whole((B, G, N)), whole((B, G, N)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=[pl.BlockSpec(memory_space=pl.ANY), whole((B, H, P))],
            scratch_shapes=[
                pltpu.VMEM((2, heads, P, N), F32), pltpu.VMEM((2, heads, P, N), F32),
                pltpu.SemaphoreType.DMA((2, 2)),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct(state.shape, F32), jax.ShapeDtypeStruct((B, H, P), F32)],
        input_output_aliases={6: 0},  # the state, after the two prefetched scalars and four operands
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=max(16 * 2**20, min(2 * vmem, 100 * 2**20)),
        ),
        interpret=interpret,
        name="ssm_step",
    )(ids, n_live, decay.astype(F32).reshape(B * H), dtx.astype(F32), bm.astype(F32), cm.astype(F32), state)
    return new_state, y
