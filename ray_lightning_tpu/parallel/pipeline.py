"""SPMD pipeline parallelism (GPipe-style) over a "pp" mesh axis.

Beyond-parity capability (the reference's parallelism surface is DP-only,
SURVEY.md §2c). The stacked-layer parameter tree (every block leaf carries a
leading ``layers`` dim) is sharded over the "pp" axis, so each pipeline rank
holds L/P consecutive layers. Under ``shard_map`` (manual over "pp" only —
data/model/ep axes stay under GSPMD), every rank runs the same per-tick
program:

    tick t: rank 0 feeds microbatch t; every rank applies its local layers
    to its current activation; activations hop one rank down the pipeline
    via ``ppermute`` (ICI neighbor exchange).

After M + P - 1 ticks all M microbatches have drained; the last rank's
collected outputs are broadcast with a masked ``psum``. Built entirely from
``lax.scan`` + ``ppermute`` so the backward pass is the reverse pipeline
schedule by transposition — no hand-written backward needed.

The bubble fraction is the textbook (P-1)/(M+P-1); raise
``num_microbatches`` to amortize it. Fill/drain ticks where a rank holds
no real microbatch SKIP the layer compute via a per-rank ``lax.cond``
(the predicate is uniform across the model/data groups sharing a pp
stage, so GSPMD collectives inside the stage stay coherent) — the bubble
costs idle time, not redundant FLOPs.
"""
from __future__ import annotations

from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def bubble_fraction(pp: int, num_microbatches: Optional[int] = None) -> float:
    """Textbook GPipe bubble: the share of the M+P-1 schedule ticks a rank
    spends without a real microbatch, (P-1)/(M+P-1)."""
    m = int(num_microbatches or pp)
    return (pp - 1) / (m + pp - 1)


def pipeline_apply(
    stage_fn: Callable[[Any, jax.Array], Any],
    stacked_params: Any,
    x: jax.Array,
    mesh: Mesh,
    axis_name: str = "pp",
    num_microbatches: Optional[int] = None,
    with_aux: bool = False,
) -> Any:
    """Run ``x`` through L stacked layers pipelined over ``axis_name``.

    Args:
      stage_fn: applies ONE layer: ``stage_fn(layer_params, h) -> h`` with
        ``h`` (mb, S, D)-like. Scanned over each rank's local layer shard.
        With ``with_aux`` it returns ``(h, aux_scalar)`` instead — the MoE
        load-balancing loss rides this channel.
      stacked_params: pytree whose leaves have leading dim L, sharded
        ``P(axis_name)`` on that dim (the "layers" -> "pp" logical rule).
      x: global activations (B, ...), replicated w.r.t. the pp axis.
      num_microbatches: default P; B must divide by it.
      with_aux: when True, returns ``(activations, aux_total)`` where
        ``aux_total`` sums each layer's mean-over-microbatches aux scalar
        (fp32). Per-microbatch aux means match the unpipelined full-batch
        value exactly when routing statistics are microbatch-independent,
        and in expectation otherwise — the same contract gradient
        accumulation gives batch-statistic losses.

    Returns activations (B, ...) replicated w.r.t. the pp axis, plus the
    aux scalar when ``with_aux``.
    """
    pp = mesh.shape[axis_name]
    M = int(num_microbatches or pp)
    B = x.shape[0]
    if B % M:
        raise ValueError(f"batch {B} not divisible by num_microbatches {M}")

    param_specs = jax.tree_util.tree_map(lambda _: P(axis_name), stacked_params)

    def per_rank(blocks_local: Any, x_full: jax.Array):
        stage = jax.lax.axis_index(axis_name)
        mb = x_full.reshape(M, B // M, *x_full.shape[1:])

        def varying(v):
            # The scan carry genuinely differs per pp rank; mark it so for
            # shard_map's varying-mesh-axes type system.
            return jax.lax.pcast(v, (axis_name,), to="varying")

        def apply_local(h: jax.Array) -> Tuple[jax.Array, jax.Array]:
            def body(carry, lp):
                h, a = carry
                if with_aux:
                    h2, da = stage_fn(lp, h)
                    return (h2, a + da.astype(jnp.float32)), None
                return (stage_fn(lp, h), a), None

            (h, a), _ = jax.lax.scan(
                body, (h, varying(jnp.zeros((), jnp.float32))), blocks_local
            )
            return h, a

        T = M + pp - 1
        perm = [(i, (i + 1) % pp) for i in range(pp)]

        zero = varying(jnp.zeros_like(mb[0]))
        outs0 = varying(jnp.zeros_like(mb))
        aux0 = varying(jnp.zeros((), jnp.float32))

        def tick(carry, t):
            recv, outs, aux_acc = carry
            feed = mb[jnp.clip(t, 0, M - 1)]
            inp = jnp.where(stage == 0, feed, recv)
            # Rank ``stage`` holds microbatch (t - stage) this tick; outside
            # [0, M) it's fill/drain garbage — skip the layer compute so the
            # bubble is idle time, not wasted FLOPs. Devices sharing a pp
            # stage (model/data/ep groups) share the predicate, so
            # collectives inside stage_fn stay coherent across the branch.
            valid = jnp.logical_and(t >= stage, t - stage <= M - 1)
            out, aux = jax.lax.cond(
                valid,
                apply_local,
                lambda h: (h, varying(jnp.zeros((), jnp.float32))),
                inp,
            )
            slot = t - (pp - 1)
            idx = jnp.clip(slot, 0, M - 1)
            collect = jnp.logical_and(stage == pp - 1, slot >= 0)
            outs = outs.at[idx].set(jnp.where(collect, out, outs[idx]))
            nxt = jax.lax.ppermute(out, axis_name, perm)
            return (nxt, outs, aux_acc + aux), None

        (_, outs, aux_local), _ = jax.lax.scan(
            tick, (zero, outs0, aux0), jnp.arange(T)
        )
        # Only the last stage holds real outputs; masked psum replicates
        # them across the pp axis (everyone else contributes zeros).
        outs = jax.lax.psum(
            jnp.where(stage == pp - 1, outs, jnp.zeros_like(outs)), axis_name
        )
        outs = outs.reshape(B, *x_full.shape[1:])
        if not with_aux:
            return outs
        # Every (layer, microbatch) pair contributed aux exactly once across
        # the ranks; the psum totals the layers and /M takes the microbatch
        # mean, matching the unpipelined per-layer full-batch scale.
        aux_total = jax.lax.psum(aux_local, axis_name) / M
        return outs, aux_total

    return shard_map(
        per_rank,
        mesh=mesh,
        in_specs=(param_specs, P()),
        out_specs=(P(), P()) if with_aux else P(),
        axis_names={axis_name},
    )(stacked_params, x)
