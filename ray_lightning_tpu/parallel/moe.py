"""Mixture-of-Experts layer with expert parallelism over a mesh axis.

Beyond-parity capability (the reference has no model code at all,
SURVEY.md §2c): a switch-style MoE feed-forward whose expert weights
carry a leading ``experts`` dim annotated with the "expert" logical axis —
mapped by GSPMDStrategy to the "ep" mesh axis, so each ep rank holds
E/ep_size experts and XLA routes tokens between ranks (the all-to-all
pattern) from the shardings alone.

Two dispatch implementations:

- ``moe_ffn`` (default, sort-based): tokens are grouped by expert with one
  stable argsort and moved with gather/scatter-add — O(T·K·D + E·C·D)
  memory, supports top-1 and top-2 routing. Static shapes throughout
  (argsort/scatter are XLA-native), so it jits and shards like any other op.
- ``moe_ffn_held``: the layer of a process that holds a share of the
  experts (``held = (first, count)``): it routes over all of them, groups
  the pairs that land on its own by expert and runs a drop-free grouped
  SwiGLU sized by the counts. Sigmoid or softmax scores. Serving only: the
  loop over the tiles in use has no reverse mode.
- ``moe_ffn_dense``: the original one-hot einsum formulation, O(T·E·C)
  dispatch tensors. Kept as the readable oracle the tests check the sparse
  path against, and as a fallback for tiny expert counts where the dense
  einsum fuses better.

Capacity factoring drops overflow tokens (standard switch behavior) to keep
per-expert compute static; with the stable sort, earlier tokens win expert
slots in both implementations, so top-1 sparse == dense exactly.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map


def init_moe_params(
    rng: jax.Array,
    n_experts: int,
    d_model: int,
    d_ff: int,
    std: float = 0.02,
    res_std: float = 0.02,
    mlp_variant: str = "gelu",
) -> Dict[str, jax.Array]:
    if mlp_variant not in ("gelu", "swiglu"):
        raise ValueError(
            f"unknown mlp_variant {mlp_variant!r}; use 'gelu' or 'swiglu'"
        )
    k1, k2, k3 = jax.random.split(rng, 3)
    if mlp_variant == "swiglu":
        # Mixtral-style experts: gate/up stacked (E, D, 2, F) — same
        # co-sharded packing as the dense decoder's SwiGLU.
        wi = (
            jax.random.normal(k2, (n_experts, d_model, 2, d_ff)) * std
        ).astype(jnp.float32)
        bi = jnp.zeros((n_experts, 2, d_ff))
    else:
        wi = (
            jax.random.normal(k2, (n_experts, d_model, d_ff)) * std
        ).astype(jnp.float32)
        bi = jnp.zeros((n_experts, d_ff))
    return {
        "router": (jax.random.normal(k1, (d_model, n_experts)) * std).astype(
            jnp.float32
        ),
        "wi": wi,
        "bi": bi,
        "wo": (
            jax.random.normal(k3, (n_experts, d_ff, d_model)) * res_std
        ).astype(jnp.float32),
        "bo": jnp.zeros((n_experts, d_model)),
    }


def _route_and_pack(
    tokens: jax.Array, router: jax.Array, top_k: int, capacity: int
) -> Tuple[jax.Array, ...]:
    """Shared routing + sort-based queue packing for the sparse dispatchers.

    tokens (T, D), router (D, E) -> (probs, e_flat, e_s, t_s, g_s, keep,
    pos_c): choice-major flattened assignments (e_flat unsorted, for load
    stats), stable-argsorted by expert (first choices outrank seconds,
    token order within a choice — the dense oracle's priority), with
    per-expert queue positions clipped to ``capacity``. Any routing-rule
    change lives HERE so the in-place (:func:`moe_ffn`) and
    expert-parallel (:func:`moe_ffn_ep`) paths cannot drift apart."""
    T = tokens.shape[0]
    E = router.shape[1]
    K = int(top_k)
    logits = tokens.astype(jnp.float32) @ router  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, K)  # (T, K)
    # Switch top-1 gates with the raw router prob (dense-oracle semantics);
    # top-k>1 renormalizes over the selected experts (GShard).
    gates = (
        top_p
        if K == 1
        else top_p / jnp.clip(top_p.sum(axis=-1, keepdims=True), 1e-9, None)
    )
    e_flat = top_e.T.reshape(-1)  # (K*T,)
    g_flat = gates.T.reshape(-1)
    t_flat = jnp.tile(jnp.arange(T), K)
    order = jnp.argsort(e_flat, stable=True)
    e_s = e_flat[order]
    t_s = t_flat[order]
    g_s = g_flat[order]
    seg_start = jnp.searchsorted(e_s, jnp.arange(E))  # (E,)
    pos = jnp.arange(T * K) - seg_start[e_s]
    keep = pos < capacity
    pos_c = jnp.clip(pos, 0, capacity - 1)
    return probs, e_flat, e_s, t_s, g_s, keep, pos_c


def _expert_ffn(
    expert_in: jax.Array, params: Dict[str, jax.Array], cdt: Any
) -> jax.Array:
    """(E, C, D) expert batches -> (E, C, D). Gelu MLP, or SwiGLU experts
    (Mixtral-style) when ``wi`` carries the stacked gate/up axis
    (E, D, 2, F) — the same (co-sharded) packing the dense decoder uses.
    One definition serves the in-place, expert-parallel, and dense-oracle
    dispatchers."""
    wi = params["wi"]
    if wi.ndim == 4:  # (E, D, 2, F): SwiGLU experts
        z = jnp.einsum(
            "ecd,edgf->ecgf", expert_in, wi.astype(cdt)
        ) + params["bi"][:, None].astype(cdt)
        h = jax.nn.silu(z[..., 0, :]) * z[..., 1, :]
    else:
        h = jax.nn.gelu(
            jnp.einsum("ecd,edf->ecf", expert_in, wi.astype(cdt))
            + params["bi"][:, None, :].astype(cdt)
        )
    return jnp.einsum(
        "ecf,efd->ecd", h, params["wo"].astype(cdt)
    ) + params["bo"][:, None, :].astype(cdt)


def moe_ffn(
    params: Dict[str, jax.Array],
    x: jax.Array,
    capacity_factor: float = 1.25,
    compute_dtype: Any = jnp.float32,
    top_k: int = 1,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Sort-based MoE feed-forward with top-k routing (default top-1).

    x: (B, S, D) -> (B, S, D), plus aux metrics {"aux_loss", "dropped"}.
    ``aux_loss`` is the load-balancing loss of Shazeer et al. (mean expert
    load x mean router prob, scaled by E); add it to the task loss.

    Dispatch memory is O(T·K·D + E·C·D): one stable argsort groups the
    (token, expert) assignments by expert, positions within each expert
    queue come from a searchsorted offset, and tokens move via gather +
    scatter-add — no (T, E, C) one-hot tensors. For ``top_k=2`` every
    first-choice assignment outranks all second choices for capacity
    (GShard-style priority), and gates are renormalized over the kept
    choices' router probabilities.
    """
    B, S, D = x.shape
    E = params["router"].shape[1]
    T = B * S
    K = int(top_k)
    tokens = x.reshape(T, D)
    capacity = max(1, int(capacity_factor * T * K / E))
    probs, e_flat, e_s, t_s, g_s, keep, pos_c = _route_and_pack(
        tokens, params["router"], K, capacity
    )

    cdt = jnp.dtype(compute_dtype)
    keep_f = keep.astype(jnp.float32)[:, None]
    gathered = tokens.astype(jnp.float32)[t_s] * keep_f  # (K*T, D)
    expert_in = (
        jnp.zeros((E, capacity, D), jnp.float32).at[e_s, pos_c].add(gathered)
    ).astype(cdt)
    expert_out = _expert_ffn(expert_in, params, cdt)
    contrib = (
        expert_out.astype(jnp.float32)[e_s, pos_c]
        * (g_s[:, None] * keep_f)
    )  # (K*T, D)
    out = jnp.zeros((T, D), jnp.float32).at[t_s].add(contrib)

    # Load-balance aux loss + drop-rate metric (all K choices weighted).
    load = (
        jnp.zeros((E,), jnp.float32).at[e_flat].add(jnp.ones(T * K)) / (T * K)
    )
    importance = probs.mean(axis=0)
    aux_loss = E * jnp.sum(load * importance)
    dropped = 1.0 - keep.astype(jnp.float32).sum() / (T * K)
    return out.reshape(B, S, D).astype(x.dtype), {
        "aux_loss": aux_loss,
        "dropped": dropped,
    }


def route_top_k(
    tokens: jax.Array,
    router: jax.Array,
    bias: Any,
    top_k: int,
    scoring: str,
    scale: float = 1.0,
) -> Tuple[jax.Array, jax.Array]:
    """tokens (T, D), router (D, E) -> gates (T, K) float32 and experts
    (T, K) int32 over ALL ``E`` experts. ``scale`` multiplies the gates
    after their normalisation (a model's routed scaling factor).

    ``softmax``: :func:`_route_and_pack`'s rule (top-k of the softmax,
    renormalised over the k when k > 1). ``sigmoid``: the k largest of
    ``sigmoid(logits) + bias`` are chosen, ``bias`` (E,) taking part in
    the choice only, and the chosen sigmoids, normalised over the k, are
    the gates. The logits are float32 at the highest precision: the
    matmul is small, and a choice that flips on rounding changes which
    experts a token sees.
    """
    logits = jnp.einsum(
        "td,de->te",
        tokens.astype(jnp.float32),
        router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )
    K = int(top_k)
    if scoring == "sigmoid":
        score = jax.nn.sigmoid(logits)
        _, top_e = jax.lax.top_k(score + bias.astype(jnp.float32), K)
        top_s = jnp.take_along_axis(score, top_e, axis=-1)
    elif scoring == "softmax":
        top_s, top_e = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), K)
    else:
        raise ValueError(
            f"unknown moe_scoring {scoring!r}; use 'softmax' or 'sigmoid'"
        )
    if K > 1 or scoring == "sigmoid":
        top_s = top_s / jnp.clip(top_s.sum(-1, keepdims=True), 1e-9, None)
    if scale != 1.0:
        top_s = top_s * jnp.float32(scale)
    return top_s, top_e.astype(jnp.int32)


#: Matrices on the input side of an MLP, by variant: gate and up, or up alone.
MLP_GATES = {"swiglu": 2, "relu2": 1}


def mlp_act(z: jax.Array, variant: str) -> jax.Array:
    """What an MLP's input side z (..., gates, F) gives its output side:
    ``swiglu`` has two gates, ``silu(gate) * up``; ``relu2`` one,
    ``relu(up)^2``. The gate count is read from z, so a tree of the wrong
    kind is refused and never half used."""
    gates = MLP_GATES.get(variant)
    if gates is None or z.shape[-2] != gates:
        raise ValueError(
            f"an MLP of variant {variant!r} takes {gates} input matrices, "
            f"its weights have {z.shape[-2]}"
        )
    if variant == "swiglu":
        return jax.nn.silu(z[..., 0, :]) * z[..., 1, :]
    return jnp.square(jax.nn.relu(z[..., 0, :]))


def held_row_tile(n_tokens: int, top_k: int, n_experts: int) -> int:
    """Rows of one tile of :func:`moe_ffn_held`'s grouped matmul: twice
    what an expert gets under even routing, a power of two in [16, 256].
    A tile belongs to one expert, so an expert's weights are read once a
    tile; small tiles waste little padding at decode, large ones keep the
    matmuls of a prefill wide."""
    want = max(1, 2 * n_tokens * top_k // max(1, n_experts))
    return int(min(256, max(16, 1 << (want - 1).bit_length())))


def moe_ffn_held(
    params: Dict[str, jax.Array],
    x: jax.Array,
    *,
    held: Tuple[int, int],
    top_k: int,
    scoring: str = "sigmoid",
    valid: Any = None,
    compute_dtype: Any = jnp.float32,
    layer: Any = None,
    expert_in: Any = None,
    variant: str = "swiglu",
    scale: float = 1.0,
) -> Tuple[jax.Array, jax.Array]:
    """The expert layer of a process that holds ``held = (first, count)``
    of the experts: x (T, D) -> (T, D), the part of the layer's result
    that its own experts give, and ``[pairs routed, pairs on held
    experts, held experts hit]`` (int32).

    ``params``: ``router`` (D, E) over ALL experts (with ``router_bias``
    (E,) under sigmoid scoring), ``wi`` (count, gates, Din, F) — gate and
    up (SwiGLU) or up alone (``variant="relu2"``: :func:`mlp_act`), each
    a (Din, F) matrix as the matmul wants it — and ``wo`` (count, F, Din)
    of the held experts alone. The router reads x; the experts read
    ``expert_in`` (T, Din) where the model's experts work at another
    width than the residual's (a latent the caller projected x into), and
    the result is then (T, Din) too. ``scale`` is :func:`route_top_k`'s.
    With ``layer`` (a static index)
    ``wi`` and ``wo`` are the leaves of ALL expert layers, stacked on a
    leading axis, and the loop reads ``[layer, expert]`` out of them: a
    caller that sliced its layer out first would hand the loop a copy of
    the layer's weights, made again on every call. The choice and the
    gates are over all E and all k (:func:`route_top_k`); only the
    (token, expert) pairs whose expert is held are computed. They are
    grouped by expert into tiles of :func:`held_row_tile` rows, each
    group padded to whole tiles, and a loop over the tiles IN USE runs one
    expert's MLP a tile: no pair is dropped, no capacity factor, and
    both the matmuls and the weights read follow the counts. An expert no
    token chose is never read. Nothing here stands in for the processes
    that hold the other experts: what they would add is not in the result.

    ``valid`` (T,) bool marks the real tokens (padding of a bucketed
    prefill, idle decode lanes): the others route nowhere.
    """
    T, D = x.shape
    first, Eh = int(held[0]), int(held[1])
    K = int(top_k)
    E = params["router"].shape[1]
    cdt = jnp.dtype(compute_dtype)
    wi, wo = params["wi"], params["wo"]
    with jax.named_scope("router"):
        gates, experts = route_top_k(
            x, params["router"], params.get("router_bias"), K, scoring, scale
        )
    if expert_in is not None:
        x = expert_in
        D = x.shape[1]
    with jax.named_scope("moe_dispatch"):
        local = experts - first
        here = (local >= 0) & (local < Eh)
        routed = jnp.asarray(T * K, jnp.int32)
        if valid is not None:
            here = here & valid[:, None]
            routed = valid.sum().astype(jnp.int32) * K
        N = T * K
        e_flat = jnp.where(here, local, Eh).reshape(N)  # pair n = t * K + k
        onehot = (e_flat[:, None] == jnp.arange(Eh)[None]).astype(jnp.int32)
        rank = jnp.take_along_axis(
            jnp.cumsum(onehot, axis=0), jnp.minimum(e_flat, Eh - 1)[:, None], 1
        )[:, 0] - 1
        counts = onehot.sum(0)  # (Eh,) pairs per held expert
        tile = held_row_tile(T, K, E)
        tiles_e = (counts + tile - 1) // tile
        tile_end = jnp.cumsum(tiles_e)
        n_tiles = tile_end[-1]
        R = (-(-N // tile) + Eh) * tile  # every group padded to whole tiles
        row0 = (tile_end - tiles_e) * tile  # first row of each group
        dest = jnp.where(
            e_flat < Eh, row0[jnp.minimum(e_flat, Eh - 1)] + rank, R
        )  # R: no row
        row_token = (
            jnp.zeros((R,), jnp.int32)
            .at[dest]
            .set(jnp.arange(N, dtype=jnp.int32) // K, mode="drop")
        )
        tile_expert = jnp.searchsorted(
            tile_end, jnp.arange(R // tile), side="right"
        ).astype(jnp.int32)
    with jax.named_scope("moe_experts"):
        xc = x.astype(cdt)

        def one_expert(w, e):
            if layer is None:
                return jax.lax.dynamic_index_in_dim(w, e, 0, keepdims=False)
            start = (layer, e) + (0,) * (w.ndim - 2)
            return jax.lax.dynamic_slice(w, start, (1, 1) + w.shape[2:])[0, 0]

        def one_tile(i, y):
            e = tile_expert[i]
            rows = jax.lax.dynamic_slice(row_token, (i * tile,), (tile,))
            wi_e, wo_e = one_expert(wi, e), one_expert(wo, e)
            z = jnp.einsum("td,cdf->tcf", xc[rows], wi_e.astype(cdt))
            yt = jnp.einsum("tf,fd->td", mlp_act(z, variant), wo_e.astype(cdt))
            return jax.lax.dynamic_update_slice(y, yt, (i * tile, 0))

        y = jax.lax.fori_loop(0, n_tiles, one_tile, jnp.zeros((R, D), cdt))
    with jax.named_scope("moe_combine"):
        picked = y[jnp.minimum(dest, R - 1)].reshape(T, K, D)
        g = jnp.where(here, gates, 0.0)
        out = jnp.einsum(
            "tkd,tk->td", picked.astype(jnp.float32), g
        ).astype(x.dtype)
    stats = jnp.stack(
        [routed, here.sum().astype(jnp.int32),
         (counts > 0).sum().astype(jnp.int32)]
    )
    return out, stats


def moe_ffn_ep(
    params: Dict[str, jax.Array],
    x: jax.Array,
    mesh: Any = None,
    ep_axis: str = "ep",
    capacity_factor: float = 1.25,
    compute_dtype: Any = jnp.float32,
    top_k: int = 1,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Expert-parallel MoE with an EXPLICIT token all-to-all over ``ep``.

    Why this exists: leaving the sort-based dispatch to GSPMD with
    ep-sharded expert weights lowers to all-gathers + all-reduces (checked
    on the compiled HLO: 6 all-gathers, 12 all-reduces, ZERO all-to-alls) —
    every ep rank materializes full-size dispatch buffers, so dispatch
    traffic does not shrink as the ep axis grows. The scalable TPU design
    (GShard; "How to Scale Your Model" ch. MoE) shards the TOKENS over ep
    too and exchanges only routed tokens with ``lax.all_to_all`` riding
    ICI: per-rank traffic drops from O(T·D) to O(T·K·D/ep) each way.

    Layout contract (per ``shard_map`` over the ``ep`` axis only; other
    mesh axes stay under GSPMD inside):
      - ``x`` (B, S, D): B divides by ep; each rank takes its B/ep slice
        (free: x is ep-replicated at entry), routes its local tokens, and
        builds per-expert send queues of quota C_src = cf·T_local·K/E.
      - one all-to-all ships (E_local, ep·C_src, D) expert batches to the
        owning ranks; experts run on their local shard; a second
        all-to-all ships contributions back. The OUTPUT STAYS EP-SHARDED
        on the batch dim (out_specs P(ep)): the consumer's next op makes
        GSPMD insert any layout-restoring gather exactly where needed
        (the compiled dispatch itself carries zero all-gathers, asserted
        in tests).
      - capacity semantics: per-expert capacity C = ep·C_src is enforced
        as the concatenation of per-SOURCE-rank quotas (each rank may fill
        at most C_src slots of any expert), vs the single-queue semantics
        of :func:`moe_ffn`. With drop-free capacity both reduce to the
        exact mixture, asserted against the dense oracle in tests.
      - aux loss / drop metrics are psum'd over ep: identical to the
        single-device statistics (router probs are token-local).

    Top-1 and top-k routing follow :func:`moe_ffn` (same gating math).

    ``mesh=None`` resolves the CONTEXT abstract mesh — the way to call
    this inside another shard_map (e.g. a pipeline stage, where the pp
    axis is already manual): nested shard_maps must be built on the
    context mesh, whose already-manual axes differ from the concrete
    mesh's.
    """
    if mesh is None:
        mesh = jax.sharding.get_abstract_mesh()
    if ep_axis not in mesh.shape:
        raise ValueError(
            f"moe_ffn_ep needs a mesh with an {ep_axis!r} axis; got mesh "
            f"axes {tuple(mesh.shape)} (pass mesh= explicitly or call "
            "under a mesh context that defines it)"
        )
    B, S, D = x.shape
    E = params["router"].shape[1]
    ep = mesh.shape[ep_axis]
    if E % ep:
        raise ValueError(f"n_experts {E} must divide by ep axis {ep}")
    if B % ep:
        raise ValueError(
            f"batch {B} must divide by ep axis {ep} for all-to-all MoE "
            "dispatch (moe_dispatch='gspmd' lifts the constraint)"
        )
    E_local = E // ep
    K = int(top_k)
    cdt = jnp.dtype(compute_dtype)

    def per_rank(router, wi, bi, wo, bo, x_l):
        # x_l: (B/ep, S, D) — this rank's token shard.
        T_l = x_l.shape[0] * x_l.shape[1]
        tokens = x_l.reshape(T_l, D)
        c_src = max(1, int(capacity_factor * T_l * K / E))
        probs, e_flat, e_s, t_s, g_s, keep, pos_c = _route_and_pack(
            tokens, router, K, c_src
        )

        keep_f = keep.astype(jnp.float32)[:, None]
        gathered = tokens.astype(jnp.float32)[t_s] * keep_f
        # Build the queues in fp32 (scatter-add determinism), ship in the
        # compute dtype: both all_to_alls carry cdt-width payloads — with
        # bf16 that halves the ICI bytes this path exists to minimize, and
        # costs nothing numerically (the expert matmuls consume cdt either
        # way; the cast just moves before the wire).
        send = (
            jnp.zeros((E, c_src, D), jnp.float32).at[e_s, pos_c].add(gathered)
        ).astype(cdt)
        # (E, C_src, D) -> (ep, E_local, C_src, D) -> a2a -> source-major
        # (ep, E_local, C_src, D): dim 0 now indexes the SOURCE rank.
        send = send.reshape(ep, E_local, c_src, D)
        recv = jax.lax.all_to_all(
            send, ep_axis, split_axis=0, concat_axis=0, tiled=False
        )
        # recv: (src, E_local, c, D) — bring experts to the front before
        # collapsing the (src, c) slots (a bare reshape would interleave
        # different experts' queues).
        expert_in = recv.transpose(1, 0, 2, 3).reshape(
            E_local, ep * c_src, D
        )
        expert_out = _expert_ffn(
            expert_in, {"wi": wi, "bi": bi, "wo": wo, "bo": bo}, cdt
        )
        # Ship contributions back to their source ranks (reverse a2a), still
        # cdt-wide — the fp32 upcast happens at the local combine:
        # (E_local, src*c, D) -> (src, E_local, c, D), send chunk src back
        # to its rank; the received (owner, E_local, c, D) flattens to the
        # global (E, c, D) queue order this rank built.
        back = jax.lax.all_to_all(
            expert_out.reshape(E_local, ep, c_src, D).transpose(1, 0, 2, 3),
            ep_axis,
            split_axis=0,
            concat_axis=0,
            tiled=False,
        ).reshape(E, c_src, D)
        contrib = back.astype(jnp.float32)[e_s, pos_c] * (
            g_s[:, None] * keep_f
        )
        out_l = jnp.zeros((T_l, D), jnp.float32).at[t_s].add(contrib)
        out_l = out_l.reshape(x_l.shape).astype(x_l.dtype)

        # Global routing statistics: psum the local sums over ep.
        load_cnt = jnp.zeros((E,), jnp.float32).at[e_flat].add(
            jnp.ones(T_l * K)
        )
        load_cnt = jax.lax.psum(load_cnt, ep_axis)
        imp_sum = jax.lax.psum(probs.sum(axis=0), ep_axis)
        kept = jax.lax.psum(keep.astype(jnp.float32).sum(), ep_axis)
        t_total = jnp.float32(T_l * ep)
        aux_loss = E * jnp.sum(
            (load_cnt / (t_total * K)) * (imp_sum / t_total)
        )
        dropped = 1.0 - kept / (t_total * K)
        return out_l, aux_loss, dropped

    from jax.sharding import PartitionSpec as P

    out, aux_loss, dropped = shard_map(
        per_rank,
        mesh=mesh,
        in_specs=(
            P(),  # router replicated
            P(ep_axis),  # wi: experts sharded
            P(ep_axis),
            P(ep_axis),
            P(ep_axis),
            P(ep_axis),  # x: batch dim sliced over ep (free at entry)
        ),
        # The output stays ep-sharded on the batch dim: the consumer's
        # residual add forces GSPMD to insert the layout-restoring gather
        # exactly where it is needed (often fused with the add), instead
        # of an unconditional all_gather here.
        out_specs=(P(ep_axis), P(), P()),
        axis_names={ep_axis},
    )(
        params["router"],
        params["wi"],
        params["bi"],
        params["wo"],
        params["bo"],
        x,
    )
    return out, {"aux_loss": aux_loss, "dropped": dropped}


def moe_ffn_dense(
    params: Dict[str, jax.Array],
    x: jax.Array,
    capacity_factor: float = 1.25,
    compute_dtype: Any = jnp.float32,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Dense one-hot einsum dispatch (top-1 only) — the readable oracle.

    O(T·E·C) dispatch/combine tensors; kept for equivalence tests and tiny
    expert counts.
    """
    B, S, D = x.shape
    E = params["router"].shape[1]
    tokens = x.reshape(B * S, D)
    # Router in fp32 for stable softmax.
    logits = tokens.astype(jnp.float32) @ params["router"]  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    expert_idx = jnp.argmax(probs, axis=-1)  # (T,)
    gate = jnp.take_along_axis(probs, expert_idx[:, None], axis=1)[:, 0]

    T = B * S
    capacity = max(1, int(capacity_factor * T / E))
    onehot = jax.nn.one_hot(expert_idx, E, dtype=jnp.float32)  # (T, E)
    # Position of each token within its expert's queue; tokens past
    # capacity are dropped (residual passes through untouched).
    pos_in_expert = (jnp.cumsum(onehot, axis=0) - 1.0) * onehot  # (T, E)
    keep = (pos_in_expert < capacity) & (onehot > 0)  # (T, E) bool
    pos = jnp.where(keep, pos_in_expert, 0.0).astype(jnp.int32)
    pos_onehot = jax.nn.one_hot(pos, capacity, dtype=jnp.float32)  # (T, E, C)
    dispatch = pos_onehot * keep[..., None].astype(jnp.float32)  # (T, E, C)

    # Dispatch tokens to (E, C, D) expert buffers, run experts batched on
    # the leading (sharded) expert dim, combine back weighted by the gate.
    cdt = jnp.dtype(compute_dtype)
    expert_in = jnp.einsum(
        "tec,td->ecd", dispatch, tokens.astype(jnp.float32)
    ).astype(cdt)
    expert_out = _expert_ffn(expert_in, params, cdt)
    combine = dispatch * gate[:, None, None]
    out = jnp.einsum(
        "tec,ecd->td", combine, expert_out.astype(jnp.float32)
    )

    # Load-balance aux loss + drop-rate metric.
    load = onehot.mean(axis=0)  # fraction routed per expert
    importance = probs.mean(axis=0)
    aux_loss = E * jnp.sum(load * importance)
    dropped = 1.0 - keep.astype(jnp.float32).sum() / T
    return out.reshape(B, S, D).astype(x.dtype), {
        "aux_loss": aux_loss,
        "dropped": dropped,
    }
