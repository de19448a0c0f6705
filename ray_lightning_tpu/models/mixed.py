"""A decoder whose layers differ in kind, and one block function for it.

``GPTConfig.layer_types`` gives each layer a mixer kind — attention "full"
(the whole context), "window" (the last ``attn_window`` positions) or
"latent" (the whole context through one compressed row a position:
:func:`_latent_part`), or "ssm" (a Mamba-2 state layer: models/ssm.py) —
and an MLP kind — "dense"
(``d_ff``) or "experts" (routed experts of ``d_ff_expert``, of which this
process may hold a share: ``experts_held``). Either part may be missing
(None): such a layer is ``x + part(RMSNorm(x))`` alone. The attention
kinds may differ in KV heads, rotary base and in whether a learnable
per-head sink logit joins the softmax; q·k and v may differ in width, and
the rotation may cover only the first ``rope_dim`` dims of a head, or
there may be none (``pos_embed="none"``: the state layers carry order).
The MLPs are SwiGLU or, with ``mlp_variant="relu2"``, ``down(relu(up
x)^2)``; the experts may work in a latent narrower than the residual
(``moe_latent_dim``), beside one shared expert on the whole input
(``d_ff_shared``). RMSNorm, no biases.

A layer's mixer may also be TWO mixers side by side, spelled
``"full+ssm"`` (:data:`PARALLEL`): full attention and a state layer read
the one normed input ``u = RMSNorm(x; ln1_g)`` and both write into the
residual in one step, ``x + A(u) + M(u)``, before the layer's MLP. Such a
layer is a layer of BOTH kinds — it has an index among the full layers
(its K/V rows) and one among the state layers (its state and conv tail),
one admission writes both and one decode step advances both — and a
single ``ln1_g``. With ``multipliers`` (``GPTConfig.MULTIPLIERS``: a
model's muP scalars) the embedding's rows, the head's logits, each
mixer's input and output, the keys, the five parts of a state layer's
in-projection, a dense MLP's gate and its output are each multiplied by
a constant, decided while the program is traced: a configuration
without them compiles to what it compiled to before they existed.

One block, :func:`mixed_block`, serves the three modes the model runs in:

- no cache (``gpt_forward``): attention among the rows given;
- prefill (``gpt_prefill``, an admission): the same, and the rows' K/V come
  out so that the caller can write them into a slot
  (:func:`write_prefill_rows`); nobody differentiates these rows, so a full
  or latent layer's attention may run in the forward flash kernel
  (:func:`prefill_kernel` says when);
- decode (``gpt_decode_step``): one row a slot at per-slot positions, its
  K/V written in place into the caches and attention read from them.

The caches are one a kind, side by side, because the kinds need different
amounts: a full layer keeps every position of a request, a window layer
only the last ``attn_window`` of them, in a ring (row ``pos mod R``), a
state layer one running state and the conv's last rows whatever the
request's length. A request's state comes in two halves, each a dict by
kind: K and V of the attention kinds, and under ``"ssm"`` the recurrent
states in the first and the conv tails in the second — one array a state
layer, ``(B, H, P, N)`` float32 and ``(taps - 1, B, channels)``, in a
tuple: a step replaces each whole, so a donated one is updated where it
lies and no layer is ever sliced out of a stack. The latent kind keeps
a position's normed latent in the first half, ``(Lk, B, S,
kv_lora_rank)`` — keys and values of every head are read out of it —
and in the second the rotary key all heads share, already rotated,
``(Lk, B, S, rope_dim)``: ``kv_lora_rank + rope_dim`` values a position
where K and V would be ``n_head * (qk + v)``. (As ONE array of rows
``[latent; key]`` the chip's compiler re-laid a layer out for the second
of the two matmuls that read it and kept a padded copy of the whole:
compile-only for the v5e, PR 38. Keys of 64, half a lane tile, the
chip's compiler keeps with the positions minor, ``{2,3,1,0}``, to pad
nothing: the decode kernel, whose operands are row-major, is handed
``swapaxes(keys, 2, 3)``, which is those bytes as they lie and no copy,
and copies ``(rope_dim, block)``. PR 39;
``tests/test_latent_step_v5e.py`` guards it.)
A K/V attention kind has
one stacked array, ``{"full": ...,
"window": ...}``, ``(Lk, B, rows, Hkv * d)`` with rows ``S`` or ``R`` and
``d`` the q·k width for K and the v width for V: a position's KV heads
lie side by side in one row. A step's write is then one row a slot, and
its two matmuls read a slot's rows as one (rows, Hkv * d) matrix, against
queries laid out block-diagonally over the KV heads (:func:`_attend_cache`).
With the KV heads on an axis of their own, the write and the matmuls
each wanted another physical layout and the compiler re-laid a whole
layer's cache out on every step (0.5 GB a full layer at the benchmark's
size; compile-only for the v5e, PR 28).

The parameter tree is top-level ``wte``, ``lm_head``, ``lnf_g`` and one
flat ``blocks`` dict (:func:`mixed_param_shapes`): leaves of one kind of
layer are stacked on a leading axis over the layers OF THAT KIND (the
norm gains ``ln1_g`` over the layers that have a mixer, ``ln2_g`` over
those that have an MLP). Gate and up of a SwiGLU are two (D, F) matrices
side by side (``(2, D, F)``): the layout the TPU's matmul takes them in,
so that no step re-lays them; a relu2 MLP has the one (``(1, D, F)``).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ray_lightning_tpu.models import layers
from ray_lightning_tpu.models.layers import _lm_head, _rmsnorm, _rope, _rope_tables

#: Attention kinds that keep a K and a V row a position.
KV_KINDS = ("full", "window")
ATTN_KINDS = KV_KINDS + ("latent",)
MIXER_KINDS = ATTN_KINDS + ("ssm",)
#: Two mixers side by side on one normed input: full attention and a state layer.
PARALLEL = "full+ssm"
MLP_KINDS = ("dense", "experts")
#: Prefix of a mixer kind's leaves in ``blocks``.
_MIXER_PREFIX = {"full": "full", "window": "swa", "latent": "lat", "ssm": "ssm"}
#: Query rows a block of the no-cache full attention takes at a time: the
#: float32 scores of a block against its causal prefix are what is live.
_Q_BLOCK = 512
#: Rows of a no-cache attention from which the forward flash kernel reads it
#: (:func:`prefill_kernel`): the least bucket at which a PROGRAM gains on the
#: chip. By the layer's call alone the kernel beats the blocked XLA read from
#: 2,048 rows at all three served shapes (``tools/flash_check.py --time``, us
#: for one layer's call, XLA / kernel, bfloat16, every row real; a call of
#: either costs about 600 us of dispatch there; PERF.md §6, PR 49):
#:
#:   rows    32 heads 192/128    64 on 4, 192/128    20 on 4, 128/128
#:    512       661 / 693           784 / 860           621 / 633
#:   1024       771 / 903         1,611 / 1,166         688 / 674
#:   2048     1,804 / 1,347       4,064 / 2,046         940 / 896
#:   4096     6,467 / 3,134      11,700 / 5,063       3,120 / 1,605
#:   6144    13,853 / 5,372      24,691 / 9,707       7,162 / 2,698
#:
#: (with the prompt at half the bucket + 1 the kernel's time falls further,
#: 3,259 / 5,014 / 1,595 at 6,144 rows; XLA's does not). But sixteen latent
#: layers over a 2,048-row prompt read 53.0 ms against 53.8 — the XLA read
#: of that size overlaps the fusions around it — where 4,096 rows read 120.8
#: against 164.4, and every admission program that holds the kernel costs
#: a replica seconds of start (the docqa cell's warm ``setup_s`` 85-89 s with
#: none, 90-98 with the two buckets from 4,096 up, 97-102 with three, 103
#: with all five). So the 2,048 bucket keeps the XLA read.
_KERNEL_ROWS = 4096


@dataclass(frozen=True)
class LayerSpec:
    """One layer: its kinds (None: the layer has no such part), its index
    among the layers of each kind (where its leaves and its cache lie) and
    among the layers that have a mixer / an MLP (where its norm gains lie).
    A parallel layer (:data:`PARALLEL`) has ``mixer`` "full" and, beside it
    on the same normed input, ``side`` "ssm" with an index of its own."""

    index: int
    mixer: Optional[str]
    mlp: Optional[str]
    mixer_index: int
    mlp_index: int
    norm1_index: int
    norm2_index: int
    side: Optional[str] = None
    side_index: int = 0


def mixer_kinds(mixer: Optional[str]) -> Tuple[str, ...]:
    """The kinds a ``layer_types`` mixer entry names: none, one, or a
    parallel layer's two."""
    return tuple(mixer.split("+")) if mixer else ()


def layer_specs(cfg: Any) -> List[LayerSpec]:
    seen: Dict[Any, int] = {}
    out = []
    for i, (mixer, mlp) in enumerate(cfg.layer_types):
        kinds = mixer_kinds(mixer)
        first, side = (kinds + (None, None))[:2]
        # a missing part (None) has no index of its own: all of them read 0
        out.append(LayerSpec(
            i, first, mlp, seen.get(first, 0), seen.get(mlp, 0),
            seen.get("mixers", 0), seen.get("mlps", 0), side, seen.get(side, 0),
        ))
        for key in kinds + (("mixers",) if kinds else ()) + ((mlp, "mlps") if mlp else ()):
            seen[key] = seen.get(key, 0) + 1
    return out


def count_kind(cfg: Any, kind: str) -> int:
    """Layers that have a part of ``kind``; a parallel layer counts under
    each of its mixers."""
    return sum(kind in mixer_kinds(pair[0]) + (pair[1],) for pair in cfg.layer_types)


def count_part(cfg: Any, part: int) -> int:
    """Layers that have a mixer (``part`` 0) or an MLP (1)."""
    return sum(pair[part] is not None for pair in cfg.layer_types)


def validate_mixed(cfg: Any) -> None:
    """A mixed configuration that the block below cannot run is refused
    here, by what is wrong with it."""
    if len(cfg.layer_types) != cfg.n_layer:
        raise ValueError(
            f"layer_types names {len(cfg.layer_types)} layers, n_layer is "
            f"{cfg.n_layer}"
        )
    for pair in cfg.layer_types:
        if (
            len(pair) != 2 or pair[0] not in MIXER_KINDS + (PARALLEL, None)
            or pair[1] not in MLP_KINDS + (None,) or pair == (None, None)
        ):
            raise ValueError(
                f"layer_types entry {pair!r}: use (mixer kind of "
                f"{MIXER_KINDS} or {PARALLEL!r}, two mixers side by side on "
                f"one normed input, MLP kind of {MLP_KINDS}), either of them "
                "None for a layer that is the other part alone"
            )
    if cfg.norm_impl != "rmsnorm" or cfg.pos_embed not in ("rope", "none") or (
        cfg.mlp_variant not in ("swiglu", "relu2")
    ):
        raise ValueError(
            "layer_types runs RMSNorm, rotary or no positions and SwiGLU or "
            "relu2 MLPs: set norm_impl='rmsnorm', pos_embed='rope' or "
            "'none', mlp_variant='swiglu' or 'relu2'"
        )
    if count_kind(cfg, "ssm"):
        H, G = cfg.ssm_heads, cfg.ssm_groups
        if min(H, cfg.ssm_head_dim, G, cfg.ssm_state, cfg.ssm_chunk) < 1 or cfg.ssm_conv < 2 or H % G:
            raise ValueError(
                "state layers need ssm_heads, ssm_head_dim, ssm_state and "
                "ssm_chunk >= 1, ssm_conv >= 2 taps and ssm_heads divisible "
                f"by ssm_groups (got heads {H}, head_dim {cfg.ssm_head_dim}, "
                f"groups {G}, state {cfg.ssm_state}, conv {cfg.ssm_conv}, "
                f"chunk {cfg.ssm_chunk}); the state half of a {PARALLEL!r} "
                "layer is one"
            )
    elif cfg.pos_embed == "none" and any(count_kind(cfg, kind) for kind in ATTN_KINDS):
        raise ValueError(
            "pos_embed='none' needs state layers: attention without positions "
            "sees a set, and nothing else here carries the order"
        )
    if cfg.tie_word_embeddings:
        raise ValueError("layer_types needs an untied head (tie_word_embeddings=False)")
    if count_kind(cfg, "window") and cfg.attn_window < 1:
        raise ValueError("window layers need attn_window >= 1")
    if cfg.attn_sinks:
        raise ValueError(
            "attn_sinks (positional sinks) do not apply to layer_types; a "
            "learnable sink logit is attn_sink_logit"
        )
    for kind in cfg.attn_sink_logit:
        if kind not in KV_KINDS:
            raise ValueError(f"attn_sink_logit names {kind!r}, not one of {KV_KINDS}")
    if count_kind(cfg, "latent"):
        if cfg.kv_lora_rank < 1 or cfg.pos_embed != "rope" or not 0 < rope_dim(cfg) < qk_dim(cfg):
            raise ValueError(
                "latent layers need kv_lora_rank >= 1, pos_embed='rope' and "
                "0 < rope_dim < qk_head_dim: a q·k head is its dims against "
                "the latent's keys, then rope_dim rotated ones (got "
                f"kv_lora_rank {cfg.kv_lora_rank}, rope_dim {rope_dim(cfg)}, "
                f"q·k head {qk_dim(cfg)})"
            )
    elif cfg.kv_lora_rank:
        raise ValueError("kv_lora_rank describes latent layers: layer_types names none")
    for kind in KV_KINDS:
        if count_kind(cfg, kind) and (kv_heads(cfg, kind) < 1 or cfg.n_head % kv_heads(cfg, kind)):
            raise ValueError(
                f"n_head ({cfg.n_head}) must be divisible by the {kind} "
                f"layers' KV heads ({kv_heads(cfg, kind)}: n_kv_head, or "
                f"n_head without it; the attention half of {PARALLEL!r} is a "
                "full layer)"
            )
    if cfg.multipliers and (
        len(cfg.multipliers) != len(cfg.MULTIPLIERS)
        or not all(np.isfinite(m) and m != 0.0 for m in cfg.multipliers)
    ):
        raise ValueError(
            f"multipliers has {len(cfg.multipliers)} values "
            f"{cfg.multipliers!r}: give one finite, non-zero scalar a name of "
            f"{cfg.MULTIPLIERS}, in that order, or none at all"
        )
    if rope_dim(cfg) % 2 or rope_dim(cfg) > qk_dim(cfg):
        raise ValueError(
            f"rope_dim {rope_dim(cfg)} must be even and at most the q·k "
            f"head width {qk_dim(cfg)}"
        )
    if count_kind(cfg, "experts"):
        if cfg.n_experts < 1 or not 1 <= cfg.moe_top_k <= cfg.n_experts:
            raise ValueError(
                "expert layers need n_experts >= 1 and 1 <= moe_top_k <= n_experts"
            )
        if cfg.moe_scoring not in ("softmax", "sigmoid"):
            raise ValueError(
                f"unknown moe_scoring {cfg.moe_scoring!r}; use 'softmax' or 'sigmoid'"
            )
        first, count = experts_held(cfg)
        if len(cfg.experts_held) not in (0, 2) or first < 0 or count < 1 or (
            first + count > cfg.n_experts
        ):
            raise ValueError(
                f"experts_held {cfg.experts_held!r} must be (first, count) "
                f"inside the {cfg.n_experts} experts"
            )
        if cfg.moe_latent_dim < 0 or cfg.d_ff_shared < 0:
            raise ValueError("moe_latent_dim and d_ff_shared must be >= 0")
    elif cfg.moe_latent_dim or cfg.d_ff_shared or cfg.moe_routed_scale != 1.0:
        raise ValueError(
            "moe_latent_dim, d_ff_shared and moe_routed_scale describe "
            "expert layers: layer_types names none"
        )


def refuse_mixed(cfg: Any, mechanism: str) -> None:
    """The modes that have no block for mixed layers or held experts say
    so by name; none of them computes silently."""
    if cfg.mixed:
        state = (
            "; a state layer keeps one running state a request, with no "
            "rows to page, share, export, verify or enter mid-prompt: such a "
            "mode would need a snapshot of the state"
            if count_kind(cfg, "ssm") else ""
        )
        latent = (
            "; a latent layer keeps one row of kv_lora_rank + rope_dim values "
            "a position and no K / V pair: the page, pool, export and wire "
            "formats (serve/kvstore.py, serve/kvfleet.py) have no such row, "
            "and its decode read (ops/decode_attention.py) takes one query "
            "row a slot against whole slots' rows"
            if count_kind(cfg, "latent") else ""
        )
        raise ValueError(
            f"{mechanism} does not run a configuration with mixed layer "
            "kinds or held experts (GPTConfig.layer_types): it has the "
            f"dense engine's bucketed prefill and decode fold only{state}{latent}"
        )


# -- sizes ---------------------------------------------------------------------
def qk_dim(cfg: Any) -> int:
    return cfg.qk_head_dim or cfg.head_dim


def v_dim(cfg: Any) -> int:
    return cfg.v_head_dim or cfg.head_dim


def rope_dim(cfg: Any) -> int:
    return cfg.rope_dim or qk_dim(cfg)


def kv_heads(cfg: Any, kind: str) -> int:
    if kind == "window" and cfg.n_kv_head_window:
        return cfg.n_kv_head_window
    return cfg.n_kv_head or cfg.n_head


def rope_theta(cfg: Any, kind: str) -> float:
    if kind == "window" and cfg.rope_theta_window:
        return cfg.rope_theta_window
    return cfg.rope_theta


def experts_held(cfg: Any) -> Tuple[int, int]:
    if cfg.experts_held:
        return int(cfg.experts_held[0]), int(cfg.experts_held[1])
    return 0, cfg.n_experts


def ring_rows(cfg: Any) -> int:
    """Rows a window layer keeps for one request. A step writes position
    ``pos`` (row ``pos mod R``) before it reads positions ``pos - W + 1
    .. pos``, and an admission writes a prompt's last rows before any
    read: ``R = W`` rows hold exactly what is read, and the row that a
    write overwrites (``pos - W``) has just left the window."""
    return int(cfg.attn_window)


def mixed_param_shapes(cfg: Any) -> Dict[str, Any]:
    """``name -> shape`` of the tree the block takes (``blocks`` nested)."""
    from ray_lightning_tpu.models import ssm
    from ray_lightning_tpu.parallel.moe import MLP_GATES

    D, H, V = cfg.d_model, cfg.n_head, cfg.vocab_size
    dqk, dv, C = qk_dim(cfg), v_dim(cfg), MLP_GATES[cfg.mlp_variant]
    blocks: Dict[str, Tuple[int, ...]] = {}
    for name, part in (("ln1_g", 0), ("ln2_g", 1)):
        if count_part(cfg, part):
            blocks[name] = (count_part(cfg, part), D)
    for kind in KV_KINDS:
        n, hkv, p = count_kind(cfg, kind), kv_heads(cfg, kind), _MIXER_PREFIX[kind]
        if not n:
            continue
        blocks.update({
            f"{p}_wq": (n, D, H, dqk), f"{p}_wk": (n, D, hkv, dqk),
            f"{p}_wv": (n, D, hkv, dv), f"{p}_wo": (n, H, dv, D),
        })
        if kind in cfg.attn_sink_logit:
            blocks[f"{p}_sink"] = (n, H)
    n = count_kind(cfg, "latent")
    if n:
        # wkv_a: the stream -> [latent; rotary key]; wkv_b: the normed latent ->
        # each head's [keys of its no-position dims; values], the heads leading: the
        # order both of its uses multiply in, so that no fold re-lays the stack out
        r, dn = cfg.kv_lora_rank, dqk - rope_dim(cfg)
        blocks.update({
            "lat_wq": (n, D, H, dqk), "lat_wkv_a": (n, D, r + rope_dim(cfg)), "lat_kv_g": (n, r),
            "lat_wkv_b": (n, H, r, dn + dv), "lat_wo": (n, H, dv, D),
        })
    n = count_kind(cfg, "ssm")
    if n:
        blocks.update(ssm.param_shapes(cfg, n))
    n = count_kind(cfg, "dense")
    if n:
        blocks.update({"dense_wi": (n, C, D, cfg.ff_dim), "dense_wo2": (n, cfg.ff_dim, D)})
    n = count_kind(cfg, "experts")
    if n:
        held, F = experts_held(cfg)[1], cfg.d_ff_expert or cfg.ff_dim
        Din = cfg.moe_latent_dim or D  # the width the routed experts work at
        blocks.update({
            "moe_router": (n, D, cfg.n_experts),
            "moe_wi": (n, held, C, Din, F), "moe_wo2": (n, held, F, Din),
        })
        if cfg.moe_scoring == "sigmoid":
            blocks["moe_router_bias"] = (n, cfg.n_experts)
        if cfg.moe_latent_dim:
            blocks.update({"moe_latent_down": (n, D, Din), "moe_latent_up": (n, Din, D)})
        if cfg.d_ff_shared:
            blocks.update({
                "moe_shared_wi": (n, C, D, cfg.d_ff_shared), "moe_shared_wo2": (n, cfg.d_ff_shared, D),
            })
    return {"wte": (V, D), "lm_head": (V, D), "lnf_g": (D,), "blocks": blocks}


def init_mixed_params(rng: jax.Array, cfg: Any) -> Dict[str, Any]:
    """Seeded float32 parameters: normal ``init_std``, the writes into
    the residual stream scaled by 1/sqrt(2L), gains, sink logits and a
    state layer's ``D`` one, the router's correction bias and a state
    layer's ``A_log`` and ``dt_bias`` zero (decay ``exp(-dt)``)."""
    shapes = mixed_param_shapes(cfg)
    res_std = cfg.init_std / np.sqrt(2.0 * cfg.n_layer)
    flat = [(k, v) for k, v in shapes.items() if k != "blocks"] + [
        ("blocks/" + k, v) for k, v in shapes["blocks"].items()
    ]
    out: Dict[str, Any] = {"blocks": {}}
    for i, (name, shape) in enumerate(sorted(flat)):
        leaf = name.rsplit("/", 1)[-1]
        if leaf.endswith(("_g", "_sink")) or leaf == "ssm_D":
            w = jnp.ones(shape, jnp.float32)
        elif leaf in ("moe_router_bias", "ssm_A_log", "ssm_dt_bias"):
            w = jnp.zeros(shape, jnp.float32)
        else:
            std = res_std if leaf.endswith(("_wo", "_wo2", "_latent_up")) else cfg.init_std
            w = std * jax.random.normal(jax.random.fold_in(rng, i), shape, jnp.float32)
        if name.startswith("blocks/"):
            out["blocks"][leaf] = w
        else:
            out[name] = w
    return out


def empty_caches(cfg: Any, slots: int, max_seq: int, dtype: Any) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    """The zeroed per-request state of ``slots`` requests of up to
    ``max_seq`` positions, in its two halves (a kind the model has no
    layer of is left out): K and V of the attention kinds (of the latent
    kind the latents and the rotary keys); under "ssm" a tuple of recurrent
    states and a tuple of conv tails, one a state layer."""
    from ray_lightning_tpu.models import ssm

    rows = {"full": int(max_seq), "window": ring_rows(cfg)}
    k: Dict[str, Any] = {}
    v: Dict[str, Any] = {}
    for kind in KV_KINDS:
        n = count_kind(cfg, kind)
        if n:
            lead, hkv = (n, slots, rows[kind]), kv_heads(cfg, kind)
            k[kind] = jnp.zeros(lead + (hkv * qk_dim(cfg),), dtype)
            v[kind] = jnp.zeros(lead + (hkv * v_dim(cfg),), dtype)
    n = count_kind(cfg, "latent")
    if n:
        k["latent"] = jnp.zeros((n, slots, int(max_seq), cfg.kv_lora_rank), dtype)
        v["latent"] = jnp.zeros((n, slots, int(max_seq), rope_dim(cfg)), dtype)
    n = count_kind(cfg, "ssm")
    if n:
        k["ssm"], v["ssm"] = (tuple(x) for x in zip(*(ssm.empty_state(cfg, slots, dtype) for _ in range(n))))
    return k, v


# -- pieces --------------------------------------------------------------------
def _softmax_with_sink(s: jax.Array, sink: Optional[jax.Array]) -> jax.Array:
    """Softmax over the last axis of float32 scores (B, G, R, Q, K). With
    ``sink`` (G, R), one logit a query head, the sink joins the
    normalisation and has no column in the result: it takes probability
    and gives no value. A row with no key allowed comes out as zeros."""
    m = jnp.max(s, axis=-1, keepdims=True)
    if sink is not None:
        b = sink.astype(jnp.float32)[None, :, :, None, None]
        m = jnp.maximum(m, b)
    m = jnp.where(jnp.isfinite(m), m, 0.0)
    e = jnp.exp(s - m)
    z = e.sum(-1, keepdims=True)
    if sink is not None:
        z = z + jnp.exp(b - m)
    return e / jnp.where(z > 0, z, 1.0)


def _scores(q: jax.Array, k: jax.Array) -> jax.Array:
    """q (B, Q, G, R, d) x k (B, K, G, d) -> float32 (B, G, R, Q, K)."""
    return jnp.einsum(
        "bqgrd,bkgd->bgrqk", q, k, preferred_element_type=jnp.float32
    ) * (1.0 / np.sqrt(q.shape[-1]))


def _values(p: jax.Array, v: jax.Array) -> jax.Array:
    """p (B, G, R, Q, K) x v (B, K, G, d) -> (B, Q, G * R, d) in v's type."""
    o = jnp.einsum("bgrqk,bkgd->bqgrd", p.astype(v.dtype), v)
    return o.reshape(o.shape[0], o.shape[1], -1, o.shape[-1])


def _attend_rows_full(q, k, v, sink):
    """Causal attention among S rows, a block of queries at a time
    against its causal prefix (static slices: only the scores of one
    block are live, and no key after the block is multiplied)."""
    S = q.shape[1]
    out = []
    for s0 in range(0, S, _Q_BLOCK):
        s1 = min(S, s0 + _Q_BLOCK)
        s = _scores(q[:, s0:s1], k[:, :s1])
        ok = jnp.arange(s1)[None, :] <= jnp.arange(s0, s1)[:, None]
        p = _softmax_with_sink(jnp.where(ok, s, -jnp.inf), sink)
        out.append(_values(p, v[:, :s1]))
    return out[0] if len(out) == 1 else jnp.concatenate(out, axis=1)


def prefill_kernel(cfg: Any, kind: str, rows: int, backend: Optional[str] = None) -> bool:
    """Which read the no-cache attention of a ``kind`` layer takes over
    ``rows`` rows that nobody differentiates (a prefill), from what it can
    observe — as ``models/layers.py:decode_rows_block`` answers for decode:
    True for the forward flash kernel (``ops/flash_attention.py``: the score
    tile stays in VMEM, the KV heads are not repeated, query blocks past the
    prompt's end are not computed), False for the blocked XLA read
    (:func:`_attend_rows_full`, whose float32 scores and ``p`` go through
    HBM). The kernel wants ``attn_impl="flash"``, a TPU (elsewhere it would
    run interpreted, and the CPU's token-identity tests keep one order of
    sums), the full or the latent kind — the window kind's read is already
    ``rows x 2W`` (:func:`_attend_rows_window`) — without a learnable sink
    logit, which the kernel's sums do not know, head widths Mosaic takes
    (multiples of 64 up to 256: 64, 128, 192 and 256 lower for the v5e), rows
    that its tile divides, and at least :data:`_KERNEL_ROWS` of them: below
    that a program does not gain on the chip. :func:`prefill_reads` puts
    the answers together, bucket by bucket, for ``stats()["attn"]``."""
    from ray_lightning_tpu.ops.flash_attention import _default_block

    tile = min(_default_block(rows), rows)
    return (
        cfg.attn_impl == "flash"
        and (backend or jax.default_backend()) == "tpu"
        and kind in ("full", "latent")
        and kind not in cfg.attn_sink_logit
        and all(d % 64 == 0 and d <= 256 for d in (qk_dim(cfg), v_dim(cfg)))
        and rows >= _KERNEL_ROWS
        and rows % tile == 0
    )


def prefill_reads(cfg: Any, rows: int) -> Tuple[int, int, int, int]:
    """What an admission of ``rows`` rows (a bucket) reads, for the engine's
    counters: the attention layers; those of them whose read is the causal
    square (the full and the latent kinds; a window kind's is rows x 2W);
    those of the square ones that the forward flash kernel reads
    (:func:`prefill_kernel`, kind by kind); and the rows of a score tile —
    the kernel's where any layer takes it, else :func:`_attend_rows_full`'s
    block of queries —, clipped to the bucket."""
    from ray_lightning_tpu.ops.flash_attention import _default_block

    square = {kind: count_kind(cfg, kind) for kind in ("full", "latent")}
    kernel = sum(n for kind, n in square.items() if prefill_kernel(cfg, kind, rows))
    return (
        sum(count_kind(cfg, kind) for kind in ATTN_KINDS), sum(square.values()), kernel,
        min(_default_block(rows) if kernel else _Q_BLOCK, rows),
    )


def _attend_rows(cfg, kind, q, k, v, sink, real_rows):
    """Causal attention among the S rows of a full or latent layer with no
    cache: q (B, S, G, R, d), k (B, S, G, d), v (B, S, G, dv) -> (B, S,
    G * R, dv). ``real_rows`` None: a forward pass that may be
    differentiated, the XLA read. Else the rows are a prefill's, the first
    ``real_rows`` (int32 scalar) of them real, and :func:`prefill_kernel`
    says which read; the kernel leaves the query blocks past ``real_rows``
    zeros."""
    B, S, G, R, d = q.shape
    if real_rows is None or not prefill_kernel(cfg, kind, S):
        return _attend_rows_full(q, k, v, sink)
    from ray_lightning_tpu.ops.flash_attention import flash_attention

    return flash_attention(q.reshape(B, S, G * R, d), k, v, causal=True, true_len=real_rows)


def _attend_rows_window(q, k, v, sink, window: int):
    """Causal attention among S rows, each query seeing its last
    ``window`` positions: the queries go in blocks of ``window`` rows, and
    a block needs the keys of its own rows and of the block before."""
    B, S = q.shape[:2]
    W = int(window)
    nb = -(-S // W)
    pad = nb * W - S

    def blocks_of(x, front):
        x = jnp.pad(x, ((0, 0), (front, pad)) + ((0, 0),) * (x.ndim - 2))
        return x.reshape((B, -1, W) + x.shape[2:])

    qb = blocks_of(q, 0)  # (B, nb, W, G, R, d)
    kb, vb = blocks_of(k, W), blocks_of(v, W)  # (B, nb + 1, W, G, d)
    k2 = jnp.concatenate([kb[:, :-1], kb[:, 1:]], axis=2)  # (B, nb, 2W, G, d)
    v2 = jnp.concatenate([vb[:, :-1], vb[:, 1:]], axis=2)
    s = jnp.einsum(
        "bnqgrd,bnkgd->bngrqk", qb, k2, preferred_element_type=jnp.float32
    ) * (1.0 / np.sqrt(q.shape[-1]))
    # within a block: query a is at position n*W + a, key c at (n-1)*W + c
    a = jnp.arange(W)[:, None] + W
    c = jnp.arange(2 * W)[None, :]
    ok = (c <= a) & (c > a - W)
    first = (jnp.arange(nb) == 0)[:, None, None] & (c < W)[None]  # before position 0
    ok = ok[None] & ~first  # (nb, W, 2W)
    s = jnp.where(ok[None, :, None, None], s, -jnp.inf)
    G, R = s.shape[2], s.shape[3]
    p = _softmax_with_sink(
        s.reshape((B * nb,) + s.shape[2:]), sink
    ).reshape(s.shape)
    o = jnp.einsum("bngrqk,bnkgd->bnqgrd", p.astype(v.dtype), v2)
    return o.reshape(B, nb * W, G * R, v.shape[-1])[:, :S]


def _attend_cache(q, kc, vc, pos, sink, window: int, ring: bool):
    """One query row a slot, q (B, 1, G, R, d), against the slot's cache
    rows kc (B, rows, G * d) and vc (B, rows, G * dv) after this step's
    write; (B, 1, G * R, dv). ``pos`` (B,) is the query's position. Full
    cache: row r holds position r. Ring: row r holds the newest position
    ``<= pos`` that is ``r mod rows``.

    A row of the cache holds all G KV heads, so a query head is laid out
    over a whole row with zeros under the other KV heads' dims: one matmul
    against the cache as it lies gives every head's scores (G times the
    multiplications of the grouped form, on a read that the cache's bytes
    bound), and of p·V's (G * R, G * dv) result each head keeps its own KV
    head's block. The zeros add exact zeros: the numbers are the grouped
    form's."""
    B, _, G, R, d = q.shape
    rows, dv = kc.shape[1], vc.shape[-1] // G
    eye = jnp.eye(G, dtype=q.dtype)
    q_rows = jnp.einsum("bgrd,gh->bgrhd", q[:, 0], eye).reshape(B, G * R, G * d)
    s = jnp.einsum(
        "bhc,bsc->bhs", q_rows, kc, preferred_element_type=jnp.float32
    ) * (1.0 / np.sqrt(d))
    r = jnp.arange(rows, dtype=jnp.int32)[None, :]
    p = pos.astype(jnp.int32)[:, None]
    held = p - ((p - r) % rows) if ring else r
    ok = (held >= 0) & (held <= p)
    if window:
        ok = ok & (held > p - window)
    s = jnp.where(ok[:, None, :], s, -jnp.inf).reshape(B, G, R, 1, rows)
    probs = _softmax_with_sink(s, sink).reshape(B, G * R, rows)
    o = jnp.einsum("bhs,bsc->bhc", probs.astype(vc.dtype), vc)
    o = jnp.einsum("bgrhd,gh->bgrd", o.reshape(B, G, R, G, dv), eye.astype(o.dtype))
    return o.reshape(B, 1, G * R, dv)


def write_prefill_rows(
    k_cache: Dict[str, Any], v_cache: Dict[str, Any], pf_k: Dict[str, Any],
    pf_v: Dict[str, Any], slot: jax.Array, true_len: jax.Array,
) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    """An admitted prompt's state (:func:`mixed_rows`: of its ``Pb`` rows
    the first ``true_len`` are real) into slot ``slot``. K/V ``{kind:
    (Lk, 1, Pb, Hkv, d)}``, the KV heads side by side in a row: all ``Pb``
    rows of the full layers (rows past
    ``true_len`` lie behind the position mask, as in the dense engine),
    and of the window layers the prompt's last ``min(true_len, R)``
    positions, each at its ring row; a latent layer's latents and rotary
    keys as the full layers' K and V. A state layer has no rows: its state
    after the last real row and its conv tail are written whole, so
    nothing of the slot's last request is left."""
    zero = jnp.zeros((), jnp.int32)
    k_cache, v_cache = dict(k_cache), dict(v_cache)
    for cache, pf in ((k_cache, pf_k), (v_cache, pf_v)):
        for kind, rows in pf.items():
            if kind == "ssm":
                # state (1, H, P, N) at [slot]; tail (taps - 1, 1, channels) at [:, slot]
                cache[kind] = tuple(
                    jax.lax.dynamic_update_slice(
                        c, r.astype(c.dtype),
                        (slot,) + (zero,) * 3 if c.ndim == 4 else (zero, slot, zero),
                    )
                    for c, r in zip(cache[kind], rows)
                )
                continue
            if kind == "window":
                R, Pb = cache[kind].shape[2], rows.shape[2]
                r = jnp.arange(R, dtype=jnp.int32)
                last = true_len.astype(jnp.int32) - 1
                held = last - ((last - r) % R)  # newest prompt position at row r
                rows = rows[:, :, jnp.clip(held, 0, Pb - 1)]  # (Lw, 1, R, ...)
            cache[kind] = jax.lax.dynamic_update_slice(
                cache[kind], rows.reshape(rows.shape[:3] + (-1,)).astype(cache[kind].dtype),
                (zero, slot, zero, zero),
            )
    return k_cache, v_cache


# -- the block -----------------------------------------------------------------
#: Leaves of an expert layer outside its routed experts, by mlp index.
_MOE_OWN = ("router", "router_bias", "latent_down", "latent_up", "shared_wi", "shared_wo2")


def _layer_leaves(blocks: Dict[str, Any], ls: LayerSpec) -> Dict[str, Any]:
    """This layer's leaves, under names without the kind's prefix (the
    experts' weights stay stacked: see ``moe_ffn_held``'s ``layer``)."""
    out: Dict[str, Any] = {}
    if ls.mixer:
        out["ln1_g"] = blocks["ln1_g"][ls.norm1_index]
        p = _MIXER_PREFIX[ls.mixer] + "_"
        out.update({k[len(p):]: w[ls.mixer_index] for k, w in blocks.items() if k.startswith(p)})
    if ls.side:
        # the second mixer's leaves under a key of their own: both kinds have a ``wo``
        p = _MIXER_PREFIX[ls.side] + "_"
        out["side"] = {k[len(p):]: w[ls.side_index] for k, w in blocks.items() if k.startswith(p)}
    if ls.mlp:
        out["ln2_g"] = blocks["ln2_g"][ls.norm2_index]
    if ls.mlp == "dense":
        out.update(wi=blocks["dense_wi"][ls.mlp_index], wo2=blocks["dense_wo2"][ls.mlp_index])
    elif ls.mlp == "experts":
        out.update({k: blocks["moe_" + k][ls.mlp_index] for k in _MOE_OWN if "moe_" + k in blocks})
        out["wi"], out["wo2"] = blocks["moe_wi"], blocks["moe_wo2"]
    return out


def _scaled(x: jax.Array, scale: float) -> jax.Array:
    """``x * scale`` in x's dtype; x itself, and no multiply in the
    program, where the scale is 1 (``GPTConfig.multiplier``: a Python
    float, read while the program is traced)."""
    return x if scale == 1.0 else x * jnp.asarray(scale, x.dtype)


def _mlp(x: jax.Array, wi: jax.Array, wo: jax.Array, cfg: Any, gate: float = 1.0, out: float = 1.0) -> jax.Array:
    """x (..., D) through ``wi`` (gates, D, F) and ``wo`` (F, D'): SwiGLU
    with two gates, ``relu(up x)^2`` with one. ``gate`` multiplies the
    first input matrix's result before the activation, ``out`` the
    result (a dense layer's muP scalars)."""
    from ray_lightning_tpu.parallel.moe import mlp_act

    cdt = jnp.dtype(cfg.compute_dtype)
    z = jnp.einsum("...d,cdf->...cf", x, wi.astype(cdt))
    if gate != 1.0:
        z = z * jnp.asarray([gate] + [1.0] * (z.shape[-2] - 1), z.dtype)[:, None]
    return _scaled(jnp.einsum("...f,fd->...d", mlp_act(z, cfg.mlp_variant), wo.astype(cdt)), out)


def _attention_part(h, lp, ls, cfg, rope, pos, caches, live=None, u=None, real_rows=None):
    """``(attention's write into the residual, kv)`` of one attention layer
    (``u``: the layer's normed input where the caller has it already, a
    parallel layer's; else it is ``RMSNorm(h; ln1_g)``).
    With no cache the rows attend among themselves: the window kind in
    blocks of ``attn_window`` rows (:func:`_attend_rows_window`), the full
    kind a block of 512 queries at a time against its causal prefix in XLA
    (:func:`_attend_rows_full`) or, where the rows are a prefill's
    (``real_rows``) and :func:`prefill_kernel` says so — a TPU, no sink
    logit, the bucket at or over the crossing —, in the forward flash
    kernel, which takes ``k`` and ``v`` at their ``G`` KV heads as they
    are. In decode the read after the row's write is the one
    ``models/layers.py:decode_rows_block`` names for the layer's kind: the
    decode kernel (``ops/decode_attention.py:decode_attention``) over the
    ``live`` slots' row blocks ``0 .. pos`` — a full kind without a sink
    logit, on a TPU; a slot that is not live reads zeros — or
    :func:`_attend_cache` over every allocated row, which ``live`` does
    not reach: the ring, a kind with a sink, the CPU."""
    cdt = jnp.dtype(cfg.compute_dtype)
    B, S, _ = h.shape
    G = kv_heads(cfg, ls.mixer)
    window = cfg.attn_window if ls.mixer == "window" else 0
    with jax.named_scope("attn_" + ls.mixer):
        a = _scaled(_rmsnorm(h, lp["ln1_g"], cfg.norm_eps) if u is None else u, cfg.multiplier("attn_in"))
        q = jnp.einsum("bsd,dhk->bshk", a, lp["wq"].astype(cdt))
        k = _scaled(jnp.einsum("bsd,dhk->bshk", a, lp["wk"].astype(cdt)), cfg.multiplier("key"))
        v = jnp.einsum("bsd,dhk->bshk", a, lp["wv"].astype(cdt))
        if cfg.pos_embed == "rope":
            q = _rope(q, rope[ls.mixer], cfg.rope_interleave)
            k = _rope(k, rope[ls.mixer], cfg.rope_interleave)
        if cfg.attn_value_scale != 1.0:
            v = v * jnp.asarray(cfg.attn_value_scale, v.dtype)
        q = q.reshape(B, S, G, cfg.n_head // G, q.shape[-1])
        sink = lp["sink"].reshape(G, -1) if "sink" in lp else None
        if caches is None:
            kv: Any = (k, v)
            o = (
                _attend_rows_window(q, k, v, sink, window)
                if window
                else _attend_rows(cfg, ls.mixer, q, k, v, sink, real_rows)
            )
        else:
            k_cache, v_cache = dict(caches[0]), dict(caches[1])
            ring = ls.mixer == "window"
            # a full layer's position past the end lands on the last row
            # (frozen slots: _write_cache_rows clamps); a ring row is
            # always in bounds
            row = pos % k_cache[ls.mixer].shape[2] if ring else pos
            k_cache[ls.mixer] = layers._write_cache_rows(
                k_cache[ls.mixer], ls.mixer_index, k[:, 0].reshape(B, -1), row
            )
            v_cache[ls.mixer] = layers._write_cache_rows(
                v_cache[ls.mixer], ls.mixer_index, v[:, 0].reshape(B, -1), row
            )
            kv = (k_cache, v_cache)
            block = layers.decode_rows_block(cfg, S, k_cache, v_cache, ls.mixer)
            if block:
                from ray_lightning_tpu.ops.decode_attention import decode_attention

                # the stacked caches whole: a layer sliced out for a custom call is a copy of it a step
                o = decode_attention(
                    q[:, 0].reshape(B, cfg.n_head, -1), k_cache[ls.mixer], v_cache[ls.mixer],
                    ls.mixer_index, pos, live, block=block,
                )[:, None]
            else:
                o = _attend_cache(
                    q, k_cache[ls.mixer][ls.mixer_index], v_cache[ls.mixer][ls.mixer_index],
                    pos, sink, window, ring,
                )
        out = jnp.einsum("bshk,hkd->bsd", o.astype(cdt), lp["wo"].astype(cdt))
        return _scaled(out, cfg.multiplier("attn_out")), kv


def _attend_latent_cache(cfg, q_lat, q_rope, c_cache, r_cache, li, pos, live=None):
    """One query row a slot against latent layer ``li`` of the caches (the
    dicts by kind) after this step's write: ``q_lat`` (B, H, rank) the
    queries taken into the latent, ``q_rope`` (B, H, rope_dim) their
    rotated parts, ``pos`` (B,) the queries' positions; the weighted
    latents ``softmax((q_lat · c + q_rope · k_r) / sqrt(qk)) · c`` over each
    slot's positions ``0 .. pos``, (B, H, rank) in the compute dtype.
    ``models/layers.py:decode_rows_block`` says which read:

    - the XLA read: two matmuls over all ``S`` allocated rows of every
      slot, the scores, then the weighted sum — every latent is read
      twice whatever is live —, masked in between; ``live`` does not
      reach it;
    - one query row, ``attn_impl="flash"``, on a TPU: the decode kernel
      (``ops/decode_attention.py:latent_decode_attention``), which walks
      the slots that are ``live``, copies only the row blocks up to each
      one's position, each once for both products, and gives zeros for a
      slot that is not live. The same sums, the softmax's blockwise; p is
      float32 up to the product, whose operands the MXU takes rounded to
      bfloat16 (Mosaic's default contraction: PERF.md §6, PR 39) — what
      the XLA read's cast of p to the cache's dtype does."""
    scale = 1.0 / np.sqrt(qk_dim(cfg))
    block = layers.decode_rows_block(cfg, 1, c_cache, r_cache, "latent")
    if block:
        from ray_lightning_tpu.ops.decode_attention import latent_decode_attention

        # the keys with the positions minor: the bytes as the chip's compiler keeps 64-wide rows, so no copy
        return latent_decode_attention(
            q_lat, q_rope, c_cache["latent"], jnp.swapaxes(r_cache["latent"], 2, 3), li, pos, live,
            scale=scale, block=block,
        ).astype(q_lat.dtype)
    cc, rc = c_cache["latent"][li], r_cache["latent"][li]  # (B, rows, rank), (B, rows, rope_dim)
    s = (
        jnp.einsum("bhc,bsc->bhs", q_lat, cc, preferred_element_type=jnp.float32)
        + jnp.einsum("bhc,bsc->bhs", q_rope, rc, preferred_element_type=jnp.float32)
    ) * scale
    ok = jnp.arange(cc.shape[1], dtype=jnp.int32)[None, :] <= pos.astype(jnp.int32)[:, None]
    s = jnp.where(ok[:, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)  # row 0 is always allowed: no row is all -inf
    return jnp.einsum("bhs,bsc->bhc", p.astype(cc.dtype), cc)


def _latent_part(h, lp, ls, cfg, rope, pos, caches, live=None, real_rows=None):
    """``(a latent layer's write into the residual, kv)``. Every head's keys
    and values are projections ``wkv_b`` of one normed latent ``c`` a
    position, beside one rotary key the heads share: ``s_h = (q_nope,h ·
    wkv_b[K,h]^T c + rope(q_rope,h) · rope(k_r)) / sqrt(dqk)``.

    With no cache (forward, prefill) the keys and values are built from
    the latent, head by head, and the rows attend as the full kind's do
    (:func:`_attend_rows`: the blocked XLA read, or for a prefill's rows,
    ``real_rows``, on a TPU from the crossing up the forward flash kernel
    on the built keys and values, every head a KV head of its own, q·k as
    wide as ``qk_dim`` and v as ``v_dim``); ``kv`` is ``(c, rope(k_r))`` of
    every row, what the cache keeps. In
    decode ``wkv_b`` moves onto the query and the output instead (the same
    sums in another order): ``q^_h = wkv_b[K,h] q_nope,h`` scores against
    the cached latents as they lie, ``p`` weighs the latents themselves,
    and ``wkv_b[V,h]`` takes each head's weighted latent to its values. No
    key or value of a cached position is ever built. The read between the
    two is :func:`_attend_latent_cache`'s: on a TPU the decode kernel over
    the ``live`` slots' positions, elsewhere XLA's over every allocated
    row."""
    cdt = jnp.dtype(cfg.compute_dtype)
    B, S, _ = h.shape
    H, r, dn = cfg.n_head, cfg.kv_lora_rank, qk_dim(cfg) - rope_dim(cfg)
    wkv_b = lp["wkv_b"].astype(cdt)
    with jax.named_scope("attn_latent_prefill" if caches is None else "attn_latent_decode"):
        a = _rmsnorm(h, lp["ln1_g"], cfg.norm_eps)
        q = jnp.einsum("bsd,dhk->bshk", a, lp["wq"].astype(cdt))
        ckr = jnp.einsum("bsd,dc->bsc", a, lp["wkv_a"].astype(cdt))
        c = _rmsnorm(ckr[..., :r], lp["kv_g"], cfg.norm_eps)
        k_rope = _rope(ckr[:, :, None, r:], rope["latent"], cfg.rope_interleave)  # (B, S, 1, rope_dim)
        q_nope, q_rope = q[..., :dn], _rope(q[..., dn:], rope["latent"], cfg.rope_interleave)
        if caches is None:
            kv: Any = (c, k_rope[:, :, 0])
            kv_h = jnp.einsum("bsc,hck->bshk", c, wkv_b)
            k = jnp.concatenate([kv_h[..., :dn], jnp.broadcast_to(k_rope, (B, S, H, k_rope.shape[-1]))], axis=-1)
            qf = jnp.concatenate([q_nope, q_rope], axis=-1)[:, :, :, None]  # every head a KV head of its own
            o = _attend_rows(cfg, "latent", qf, k, kv_h[..., dn:], None, real_rows)
        else:
            c_cache, r_cache = dict(caches[0]), dict(caches[1])
            c_cache["latent"] = layers._write_cache_rows(c_cache["latent"], ls.mixer_index, c[:, 0], pos)
            r_cache["latent"] = layers._write_cache_rows(r_cache["latent"], ls.mixer_index, k_rope[:, 0, 0], pos)
            kv = (c_cache, r_cache)
            q_lat = jnp.einsum("bhk,hck->bhc", q_nope[:, 0], wkv_b[..., :dn])
            o_lat = _attend_latent_cache(cfg, q_lat, q_rope[:, 0], c_cache, r_cache, ls.mixer_index, pos, live)
            o = jnp.einsum("bhc,hck->bhk", o_lat, wkv_b[..., dn:])[:, None]
        return jnp.einsum("bshk,hkd->bsd", o.astype(cdt), lp["wo"].astype(cdt)), kv


def _state_part(h, lp, ls, cfg, caches, valid, u=None):
    """``(the state layer's write into the residual, kv)``: with no cache
    ``kv`` is the state after the last real row and the conv tail; in
    decode the live slots' own (``valid[:, 0]``) are advanced and go back
    where they were, and a slot that is not live keeps its state bit for
    bit (``models/ssm.py:ssm_step``: on a TPU its state is not even read).
    ``u``: the layer's normed input where the caller has it already."""
    from ray_lightning_tpu.models import ssm

    if u is None:
        u = _rmsnorm(h, lp["ln1_g"], cfg.norm_eps)
    if caches is None:
        out, state, tail = ssm.ssm_rows(u, lp, cfg, valid)
        return out, (state, tail)
    k_cache, v_cache = dict(caches[0]), dict(caches[1])
    i = ls.mixer_index
    out, state, tail = ssm.ssm_step(
        u, lp, cfg, k_cache["ssm"][i], v_cache["ssm"][i], None if valid is None else valid[:, 0])
    k_cache["ssm"] = k_cache["ssm"][:i] + (state,) + k_cache["ssm"][i + 1:]
    v_cache["ssm"] = v_cache["ssm"][:i] + (tail,) + v_cache["ssm"][i + 1:]
    return out, (k_cache, v_cache)


def _parallel_part(h, lp, ls, cfg, rope, pos, caches, valid, real_rows=None):
    """``(A(u) + M(u), kv)`` of a parallel layer: full attention and a state
    layer on the one normed input ``u = RMSNorm(h; ln1_g)``. With no cache
    ``kv`` is ``{"full": (k, v), "ssm": (state, tail)}``; in decode the
    attention half writes its row into the caches it is given and the
    state half advances its state in what comes back, so ``kv`` is the
    pair of dicts with both kinds replaced."""
    from dataclasses import replace

    with jax.named_scope("parallel"):
        u = _rmsnorm(h, lp["ln1_g"], cfg.norm_eps)
        a, kv_a = _attention_part(
            h, lp, ls, cfg, rope, pos, caches, None if valid is None else valid[:, 0], u=u, real_rows=real_rows,
        )
        side = replace(ls, mixer=ls.side, mixer_index=ls.side_index, side=None)
        m, kv_m = _state_part(h, lp["side"], side, cfg, caches and kv_a, valid, u=u)
    return a + m, (kv_m if caches else {ls.mixer: kv_a, ls.side: kv_m})


def _experts_part(m, lp, ls, cfg, valid):
    """The expert layer over m (B, S, D), its normed input: the routed
    experts this process holds, in the latent if the model has one, beside
    the shared expert on the whole input; ``(out, moe_stats)``."""
    from ray_lightning_tpu.parallel.moe import moe_ffn_held

    cdt = jnp.dtype(cfg.compute_dtype)
    B, S, D = m.shape
    t = m.reshape(B * S, D)
    a = None
    if "latent_down" in lp:
        with jax.named_scope("moe_latent_down"):
            a = jnp.einsum("td,de->te", t, lp["latent_down"].astype(cdt))
    out, stats = moe_ffn_held(
        {"wo" if k == "wo2" else k: lp[k] for k in ("router", "router_bias", "wi", "wo2") if k in lp},
        t, held=experts_held(cfg), top_k=cfg.moe_top_k, scoring=cfg.moe_scoring,
        compute_dtype=cdt, layer=ls.mlp_index, expert_in=a, variant=cfg.mlp_variant,
        scale=cfg.moe_routed_scale, valid=None if valid is None else valid.reshape(B * S),
    )
    if a is not None:
        with jax.named_scope("moe_latent_up"):
            out = jnp.einsum("te,ed->td", out.astype(cdt), lp["latent_up"].astype(cdt))
    if "shared_wi" in lp:
        with jax.named_scope("moe_shared"):
            out = out + _mlp(t, lp["shared_wi"], lp["shared_wo2"], cfg)
    return out.reshape(B, S, D), stats


def mixed_block(
    h: jax.Array,
    lp: Dict[str, Any],
    ls: LayerSpec,
    cfg: Any,
    rope: Dict[str, Tuple[jax.Array, jax.Array]],
    pos: Optional[jax.Array] = None,
    caches: Optional[Tuple[Dict[str, Any], Dict[str, Any]]] = None,
    valid: Optional[jax.Array] = None,
    real_rows: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Any, jax.Array]:
    """One layer over h (B, S, D) -> ``(h, kv, moe_stats)``: its mixer if
    it has one (a parallel layer's two, summed), then its MLP if it has
    one, each under its own norm.

    ``caches`` None: the S rows are a sequence from its start (forward,
    prefill) and ``kv`` is what the mixer leaves of them: an attention
    layer's ``(k, v)`` at its KV width (a latent layer's ``(latents,
    rotary keys)``), a state layer's ``(state, conv tail)`` after the last
    real row, a parallel layer's both by kind, ``{"full": (k, v), "ssm":
    (state, conv tail)}``. ``caches = (k_cache, v_cache)``:
    decode, S = 1 and ``pos`` (B,) each slot's position; the slot's K/V
    row, or its state and tail, are replaced in the caches and ``kv`` is
    the updated pair. A layer without a mixer hands back ``caches`` (None
    with no cache). ``valid`` (B, S) bool marks the real tokens for the
    state layer and the expert layer. ``real_rows`` (int32 scalar, with no
    cache): the rows are a prefill's and that many of them real, so a full
    or latent layer's attention asks :func:`prefill_kernel` which read. ``moe_stats`` is
    :func:`moe_ffn_held`'s (zeros for any other layer)."""
    kv, stats = caches, jnp.zeros((3,), jnp.int32)
    if ls.side:
        out, kv = _parallel_part(h, lp, ls, cfg, rope, pos, caches, valid, real_rows)
        h = h + out
    elif ls.mixer == "ssm":
        out, kv = _state_part(h, lp, ls, cfg, caches, valid)
        h = h + out
    elif ls.mixer:
        part = _latent_part if ls.mixer == "latent" else _attention_part
        out, kv = part(h, lp, ls, cfg, rope, pos, caches, None if valid is None else valid[:, 0], real_rows=real_rows)
        h = h + out
    if ls.mlp:
        m = _rmsnorm(h, lp["ln2_g"], cfg.norm_eps)
        if ls.mlp == "dense":
            with jax.named_scope("mlp"):
                out = _mlp(m, lp["wi"], lp["wo2"], cfg, cfg.multiplier("mlp_gate"), cfg.multiplier("mlp_out"))
        else:
            out, stats = _experts_part(m, lp, ls, cfg, valid)
        h = h + out
    return h, kv, stats


def _rope_by_kind(cfg: Any, pos: jax.Array) -> Dict[str, Tuple[jax.Array, jax.Array]]:
    """The rotation tables of positions ``pos`` (B, S), one pair a kind of
    attention the model has: computed once, shared by its layers (none
    for attention without positions)."""
    if cfg.pos_embed != "rope":
        return {}
    return {
        kind: _rope_tables(pos, rope_theta(cfg, kind), rope_dim(cfg))
        for kind in ATTN_KINDS if count_kind(cfg, kind)
    }


def mixed_logits(h: jax.Array, params: Dict[str, Any], cfg: Any) -> jax.Array:
    """Float32 logits of final-normed hidden states h (..., D): the untied
    head's, times the ``lm_head`` multiplier where the model has one."""
    return _scaled(_lm_head(h, params["lm_head"]), cfg.multiplier("lm_head"))


def mixed_rows(
    params: Dict[str, Any], cfg: Any, tokens: jax.Array, true_len: Optional[jax.Array] = None,
    prefill: bool = False,
) -> Tuple[jax.Array, Dict[str, Any], Dict[str, Any], jax.Array]:
    """The layers over tokens (B, S) with no cache: pre-final-norm hidden
    states, what the rows leave behind in its two halves by kind — K and V
    ``{kind: (Lk, B, S, Hkv, d)}`` (the latent kind's latents and rotary
    keys ``(Lk, B, S, width)``), and under "ssm" the tuples of states
    and conv tails (:func:`write_prefill_rows` takes both) — and ``[pairs
    routed, pairs on held experts, held experts hit, rows, real rows]``
    (int32; the expert layers' ``moe_stats`` summed). ``true_len``
    (scalar): only the first ``true_len`` rows are real (a right-padded
    prompt). ``prefill``: nobody differentiates these rows (an admission,
    ``gpt_prefill``), so the full and latent layers' attention may take the
    forward flash kernel (:func:`prefill_kernel` says when), which leaves
    the hidden states of query blocks past ``true_len`` unspecified; the
    forward pass (``gpt_forward``) keeps the XLA read, which has a
    gradient."""
    from ray_lightning_tpu.utils.quantize import embed_rows

    B, S = tokens.shape
    cdt = jnp.dtype(cfg.compute_dtype)
    h = _scaled(embed_rows(params["wte"], tokens).astype(cdt), cfg.multiplier("embedding"))
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    rope = _rope_by_kind(cfg, pos)
    valid = None if true_len is None else pos < true_len
    real_rows = jnp.asarray(S if true_len is None else true_len, jnp.int32) if prefill else None
    ks: Dict[str, List[jax.Array]] = {}
    vs: Dict[str, List[jax.Array]] = {}
    stats = jnp.zeros((3,), jnp.int32)
    for ls in layer_specs(cfg):
        lp = _layer_leaves(params["blocks"], ls)
        h, kv, st = mixed_block(h, lp, ls, cfg, rope, valid=valid, real_rows=real_rows)
        for kind, (k, v) in (kv if ls.side else {ls.mixer: kv} if ls.mixer else {}).items():
            ks.setdefault(kind, []).append(k if kind == "ssm" else k.astype(cdt))
            vs.setdefault(kind, []).append(v.astype(cdt))
        stats = stats + st
    real = jnp.asarray(B * S, jnp.int32) if valid is None else valid.sum().astype(jnp.int32)
    stats = jnp.concatenate([stats, jnp.stack([jnp.asarray(B * S, jnp.int32), real])])
    return (
        h, {a: tuple(x) if a == "ssm" else jnp.stack(x) for a, x in ks.items()},
        {a: tuple(x) if a == "ssm" else jnp.stack(x) for a, x in vs.items()}, stats,
    )


def mixed_decode_step(
    params: Dict[str, Any], cfg: Any, cur: jax.Array, pos: jax.Array,
    k_cache: Dict[str, Any], v_cache: Dict[str, Any], active: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Dict[str, Any], Dict[str, Any], jax.Array]:
    """``gpt_decode_step`` for mixed layers: one token a slot at per-slot
    positions through the caches; float32 logits (B, V), the caches and
    the summed ``moe_stats``. ``active`` (B,) bool: idle lanes route to
    no expert (their logits are not read; a state layer advances their
    own state, which the next admission into the slot overwrites)."""
    from ray_lightning_tpu.utils.quantize import embed_rows

    cdt = jnp.dtype(cfg.compute_dtype)
    h = _scaled(embed_rows(params["wte"], cur).astype(cdt), cfg.multiplier("embedding"))[:, None]  # (B, 1, D)
    rope = _rope_by_kind(cfg, pos[:, None])
    valid = None if active is None else active[:, None]
    stats = jnp.zeros((3,), jnp.int32)
    for ls in layer_specs(cfg):
        lp = _layer_leaves(params["blocks"], ls)
        h, (k_cache, v_cache), st = mixed_block(
            h, lp, ls, cfg, rope, pos=pos, caches=(k_cache, v_cache), valid=valid,
        )
        stats = stats + st
    with jax.named_scope("lm_head"):
        h = _rmsnorm(h[:, 0], params["lnf_g"], cfg.norm_eps)
        logits = mixed_logits(h, params, cfg)
    return logits, k_cache, v_cache, stats
