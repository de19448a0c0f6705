"""The plain reference's shared pieces: matmuls in float32 at ``highest``
precision, norms, rotary positions, causal attention, the language-model
loss and its gradients, the AdamW step, and the statistics the output
check compares. No kernel, no cache, no batching trick; it imports
nothing of the program.

The forward pass of an architecture is its family's
(``families/<model_type>.py``, found through ``dims["family"]``), built
from these pieces. Departures from the published descriptions: none in
the mathematics; the per-layer weights arrive stacked on a leading axis
and the layers run under ``lax.scan`` with per-layer rematerialisation,
which changes what is stored, not what is computed.

``lowp`` puts the *control* in the reference's place: every matmul takes
its two operands rounded to float8 (e4m3, per-tensor absmax scaling) —
the nearest precision below the bfloat16 the configurations state.
"""
from __future__ import annotations

import math
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from pb.plug import family_of

F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST


def fake_fp8(x: jax.Array) -> jax.Array:
    """Round to float8 e4m3 with one scale per tensor; straight-through
    gradient."""
    s = jnp.max(jnp.abs(x)) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    q = (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s
    return x + jax.lax.stop_gradient(q - x)


def mm(eq: str, a: jax.Array, b: jax.Array, lowp: bool) -> jax.Array:
    if lowp:
        a, b = fake_fp8(a), fake_fp8(b)
    return jnp.einsum(eq, a, b, precision=_HI, preferred_element_type=F32)


def layernorm(x, g, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def rmsnorm(x, g, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * g


def rope(x: jax.Array, theta: float) -> jax.Array:
    """Rotate (B, S, H, hd) by position, half-split pairs (i, i + hd/2)."""
    S, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(S, dtype=F32)[:, None] * freqs[None]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(q, k, v, window: int, lowp: bool):
    """Causal softmax attention; q (B,S,H,hd), k and v (B,S,Hkv,hd)."""
    H, Hkv = q.shape[2], k.shape[2]
    if Hkv != H:
        k = jnp.repeat(k, H // Hkv, axis=2)
        v = jnp.repeat(v, H // Hkv, axis=2)
    S, hd = q.shape[1], q.shape[-1]
    s = mm("bqhd,bkhd->bhqk", q, k, lowp) / math.sqrt(hd)
    i = jnp.arange(S)[:, None]
    j = jnp.arange(S)[None, :]
    ok = j <= i
    if window:
        ok = ok & (j > i - window)
    s = jnp.where(ok[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return mm("bhqk,bkhd->bqhd", p, v, lowp)


def logits_of(params, tokens, dims, lowp: bool = False) -> jax.Array:
    """tokens (B, S) -> logits (B, S, V), by the family's forward pass."""
    return family_of(dims).logits(params, tokens, dims, lowp)


def lm_loss(params, rows: jax.Array, dims, lowp: bool = False) -> jax.Array:
    """Mean next-token cross entropy over rows (B, S + 1)."""
    lg = logits_of(params, rows[:, :-1], dims, lowp)
    lse = jax.scipy.special.logsumexp(lg, axis=-1)
    tgt = jnp.take_along_axis(lg, rows[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(lse - tgt)


# -- the optimizer, as optax.adamw under warmup_cosine_decay_schedule ----
def lr_at(count: int, opt: Dict[str, float]) -> float:
    """Learning rate of the update with 0-based index ``count``: linear
    from 0 to ``lr`` over ``warmup_steps``, then cosine to 0 at
    ``decay_steps``."""
    w = int(opt["warmup_steps"])
    total = max(w + 1, int(opt.get("decay_steps", 10_000)))
    if count < w:
        return float(opt["lr"]) * count / w
    c = min(count - w, total - w)
    return float(opt["lr"]) * 0.5 * (1.0 + math.cos(math.pi * c / (total - w)))


def leaf_names(tree: Dict[str, Any]) -> List[str]:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return ["/".join(str(getattr(k, "key", k)) for k in path) for path, _ in flat]


def leaf_norms(tree) -> Dict[str, float]:
    leaves = jax.tree_util.tree_leaves(tree)
    norms = jax.jit(lambda ls: [jnp.sqrt(jnp.sum(jnp.square(x.astype(F32)))) for x in ls])(leaves)
    return {n: float(v) for n, v in zip(leaf_names(tree), norms)}


def part_norms(name: str, x: jax.Array, split: Dict[str, Any]) -> Dict[str, float]:
    """The norm of one leaf, or of each part of a fused leaf that the
    family's ``SPLIT`` names (``leaf -> (axis, part names)``)."""
    x = x.astype(F32)
    if name not in split:
        return {name: float(jnp.sqrt(jnp.sum(jnp.square(x))))}
    axis, parts = split[name]
    other = tuple(a for a in range(x.ndim) if a != axis)
    norms = np.asarray(jnp.sqrt(jnp.sum(jnp.square(x), axis=other)))
    return {f"{name}.{p}": float(v) for p, v in zip(parts, norms)}


def view_norms(tree, split: Dict[str, Any]) -> Dict[str, float]:
    """``leaf_norms`` with fused leaves taken part by part."""
    out: Dict[str, float] = {}
    for n, x in zip(leaf_names(tree), jax.tree_util.tree_leaves(tree)):
        out.update(part_norms(n, x, split))
    return out


def noise_leaves(ref_grad_norms: Dict[str, float], floor: float = 1e-3) -> List[str]:
    """Leaves whose *reference* gradient is nothing but rounding: its
    norm lies under ``floor`` of the median leaf's (a gradient that is
    zero by the mathematics, such as the key bias's, reads about 1e-6 of
    it in float32). What Adam makes of such a gradient is noise at the
    size of a real update in any precision, so the parameter-change
    comparison leaves these out, and says so."""
    live = [v for v in ref_grad_norms.values() if v > 0]
    med = float(np.median(live)) if live else 0.0
    return sorted(k for k, v in ref_grad_norms.items() if v < floor * med)


SKETCH = 256


def leaf_sketches(tree) -> Dict[str, List[float]]:
    """A seeded linear sketch of every leaf: the leaf, under fixed random
    signs, summed into ``SKETCH`` buckets. Two processes that sketch two
    trees with it can compare them as vectors without exchanging them:
    for errors that are not aligned with the signs, the norm of the
    sketches' difference estimates the norm of the trees' difference (to
    about 1/sqrt(2 * SKETCH), 4%)."""
    leaves = jax.tree_util.tree_leaves(tree)

    def sk(x, i):
        x = x.astype(F32).reshape(-1)
        pad = (-x.shape[0]) % SKETCH
        x = jnp.pad(x, (0, pad))
        signs = jax.random.rademacher(jax.random.fold_in(jax.random.PRNGKey(0x5EED), i), x.shape, F32)
        return (x * signs).reshape(SKETCH, -1).sum(1)

    out = jax.jit(lambda ls: [sk(x, i) for i, x in enumerate(ls)])(leaves)
    return {n: [float(v) for v in np.asarray(a)] for n, a in zip(leaf_names(tree), out)}


def sketch_diff(prog: Dict[str, List[float]], ref: Dict[str, List[float]]) -> Tuple[float, str]:
    """Worst leaf of ||sketch(prog) - sketch(ref)|| / max(||sketch(ref)||
    of that leaf, of the median leaf)."""
    norms = {k: float(np.linalg.norm(v)) for k, v in ref.items()}
    med = float(np.median(list(norms.values())))
    worst, name = 0.0, ""
    for k, r in ref.items():
        if k not in prog:
            return float("inf"), k
        d = float(np.linalg.norm(np.asarray(prog[k]) - np.asarray(r))) / max(norms[k], med, 1e-30)
        if not d <= worst:
            worst, name = d, k
    return worst, name


def make_train_step(dims: Dict[str, Any], opt: Dict[str, float], batch: int, lowp: bool = False, micro: int = 2):
    """The jitted reference step ``(params, mu, nu, rows, t, lr) ->
    (loss, params, mu, nu)``: mean loss and gradient over ``rows``
    (batch, S + 1), taken ``micro`` rows at a time and accumulated, then
    one AdamW update as ``optax.adamw`` makes it."""
    b1, b2, eps, wd = 0.9, 0.999, 1e-8, float(opt["weight_decay"])
    micro = min(micro, batch)
    if batch % micro:
        raise ValueError(f"batch {batch} is not a multiple of the micro-batch {micro}")
    n = batch // micro

    def step(p, mu, nu, rows, t, lr):
        def acc(carry, r):
            l, g = jax.value_and_grad(lm_loss)(p, r, dims, lowp)
            return (carry[0] + l, jax.tree_util.tree_map(jnp.add, carry[1], g)), None

        zero = jax.tree_util.tree_map(jnp.zeros_like, p)
        (loss, g), _ = jax.lax.scan(acc, (jnp.zeros((), F32), zero), rows.reshape(n, micro, rows.shape[-1]))
        g = jax.tree_util.tree_map(lambda x: x / n, g)
        mu = jax.tree_util.tree_map(lambda m, x: b1 * m + (1 - b1) * x, mu, g)
        nu = jax.tree_util.tree_map(lambda v, x: b2 * v + (1 - b2) * x * x, nu, g)
        c1, c2 = 1 - b1 ** t, 1 - b2 ** t
        p = jax.tree_util.tree_map(
            lambda w, m, v: w - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps) + wd * w), p, mu, nu,
        )
        return loss / n, p, mu, nu

    return jax.jit(step, donate_argnums=(1, 2))


def train_reference(
    params: Dict[str, Any], batches: np.ndarray, dims: Dict[str, Any],
    opt: Dict[str, float], lowp: bool = False, micro: int = 2,
    place: Optional[Callable[[Any], Any]] = None,
) -> Dict[str, Any]:
    """Follow ``batches`` (K, B, S + 1) from ``params`` through K AdamW
    steps. Returns each step's loss and, after the K steps, the per-leaf
    norms of Adam's first moment (the gradients as the optimizer got
    them) and of the parameters' change."""
    step = make_train_step(dims, opt, batches.shape[1], lowp, micro)
    p = params
    mu = jax.tree_util.tree_map(jnp.zeros_like, params)
    nu = jax.tree_util.tree_map(jnp.zeros_like, params)
    if place is not None:
        mu, nu = place(mu), place(nu)
    losses = []
    for k in range(batches.shape[0]):
        loss, p, mu, nu = step(
            p, mu, nu, jnp.asarray(batches[k]), jnp.float32(k + 1), jnp.float32(lr_at(k, opt)),
        )
        losses.append(float(loss))
    delta = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.subtract, a, b))(p, params)
    split = getattr(family_of(dims), "SPLIT", {})
    return {
        "losses": losses,
        "mu_norms": leaf_norms(mu),
        "mu_sketch": leaf_sketches(mu),
        "mu_view_norms": view_norms(mu, split),
        "delta_norms": view_norms(delta, split),
    }


def serve_reference(
    params: Dict[str, Any], samples: List[Dict[str, Any]], dims: Dict[str, Any],
    pad_to: int, control: bool = False,
) -> Dict[str, Any]:
    """For each sampled request (``prompt`` and served ``tokens``) run the
    reference once over prompt + served tokens and read, at every served
    position, how far the served token's logit lies below the
    reference's best. With ``control``, also the gap of the token that
    the lower precision puts first at that position."""
    fwd = jax.jit(lambda p, t: logits_of(p, t, dims, False)[0])
    fwd_low = jax.jit(lambda p, t: jnp.argmax(logits_of(p, t, dims, True)[0], -1))
    gaps: List[float] = []
    ctl: List[float] = []
    agree = 0
    for s in samples:
        seq = list(s["prompt"]) + list(s["tokens"])
        P, n = len(s["prompt"]), len(s["tokens"])
        if len(seq) > pad_to:
            raise ValueError(f"sequence of {len(seq)} tokens exceeds pad_to={pad_to}")
        toks = np.zeros((1, pad_to), np.int32)
        toks[0, : len(seq)] = seq
        lg = fwd(params, jnp.asarray(toks))[P - 1 : P - 1 + n]
        best = jnp.max(lg, -1)
        served = jnp.take_along_axis(lg, jnp.asarray(s["tokens"])[:, None], -1)[:, 0]
        g = np.asarray(best - served)
        gaps.extend(float(x) for x in g)
        agree += int(np.sum(g == 0.0))
        if control:
            low = fwd_low(params, jnp.asarray(toks))[P - 1 : P - 1 + n]
            c = np.asarray(best - jnp.take_along_axis(lg, low[:, None], -1)[:, 0])
            ctl.extend(float(x) for x in c)
    out: Dict[str, Any] = {
        "tokens_compared": len(gaps),
        "widest_gap": max(gaps),
        "mean_gap": float(np.mean(gaps)),
        "greedy_agree_share": agree / max(1, len(gaps)),
    }
    if control:
        out["control_widest_gap"] = max(ctl)
        out["control_mean_gap"] = float(np.mean(ctl))
    return out


def norm_gaps(prog: Dict[str, float], ref: Dict[str, float], skip: Tuple[str, ...] = ()) -> Dict[str, float]:
    """Per leaf, |‖prog‖ − ‖ref‖| / max(‖ref‖ of that leaf, ‖ref‖ of the
    median leaf): some gradients are all but zero, and a gap there is
    measured against a leaf that is not. Leaves in ``skip`` (see
    ``noise_leaves``) are left out; a leaf the program lacks reads inf."""
    ref = {k: v for k, v in ref.items() if k not in skip}
    med = float(np.median(list(ref.values())))
    return {
        k: (abs(prog[k] - r) / max(r, med, 1e-30) if k in prog else float("inf"))
        for k, r in ref.items()
    }


def norm_gap(prog: Dict[str, float], ref: Dict[str, float], skip: Tuple[str, ...] = ()) -> Tuple[float, str]:
    """The worst leaf of ``norm_gaps``, and its name."""
    worst, name = 0.0, ""
    for k, g in norm_gaps(prog, ref, skip).items():
        if not g <= worst:
            worst, name = g, k
    return worst, name
