"""Seeded weights, made on the device in one jitted call.

The tree's leaves are the family's (``families/<model_type>.py``), in the
layout the program takes (per-layer leaves stacked on a leading layer
axis): that layout is the interface between the benchmark and the
system, as a checkpoint format would be. The values are
the benchmark's own: GPT-2's initialisation (normal, std 0.02, residual
writes scaled by 1/sqrt(2L)), norm gains near one and, for the GPT-2
family, small biases, so that no term of the mathematics is multiplied by
exactly one or zero. The program and the reference both call this, each
in its own process, and get the same bits.
"""
from __future__ import annotations

from typing import Any, Dict

from pb.plug import family_of

STD = 0.02


def seed_key(seed: int) -> Any:
    """A PRNG key for any whole-number seed, also past 32 signed bits."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), (seed >> 31) & 0x7FFFFFFF
    )


def param_shapes(dims: Dict[str, Any], max_seq: int) -> Dict[str, Any]:
    """``name -> (shape, kind)``, by the family: kind is one of w (std),
    r (residual std), g (gain), b (bias), z (zeros: a leaf of the
    program's tree that the family does not use)."""
    return family_of(dims).param_shapes(dims, max_seq)


def _build(key: Any, dims: Dict[str, Any], max_seq: int, dtype: Any) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp
    import numpy as np

    res_std = STD / float(np.sqrt(2.0 * dims["layers"]))
    shapes = param_shapes(dims, max_seq)
    flat = [(k, v) for k, v in shapes.items() if k != "blocks"] + [
        ("blocks/" + k, v) for k, v in shapes["blocks"].items()
    ]
    out: Dict[str, Any] = {"blocks": {}}
    for i, (name, (shape, kind)) in enumerate(sorted(flat)):
        k = jax.random.fold_in(key, i)
        if kind == "z":
            leaf = jnp.zeros(shape, jnp.float32)
        elif kind == "g":
            leaf = 1.0 + STD * jax.random.normal(k, shape, jnp.float32)
        else:
            std = res_std if kind == "r" else STD
            leaf = std * jax.random.normal(k, shape, jnp.float32)
        leaf = leaf.astype(dtype)
        if name.startswith("blocks/"):
            out["blocks"][name[7:]] = leaf
        else:
            out[name] = leaf
    return out


def make_params(seed: int, dims: Dict[str, Any], max_seq: int, dtype: str, device: Any = None) -> Dict[str, Any]:
    """The whole tree in one jitted call on ``device`` (default: the
    first device of the default backend), in ``dtype``."""
    import jax
    import jax.numpy as jnp

    device = device or jax.devices()[0]
    fn = jax.jit(
        lambda key: _build(key, dims, max_seq, jnp.dtype(dtype)),
    )
    with jax.default_device(device):
        return fn(seed_key(seed))
