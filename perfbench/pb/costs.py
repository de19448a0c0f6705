"""Peaks of the chip and the operations and bytes an algorithm needs,
from shapes. Kept with the benchmark so that no PR which claims a gain
can move the yardstick.

A count here is what the *algorithm* needs (causal attention needs half
the square; a recomputation is not needed work), so a share of a peak
built on it cannot pass 100% by counting too much.
"""
from __future__ import annotations

from typing import Any, Dict

from pb.plug import family_of

#: Published peaks of one chip, keyed by ``device_kind`` as jax reports it.
#: Source: Google Cloud documentation, "TPU v5e" (system architecture):
#: 197 TFLOP/s bf16, 819 GB/s HBM, 16 GB HBM, 1,600 Gbit/s chip-to-chip.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bytes_per_s": 1600e9 / 8,
    },
}


def peaks(device_kind: str) -> Dict[str, float]:
    """The peaks of ``device_kind``. An unknown device is an error: a
    share of a guessed peak is not a measurement."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"device_kind {device_kind!r} is not in the benchmark's peaks "
            f"table (known: {sorted(PEAKS)})"
        ) from None


def matmul_params(dims: Dict[str, Any]) -> int:
    """Parameters that sit in a matrix multiplication on a token's path
    (the family counts them: ``families/<model_type>.py``)."""
    return family_of(dims).matmul_params(dims)


def total_params(dims: Dict[str, Any]) -> int:
    """All parameters held, by the family's count."""
    return family_of(dims).total_params(dims)


def attn_flops_per_token_fwd(dims: Dict[str, Any], seq: int) -> float:
    """Forward attention FLOPs per token at length ``seq``, by the family."""
    return family_of(dims).attn_flops_per_token_fwd(dims, seq)


def train_flops_per_token(dims: Dict[str, Any], seq: int) -> float:
    """Forward + backward FLOPs a token needs: 2 per matmul parameter and
    the attention above, times 3 (the backward is twice the forward)."""
    return 3.0 * (2.0 * matmul_params(dims) + attn_flops_per_token_fwd(dims, seq))


def flash_train_cost(
    batch: int, heads: int, seq: int, head_dim: int, layers: int,
    dtype_bytes: int = 2,
) -> Dict[str, float]:
    """FLOPs and HBM bytes of flash attention forward + backward for one
    training step on one device (FlashAttention's own accounting: the
    backward is 2.5x the forward, one recomputation of the scores
    included; causal, so half the square)."""
    fwd = 4.0 * batch * heads * seq * seq * head_dim / 2.0
    flops = 3.5 * fwd * layers
    # forward reads q, k, v and writes o; backward reads q, k, v, o, do and
    # writes dq, dk, dv.
    tensor = batch * heads * seq * head_dim * dtype_bytes
    return {"flops": flops, "bytes": 12.0 * tensor * layers}


def roofline_seconds(flops: float, nbytes: float, pk: Dict[str, float]) -> Dict[str, Any]:
    """The least time the chip could take, and which peak bounds it."""
    t_c = flops / pk["bf16_flops"]
    t_m = nbytes / pk["hbm_bytes_per_s"]
    return {"seconds": max(t_c, t_m), "bound": "compute" if t_c >= t_m else "memory"}


def decode_step_bytes(
    dims: Dict[str, Any], live_kv_tokens: float, weight_bytes: int = 2,
    kv_bytes: int = 2,
) -> float:
    """HBM bytes one decode step has to read: every block's weights and
    the output head once, and the keys and values of the tokens that are
    live in the batch (not of the whole allocation)."""
    per_token_kv = family_of(dims).kv_bytes_per_token(dims, kv_bytes)
    return matmul_params(dims) * weight_bytes + live_kv_tokens * per_token_kv
