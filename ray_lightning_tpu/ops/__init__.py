"""TPU compute ops: Pallas kernels and mesh collectives for the hot path.

The reference has no kernels of its own — its hot loop is torch/NCCL
(SURVEY.md §2b). This package is the TPU build's native compute layer:

- ``attention``: plain-XLA reference attention (ground truth + fallback).
- ``flash_attention``: Pallas online-softmax attention kernel (TPU MXU
  tiling; interpret mode on CPU for tests).
- ``decode_attention``: Pallas decode kernel over a cache of rows — one
  query row a slot, only the row blocks up to each live slot's position
  (imported from its module by ``models/gpt.py``: the function shares
  the module's name) — and its twin over a latent layer's latents and
  shared rotary keys, ``latent_decode_attention`` (``models/mixed.py``).
- ``ring_attention``: sequence-parallel blockwise attention over a mesh
  axis (ICI ``ppermute`` ring) for long-context training.
- ``zigzag_attention``: load-balanced causal ring attention — zigzag chunk
  assignment removes the causal-mask FLOP waste (~2x at large ring sizes)
  and keeps every rank's per-tick work identical.
"""
from ray_lightning_tpu.ops.attention import attention_reference
from ray_lightning_tpu.ops.flash_attention import flash_attention
from ray_lightning_tpu.ops.ring_attention import ring_attention, ring_self_attention
from ray_lightning_tpu.ops.zigzag_attention import (
    zigzag_ring_attention,
    zigzag_ring_self_attention,
)

__all__ = [
    "attention_reference",
    "flash_attention",
    "ring_attention",
    "ring_self_attention",
    "zigzag_ring_attention",
    "zigzag_ring_self_attention",
]
