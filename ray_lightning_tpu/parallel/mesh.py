"""Device mesh construction.

Replaces the reference's process-group bootstrap
(torch.distributed.init_process_group at ray_ddp.py:192-196): after
``jax.distributed.initialize``, every process sees the global device list and
builds the same Mesh; XLA routes collectives over ICI within a slice and DCN
across slices based on the mesh axes.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh


def local_chip_count() -> int:
    return len(jax.local_devices())


def parse_mesh_spec(spec: Union[str, int, None]) -> Tuple[int, int]:
    """Parse a serving mesh spec ``"MODELxDATA"`` into ``(model, data)``.

    The serve CLI's ``--serve.mesh`` vocabulary: ``"1x1"`` (single
    device), ``"4x1"`` (4-way tensor parallel), ``"4x2"``, or a bare
    integer/``"8"`` meaning ``8x1`` (model axis only — YAML coerces the
    undecorated form to int). Rejects anything else up front with the
    valid vocabulary, so a malformed flag fails before checkpoints load
    or replicas spawn. Whether the sizes actually factor the device
    count is :func:`build_mesh`'s check — that needs live devices, this
    one doesn't.
    """
    if spec is None:
        return (1, 1)
    if isinstance(spec, bool):  # YAML 1.1: a bare "on"/"off" typo
        raise ValueError(
            f"malformed mesh spec {spec!r}: use 'MODELxDATA' (e.g. '1x1', "
            "'4x1', '4x2') or a bare model-axis size like '8'"
        )
    if isinstance(spec, int):
        parts: Tuple[Union[str, int], ...] = (spec, 1)
    else:
        text = str(spec).strip().lower()
        parts = tuple(text.split("x")) if text else ()
        if len(parts) == 1:
            parts = (parts[0], 1)
    try:
        if len(parts) != 2:
            raise ValueError
        model, data = (int(p) for p in parts)
    except (TypeError, ValueError):
        raise ValueError(
            f"malformed mesh spec {spec!r}: use 'MODELxDATA' with positive "
            "integer axis sizes (e.g. '1x1', '4x1', '4x2'), or a bare "
            "model-axis size like '8'"
        ) from None
    if model < 1 or data < 1:
        raise ValueError(
            f"malformed mesh spec {spec!r}: 'MODELxDATA' axis sizes must "
            "be >= 1 (e.g. '1x1', '4x1', '4x2')"
        )
    return model, data


def mesh_from_spec(spec: Union[str, int, None]) -> Optional[Mesh]:
    """A serving ``("model", "data")`` mesh from a ``"MODELxDATA"`` spec.

    ``None``/``"1x1"`` (one device total) returns None — the engine's
    single-device path, byte-for-byte the pre-mesh behavior. Anything
    larger builds a mesh over ALL global devices; the sizes must factor
    the device count exactly (:func:`build_mesh` raises the friendly
    error naming both otherwise).
    """
    model, data = parse_mesh_spec(spec)
    if model * data == 1:
        return None
    return build_mesh((model, data), ("model", "data"))


def build_mesh(
    axis_shape: Optional[Sequence[int]] = None,
    axis_names: Tuple[str, ...] = ("data",),
) -> Mesh:
    """Build a Mesh over all global devices.

    Default: 1-D "data" mesh over every chip (pure DP). Multi-axis shapes
    (e.g. ``(dp, model)``) carve the same device list for DP x TP/FSDP; on
    multi-host topologies the leading axis should span hosts so per-step DP
    all-reduces ride ICI within a host first.

    Axes are ``Auto`` (GSPMD propagation): the strategies annotate inputs
    with NamedShardings and let the partitioner infer the rest — newer JAX
    defaults to ``Explicit`` sharding-in-types, which rejects the
    ZeRO-style mixed shardings these strategies rely on.
    """
    devices = jax.devices()
    if axis_shape is None:
        axis_shape = (len(devices),)
    total = 1
    for s in axis_shape:
        total *= s
    if total != len(devices):
        named = ", ".join(
            f"{n}={s}" for n, s in zip(axis_names, axis_shape)
        )
        raise ValueError(
            f"mesh shape ({named}) covers {total} device(s) but this "
            f"process sees {len(devices)}: the axis sizes must multiply to "
            f"EXACTLY the global device count. Pick sizes that factor "
            f"{len(devices)} (e.g. shrink an axis), or change the device "
            f"count — on CPU, virtual devices come from "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={total} set "
            f"before jax initializes."
        )
    axis_types = (jax.sharding.AxisType.Auto,) * len(axis_names)
    return jax.make_mesh(tuple(axis_shape), axis_names, axis_types=axis_types)


def setup_distributed(env) -> None:
    """Rendezvous this process with its peers (no-op single-host)."""
    if not env.is_distributed:
        return
    jax.distributed.initialize(
        coordinator_address=env.coordinator_address,
        num_processes=env.num_hosts,
        process_id=env.host_rank,
    )
