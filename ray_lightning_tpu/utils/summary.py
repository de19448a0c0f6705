"""Model summary (PTL's ModelSummary / enable_model_summary analog).

PTL prints a per-module table of layer names, types, and parameter counts
when a fit starts. Params here are plain pytrees, so the summary groups by
pytree path prefix instead of nn.Module hierarchy — with the TPU-relevant
additions: per-group dtype, on-device bytes, and (for placed jax.Arrays)
whether leaves are sharded or replicated across the mesh.
"""
from __future__ import annotations

from typing import Any, Dict, List, Tuple


def _key_name(k: Any) -> str:
    """DictKey -> key, SequenceKey -> idx, GetAttrKey -> name, else str."""
    for attr in ("key", "idx", "name"):
        if hasattr(k, attr):
            return str(getattr(k, attr))
    return str(k)


def _walk(params: Any) -> List[Tuple[Tuple[str, ...], Any]]:
    import jax

    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    return [(tuple(_key_name(k) for k in path), leaf) for path, leaf in flat]


def _placement(leaf: Any) -> str:
    sh = getattr(leaf, "sharding", None)
    if sh is None:
        return "host"
    try:
        return "replicated" if sh.is_fully_replicated else "sharded"
    except Exception:  # noqa: BLE001 - exotic shardings: just say placed
        return "device"


def summarize_params(params: Any, depth: int = 1) -> str:
    """Human-readable parameter table, grouped by path prefix.

    ``depth`` controls grouping granularity (1 = top-level keys). Returns a
    string; callers decide where to print (the loop does it rank-0 only,
    to stderr — stdout is a data channel for CLI pipelines).
    """
    import numpy as np

    rows: Dict[str, Dict[str, Any]] = {}
    total = 0
    total_bytes = 0
    placements = set()
    for path, leaf in _walk(params):
        group = ".".join(path[:depth]) if path else "(root)"
        shape = tuple(getattr(leaf, "shape", ()) or ())
        n = int(np.prod(shape, initial=1))
        dtype = str(getattr(leaf, "dtype", "?"))
        nbytes = n * int(getattr(getattr(leaf, "dtype", None), "itemsize", 4) or 4)
        row = rows.setdefault(
            group, {"params": 0, "bytes": 0, "dtypes": set(), "place": set()}
        )
        row["params"] += n
        row["bytes"] += nbytes
        row["dtypes"].add(dtype)
        row["place"].add(_placement(leaf))
        placements |= row["place"]
        total += n
        total_bytes += nbytes

    def fmt_n(n: int) -> str:
        for unit, div in (("B", 1e9), ("M", 1e6), ("K", 1e3)):
            if n >= div:
                return f"{n / div:.1f} {unit}"
        return str(n)

    # The placement column only appears once something is device-placed —
    # a host-side numpy tree prints the compact classic table.
    show_place = placements - {"host"}
    name_w = max([len(g) for g in rows] + [5])
    head = f"{'name':<{name_w}} | {'params':>8} | {'bytes':>8} | dtype"
    if show_place:
        head += " | placement"
    lines = [head, "-" * len(head)]
    for group, row in rows.items():
        line = (
            f"{group:<{name_w}} | {fmt_n(row['params']):>8} | "
            f"{fmt_n(row['bytes']):>8} | {','.join(sorted(row['dtypes']))}"
        )
        if show_place:
            line += f" | {','.join(sorted(row['place']))}"
        lines.append(line)
    lines.append("-" * len(head))
    lines.append(
        f"{'total':<{name_w}} | {fmt_n(total):>8} | {fmt_n(total_bytes):>8} |"
        f" {len(rows)} groups"
    )
    return "\n".join(lines)
