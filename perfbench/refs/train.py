"""The reference's side of the ``train`` kind: the same seeded weights,
the rows the program's first dispatch was fed, followed through as many
AdamW steps in float32 (and, for the control, once more in float8)."""
from __future__ import annotations

import os
from typing import Any, Dict, List

import numpy as np

from pb import reference, weights


def run(spec: Dict[str, Any], out_dir: str, devs: List[Any]) -> Dict[str, Any]:
    rows = np.load(os.path.join(out_dir, "first_rows.npy"))
    dims = spec["dims"]
    params = weights.make_params(spec["seed"], dims, spec["max_seq"], "float32")
    place = None
    if len(devs) > 1:
        params, place = _spread(params, devs)
    out = {"reference": reference.train_reference(
        params, rows, dims, spec["optimizer"], lowp=False, micro=spec.get("micro", 2), place=place)}
    if spec.get("control"):
        out["control"] = reference.train_reference(
            params, rows, dims, spec["optimizer"], lowp=True, micro=spec.get("micro", 2), place=place)
    return out


def _spread(params, devs):
    """On several chips the reference keeps each leaf split over them
    along its first axis that divides (the layer axis of the stacked
    leaves), so that float32 weights, gradients and both Adam moments of
    a model that one chip cannot hold fit; the arithmetic is unchanged."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.asarray(devs), ("x",))
    n = len(devs)

    def sh(a):
        for ax, d in enumerate(a.shape):
            if d % n == 0:
                return NamedSharding(mesh, P(*([None] * ax + ["x"])))
        return NamedSharding(mesh, P())

    def place(tree):
        return jax.tree_util.tree_map(lambda a: jax.device_put(a, sh(a)), tree)

    return place(params), place
