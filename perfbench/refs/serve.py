"""The reference's side of the ``serve`` kind: the same seeded weights in
the type they are served in, and one forward pass over each sampled
request's prompt with its served tokens."""
from __future__ import annotations

from typing import Any, Dict, List

from pb import reference, weights


def run(spec: Dict[str, Any], out_dir: str, devs: List[Any]) -> Dict[str, Any]:
    params = weights.make_params(spec["seed"], spec["dims"], spec["max_seq"], spec["dtype"])
    return {"reference": reference.serve_reference(
        params, spec["samples"], spec["dims"], spec["pad_to"], control=bool(spec.get("control")))}
