"""The ``gamma`` arrival process: gaps that are the stratified quantiles of
a Gamma distribution of shape 0.5 — burstier than a Poisson process's
exponential gaps (coefficient of variation ``1 / sqrt(0.5)`` = 1.41 where
Poisson has 1): many short gaps, a few long ones, as chat turns that come
in clumps do (BurstGPT, arXiv:2401.17644, fits Gamma gaps to such traces).

A Gamma variable of shape 1/2 and scale 1 is ``Z^2 / 2`` with ``Z``
standard normal, so its quantile at ``u`` is ``z((1 + u) / 2)^2 / 2`` with
``z`` the normal quantile: no special function is needed, and no other
shape is offered (a mix that names one is refused). The generator scales
the gaps to the span (``pb/traffic.py:arrival_gaps``), so only their
shape matters here. It also puts a request at the MIDDLE of its gap, so
the time between two requests is the mean of two gaps: between due times
the coefficient of variation is 1.0 with these gaps where the ``poisson``
mixes read 0.71 — burstier by the same ``sqrt(2)``.
"""
from __future__ import annotations

from statistics import NormalDist
from typing import Any, Dict, List


def raw_gaps(arrival: Dict[str, Any], n: int) -> List[float]:
    shape = float(arrival.get("shape", 0.5))
    if shape != 0.5:
        raise ValueError(f"the gamma arrival process has shape 0.5 (a squared normal's quantiles); the mix names {shape}")
    z = NormalDist().inv_cdf
    return [z((1.0 + (i + 0.5) / n) / 2.0) ** 2 / 2.0 for i in range(n)]
