"""The one traffic generator: a pure function of a mix file and a seed.

A mix names its size distributions, its arrival process and what its
requests share; the generator has the common ones built in
(``lognormal`` / ``fixed`` / ``uniform``; ``poisson`` / ``uniform``;
``none`` / ``prefix``) and finds any other by its name in
``generators/<name>.py`` (``pb/plug.py``), so a mix with a new shape
brings one small file and edits nothing.

Sizes and arrival gaps are the stratified quantiles of the mix's
distributions, arranged by the mix's own ``arrangement_seed``: every
``--seed`` offers the same requests at the same due times, with other
token contents (and other weights). The seed must not change the work:
on the chip, of six arrangements of one multiset five read a p95 time to
first token of 459.5-460.1 ms and one 566-611 (a slot-saturation episode
that the others do not have), while two runs of one arrangement agree to
0.1% (PERF.md, PR 23).
"""
from __future__ import annotations

import math
from statistics import NormalDist
from typing import Any, Callable, Dict, List

import numpy as np

from pb import plug


# -- the pieces a mix names ----------------------------------------------
def _lognormal(dist: Dict[str, Any], u: float) -> float:
    return float(dist["median"]) * math.exp(float(dist["sigma"]) * NormalDist().inv_cdf(u))


#: ``dist -> quantile(dist, u)``: the size at the u-th quantile, before clipping
SIZE_QUANTILES: Dict[str, Callable[[Dict[str, Any], float], float]] = {
    "lognormal": _lognormal,
    "fixed": lambda dist, u: float(dist["value"]),
    "uniform": lambda dist, u: int(dist["min"]) + (int(dist["max"]) - int(dist["min"])) * u,
}

#: ``process -> raw_gaps(arrival, n)``: n gaps in any unit (scaled to the span)
ARRIVAL_GAPS: Dict[str, Callable[[Dict[str, Any], int], List[float]]] = {
    # quantiles of the exponential: a Poisson process's gaps
    "poisson": lambda arrival, n: [-math.log(1.0 - (i + 0.5) / n) for i in range(n)],
    "uniform": lambda arrival, n: [1.0] * n,
}


def _piece(table: Dict[str, Any], name: str, attr: str) -> Any:
    if name in table:
        return table[name]
    return getattr(plug.module("generators", name), attr)


def stratified(dist: Dict[str, Any], n: int) -> List[int]:
    """``n`` whole sizes: the (i+0.5)/n quantiles of ``dist``, clipped."""
    lo, hi = int(dist["min"]), int(dist["max"])
    q = _piece(SIZE_QUANTILES, dist["dist"], "quantile")
    return [int(min(hi, max(lo, round(q(dist, (i + 0.5) / n))))) for i in range(n)]


def arrival_gaps(arrival: Dict[str, Any], n: int, span_s: float) -> List[float]:
    """``n`` gaps that sum to ``span_s``, in the shape of the process."""
    if n == 0:
        return []
    raw = _piece(ARRIVAL_GAPS, arrival["process"], "raw_gaps")(arrival, n)
    k = span_s / sum(raw)
    return [g * k for g in raw]


def _share_prefix(reqs: List[Dict[str, Any]], sharing: Dict[str, Any], seed: int, vocab: int) -> None:
    """``{"kind": "prefix", "groups": G, "prefix_tokens": P}``: every
    prompt opens with one of G system prompts of P tokens (made from the
    seed; which one a request takes goes round in due order) and keeps
    its own length, so the work is what the mix's sizes say."""
    g = np.random.default_rng([int(seed), 0x5A4E])
    groups, p = int(sharing["groups"]), int(sharing["prefix_tokens"])
    prefixes = g.integers(0, vocab, size=(groups, p))
    for i, r in enumerate(reqs):
        n = min(p, len(r["prompt"]) - 1)
        if n > 0:
            r["prompt"][:n] = prefixes[i % groups, :n].tolist()


SHARING: Dict[str, Callable[..., None]] = {"none": lambda reqs, sharing, seed, vocab: None, "prefix": _share_prefix}


def _phase(
    mix: Dict[str, Any], n: int, span_s: float, t0: float,
    rng: np.random.Generator, content: np.random.Generator, vocab: int, counted: bool,
) -> List[Dict[str, Any]]:
    prompts = np.asarray(stratified(mix["prompt_tokens"], n))[rng.permutation(n)]
    outs = np.asarray(stratified(mix["output_tokens"], n))[rng.permutation(n)]
    gaps = np.asarray(arrival_gaps(mix["arrival"], n, span_s))[rng.permutation(n)]
    # A request falls due at the middle of its gap, so the first is not at
    # the phase's very start and the last not at its very end.
    due = t0 + np.cumsum(gaps) - gaps / 2.0
    reqs = []
    for i in range(n):
        # the arrangement's generator draws as many numbers as the contents
        # do, so that a mix's arrangement does not depend on the vocabulary
        rng.integers(0, vocab, size=int(prompts[i]))
        reqs.append({
            "due_s": float(due[i]),
            "prompt": content.integers(0, vocab, size=int(prompts[i])).tolist(),
            "max_new_tokens": int(outs[i]),
            "counted": counted,
        })
    return reqs


def serve_schedule(
    mix: Dict[str, Any], seed: int, seconds: float, vocab: int
) -> List[Dict[str, Any]]:
    """Requests in due order. ``due_s`` is relative to the start of the
    window: lead-in requests (``counted`` false) fall due before 0 and
    bring the slots to steady occupancy; the others fall due in
    ``[0, seconds)``."""
    rate = float(mix["arrival"]["rate_rps"])
    lead = float(mix.get("lead_in_s", 0.0))
    rng = np.random.default_rng([int(mix.get("arrangement_seed", 0)), 0x5E12])
    content = np.random.default_rng([int(seed), 0xC0DE])
    n_lead = int(round(rate * lead))
    n_win = max(1, int(round(rate * seconds)))
    reqs = _phase(mix, n_lead, lead, -lead, rng, content, vocab, False) + _phase(
        mix, n_win, float(seconds), 0.0, rng, content, vocab, True
    )
    sharing = mix.get("sharing", "none")
    if isinstance(sharing, str):
        sharing = {"kind": sharing}
    _piece(SHARING, sharing["kind"], "share")(reqs, sharing, seed, vocab)
    return reqs


def fake_text(rows: int, seq: int, vocab: int, seed: int, noise: float = 0.05) -> np.ndarray:
    """Seeded synthetic corpus, ``(rows, seq + 1)`` int32: an affine token
    recurrence with occasional random flips (the benchmark's copy of the
    program's ``make_fake_text``; all rows differ by their start and
    their flips)."""
    g = np.random.default_rng([int(seed), 0x7E87])
    toks = np.empty((rows, seq + 1), dtype=np.int32)
    toks[:, 0] = g.integers(0, vocab, size=rows)
    flips = g.random((rows, seq)) < noise
    rand = g.integers(0, vocab, size=(rows, seq))
    for i in range(seq):
        nxt = (5 * toks[:, i].astype(np.int64) + 7) % vocab
        toks[:, i + 1] = np.where(flips[:, i], rand[:, i], nxt)
    return toks
