"""The window's share of what the replica records about WHOSE time its
loop spends: ``stats()["spans"]["riders_s"]`` (request-seconds behind
each span of the loop thread, for the requests that wait for a first
token and for the slots that decode) and ``stats()["latency"]`` (the
per-bucket counts of the time to first token, the time per output token
and the queue phase). Every number of both is monotone since the replica
was built, so the difference of the two ``stats()`` calls that bracket
the window is exactly the window — where the replica's own ``occupancy``
and ``ttft_queue_p95_s`` are over its last 512 samples whenever those
fell.

A program without the blocks (a parent commit from before them) gives
``None``, and each reader then reports nothing, as ``pb/spans.py`` does.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Sequence

#: spans of the loop thread in which it runs an admission: its own
#: bookkeeping with the prefill's dispatch, the sync on the prefill's first
#: token, and the chunks of a chunked prefill
ADMISSION = ("serve.sched.admit", "serve.engine.admit_wait", "serve.sched.prefill_chunks")


def riders(ctx: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """``{"seconds", "num_slots", "waiting": {span: request-seconds},
    "decoding": {span: ...}, "residual": {kind: {"rode_s", "ledger_s",
    "open_s"}}}`` of the window, or None if the program ships no riders.

    ``residual`` is the conservation check: what the spans were charged
    (``rode_s``) beside what the scheduler's ledger says the same requests
    spent — for ``decoding`` the ``decode`` (and ``ship``) phases of the
    requests closed in the window, for ``waiting`` the times to first token
    of the first tokens given in it (a request's ``queue`` + ``prefill``)
    — plus what the requests still open had accrued at its end less at its
    start (``open_s``)."""
    from pb import spans

    w = spans.window(ctx)
    p = ctx["program"]
    s0, s1 = (p.get("stats0") or {}), (p.get("stats1") or {})
    r0, r1 = (s0.get("spans") or {}).get("riders_s"), (s1.get("spans") or {}).get("riders_s")
    if w is None or r0 is None or r1 is None:
        return None
    out: Dict[str, Any] = {"seconds": w["seconds"], "num_slots": int(s1["num_slots"])}
    for kind in ("waiting", "decoding"):
        out[kind] = {k: v - r0[kind].get(k, 0.0) for k, v in r1[kind].items()}

    def grew(read: Any) -> float:
        return float(read(s1)) - float(read(s0))

    def phases(s: Dict[str, Any], names: Sequence[str]) -> float:
        return sum(v for k, v in (s.get("metrics") or {}).items()
                   if k.startswith("rlt_serve_phase_seconds_sum{") and any(f'phase="{n}"' in k for n in names))

    out["residual"] = {
        "waiting": {"ledger_s": grew(lambda s: s["latency"]["ttft"]["sum_s"])},
        "decoding": {"ledger_s": grew(lambda s: phases(s, ("decode", "ship")))},
    }
    for kind, row in out["residual"].items():
        row["rode_s"] = sum(out[kind].values())
        row["open_s"] = grew(lambda s: s["spans"]["riders_open_s"][kind])
    return out


def behind(ctx: Dict[str, Any], kind: str) -> Optional[Dict[str, Any]]:
    """Of the window's ``kind`` request-seconds (``"waiting"`` or
    ``"decoding"``): ``{"all_s", "admission_s", "by_span"}``, the split by
    span printed with the conservation residual; None without riders or
    without a second of them."""
    from pb import spans

    r = riders(ctx)
    if r is None:
        return None
    by_span = r[kind]
    all_s = sum(by_span.values())
    if all_s <= 0.0:
        return None
    res = r["residual"][kind]
    ledger = res["ledger_s"] + res["open_s"]
    closed = {"waiting": "to the first tokens given in the window",
              "decoding": "of decode phases of the requests closed in the window"}[kind]
    print(f"{kind} request-seconds behind the loop's spans: {all_s:.3f} = {spans.split(by_span, 's', 1.0)}; the "
          f"ledger: {res['ledger_s']:.3f} s {closed} and {res['open_s']:+.3f} s more accrued by the requests still "
          f"{kind} at its end than at its start: residual {abs(all_s - ledger):.4f} s "
          f"({100.0 * abs(all_s - ledger) / ledger if ledger else 0.0:.3f}%)", flush=True)
    return {"all_s": all_s, "admission_s": sum(by_span.get(k, 0.0) for k in ADMISSION), "by_span": by_span}


def percentile(le: Sequence[float], counts: Sequence[int], q: float) -> Optional[float]:
    """The q-th percentile (0..100) of a histogram's per-bucket counts
    (``counts[i]`` values in ``(le[i-1], le[i]]``, the last past every
    bound), linear inside the bucket it falls in; None of no values. A
    value in the last bucket reads as the last bound: no less than that."""
    n = sum(counts)
    if n <= 0:
        return None
    rank, below = n * q / 100.0, 0
    for i, c in enumerate(counts):
        if c and below + c >= rank:
            if i >= len(le):
                return float(le[-1])
            lo = float(le[i - 1]) if i else 0.0
            return lo + (float(le[i]) - lo) * (rank - below) / c
        below += c
    return float(le[-1])


def tail(ctx: Dict[str, Any], row: str, q: float = 95.0) -> Optional[Dict[str, float]]:
    """``{"p_ms", "mean_ms", "n"}`` of the window's values of
    ``stats()["latency"][row]``: the q-th percentile of the bucket counts'
    difference and the mean from the sums'; None without the block or
    without a value in the window."""
    p = ctx["program"]
    l0 = ((p.get("stats0") or {}).get("latency") or {}).get(row)
    l1 = ((p.get("stats1") or {}).get("latency") or {}).get(row)
    if not l0 or not l1:
        return None
    counts = [b - a for a, b in zip(l0["counts"], l1["counts"])]
    n = l1["count"] - l0["count"]
    p_s = percentile(l1["le"], counts, q)
    if p_s is None or n <= 0:
        return None
    return {"p_ms": 1000.0 * p_s, "mean_ms": 1000.0 * (l1["sum_s"] - l0["sum_s"]) / n, "n": n}
