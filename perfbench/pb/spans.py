"""The window's share of the replica's ``stats()["spans"]``: what the
host did, by name, between the two ``stats()`` calls that bracket the
measured window. Every field of that block is monotone since the
replica was built, so the difference is exactly the window (unlike the
replica's occupancy and queue wait, which are over its last 512
samples whenever those fell).

A program without the block (a parent commit that predates the spans)
gives ``None``, and each reader then reports nothing.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

#: names on the replica's loop thread start with one of these; the RPC
#: thread's with ``serve.rpc.``
LOOP_PREFIXES = ("serve.loop.", "serve.sched.", "serve.engine.")
#: loop-thread spans that are not the host working: it has nothing to do, or
#: it is blocked on the device (a fold's tokens; an admission's key and first token)
BLOCKED_IN_ADMIT = ("serve.engine.key_wait", "serve.engine.admit_wait")
NOT_HOST_WORK = ("serve.loop.idle", "serve.engine.harvest_wait") + BLOCKED_IN_ADMIT


def window(ctx: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """``{"seconds", "segments": {name: {"n", "s"}}, "exposed_s": {name:
    s}, "work_s", "folds", "gc": {gen: {"n", "s"}}, "gc_max_s"}`` of the
    window, or None if the program ships no spans. ``gc_max_s`` is the
    longest pause since the replica was built (a maximum has no
    difference)."""
    p = ctx["program"]
    s0 = (p.get("stats0") or {}).get("spans")
    s1 = (p.get("stats1") or {}).get("spans")
    if not s0 or not s1:
        return None
    marks = p.get("marks") or {}
    if "stats0_s" in marks and "stats1_s" in marks:
        seconds = float(marks["stats1_s"]) - float(marks["stats0_s"])
    else:
        seconds = float(ctx["seconds"])
    if seconds <= 0:
        return None

    def rows(key: str) -> Dict[str, Dict[str, float]]:
        out = {}
        for name, row in s1[key].items():
            was = s0[key].get(name, {})
            out[name] = {"n": row["n"] - was.get("n", 0), "s": row["s"] - was.get("s", 0.0)}
        return out

    return {
        "seconds": seconds,
        "segments": rows("segments"),
        "exposed_s": {k: v - s0["exposed_s"].get(k, 0.0) for k, v in s1["exposed_s"].items()},
        "work_s": s1["work_s"] - s0["work_s"],
        "folds": s1["folds"] - s0["folds"],
        "gc": rows("gc"),
        "gc_max_s": max((row["max_s"] for row in s1["gc"].values()), default=0.0),
    }


def split(parts: Dict[str, float], unit: str = "ms", scale: float = 1000.0) -> str:
    """``name 1.234 ms, ...`` largest first, for a line of the run."""
    return ", ".join(f"{k} {scale * v:.3f} {unit}" for k, v in sorted(parts.items(), key=lambda kv: -kv[1]) if v > 0)
