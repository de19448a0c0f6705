"""From a profiler trace to numbers: busy and idle time of the device,
time per operation and per executable, collective time that no compute
covers, and the longest idle gaps.

Two stages. ``load`` turns an ``.xplane.pb`` into plain lists of events
(the only place that knows the profiler's file); ``reduce`` is pure
arithmetic on those lists and is what the tests check on a small recorded
trace.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

from pb.stats import union_seconds

COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all|"
    r"collective-broadcast|^send|^recv", re.I,
)
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ASYNC_LINE = "Async XLA Ops"
MOSAIC = 'custom_call_target="tpu_custom_call"'


def find_xplane(trace_dir: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    return files[-1] if files else None


def load(path: str) -> Dict[str, Any]:
    """``{"devices": {plane: {"ops": [[name, start_s, dur_s]...],
    "modules": [...]}}, "lines": {plane: [line names]}}``; times in
    seconds on the trace's own clock."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out: Dict[str, Any] = {"devices": {}, "lines": {}}
    for plane in data.planes:
        out["lines"][plane.name] = [ln.name for ln in plane.lines]
        if not DEVICE_PLANE.match(plane.name):
            continue
        dev: Dict[str, List[Any]] = {"ops": [], "modules": [], "async": []}
        for line in plane.lines:
            key = {OPS_LINE: "ops", MODULES_LINE: "modules", ASYNC_LINE: "async"}.get(line.name)
            if key is None:
                continue
            for ev in line.events:
                # an operation's name is its whole HLO line: keep its head and,
                # for a Mosaic kernel, the mark that says so
                name = ev.name[:160] + (" " + MOSAIC if MOSAIC in ev.name[160:] else "")
                dev[key].append([name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9])
        out["devices"][plane.name] = dev
    return out


def _short(name: str) -> str:
    """A stable short name for an operation: the HLO instruction name
    without its numeric suffix (``%fusion.123 = ...`` -> ``fusion``). A
    Mosaic kernel is a custom call whose instruction name says little, so
    it is marked as what it is."""
    head = name.split(" = ")[0].strip().lstrip("%")
    head = re.sub(r"[.\-_]\d+$", "", head)[:80]
    return "tpu_custom_call/" + head if MOSAIC in name else head


def self_seconds(ops: Sequence[Sequence[Any]]) -> List[float]:
    """Exclusive time of each event of one line, in the order given: its
    duration less that of the events nested directly inside it (a
    ``while`` holds its body's operations). Events must be sorted by
    start, an enclosing event before what it holds."""
    out = [float(du) for _, _, du in ops]
    stack: List[Tuple[float, int]] = []
    for i, (_, s, du) in enumerate(ops):
        while stack and stack[-1][0] <= s + 1e-12:
            stack.pop()
        if stack:
            out[stack[-1][1]] -= du
        stack.append((s + du, i))
    return [max(0.0, x) for x in out]


def reduce(events: Dict[str, Any], top: int = 10, min_gap_s: float = 1e-4) -> Dict[str, Any]:
    """Busy/idle, per-operation and per-module time, exposed collective
    time and the longest gaps, averaged over the devices in the trace.

    ``window_s`` is the span from the first operation's start to the
    last one's end (the profiler takes seconds to start and stop, and the
    device does nothing of the program's in them); ``busy_s`` the union
    of the operations' intervals in it. Operation times are exclusive
    (see ``self_seconds``), so they add up to the busy time."""
    devs = events["devices"]
    busy: List[float] = []
    spans: List[float] = []
    exposed: List[float] = []
    coll_total: List[float] = []
    op_time: Dict[str, float] = {}
    mod_durs: Dict[str, List[float]] = {}
    gaps: List[Tuple[float, float, str]] = []
    for name, d in sorted(devs.items()):
        ops = sorted(d["ops"], key=lambda e: (e[1], -e[2]))
        if not ops:
            continue
        iv = [(s, s + du) for _, s, du in ops]
        t0, t1 = min(s for s, _ in iv), max(e for _, e in iv)
        busy.append(union_seconds(iv))
        spans.append(t1 - t0)
        selfs = self_seconds(ops)
        # a leaf holds no other event: what the core actually runs
        leaf = [abs(sf - du) <= 1e-9 + 1e-6 * du for sf, (_, _, du) in zip(selfs, ops)]
        comp = [(s, s + du) for (n, s, du), lf in zip(ops, leaf) if lf and not COLLECTIVE.search(_short(n))]
        coll = [(s, s + du) for (n, s, du), lf in zip(ops, leaf) if lf and COLLECTIVE.search(_short(n))]
        coll += [(s, s + du) for n, s, du in d.get("async", []) if COLLECTIVE.search(_short(n))]
        coll_total.append(union_seconds(coll))
        # collective time under no compute operation: |coll U comp| - |comp|
        exposed.append(union_seconds(comp + coll) - union_seconds(comp))
        for (n, _, _), sf in zip(ops, selfs):
            k = _short(n)
            op_time[k] = op_time.get(k, 0.0) + sf
        for n, _, du in d["modules"]:
            mod_durs.setdefault(_short(n), []).append(du)
        end = None
        for s, e in sorted(iv):
            if end is not None and s - end >= min_gap_s:
                gaps.append((end, s - end, name))
            end = e if end is None else max(end, e)
    n = len(busy)
    if n == 0:
        return {"devices": 0}
    gaps.sort(key=lambda g: -g[1])
    return {
        "devices": n,
        "busy_s": sum(busy) / n,
        "window_s": max(spans),
        "collective_s": sum(coll_total) / n,
        "collective_exposed_s": sum(exposed) / n,
        "op_seconds": {k: v / n for k, v in op_time.items()},
        "device_ops": [[k, v / n] for k, v in sorted(op_time.items(), key=lambda kv: -kv[1])[:top]],
        "modules": mod_durs,
        "module_spans": sorted(
            (s, s + du) for d in devs.values() for _, s, du in d["modules"]
        ),
        "gaps": [[s, du, dev] for s, du, dev in gaps[: 4 * top]],
    }


def match_seconds(op_seconds: Dict[str, float], patterns: Sequence[str]) -> float:
    """Summed time of the operations whose name matches any pattern."""
    rx = [re.compile(p, re.I) for p in patterns]
    return sum(v for k, v in op_seconds.items() if any(r.search(k) for r in rx))


def module_durations(modules: Dict[str, List[float]], patterns: Sequence[str]) -> List[float]:
    """Durations of the executables whose name matches any pattern."""
    rx = [re.compile(p, re.I) for p in patterns]
    return [d for name, ds in modules.items() if any(r.search(name) for r in rx) for d in ds]


def name_gaps(gaps: Sequence[Sequence[Any]], module_spans: Sequence[Sequence[float]], between: str, top: int = 10) -> List[List[Any]]:
    """Name each gap ``[start, dur, dev]`` as far as the trace itself
    allows: a gap whose middle lies in no executable's span is the host's
    (``between``: what the host does between two dispatches in this kind
    of cell); one inside an executable is the device waiting within a
    program, and stays ``unattributed`` until the program's own spans are
    on the profiler's clock. Returns the summed seconds per name."""
    total: Dict[str, float] = {}
    for s, du, _ in gaps:
        mid = s + du / 2.0
        label = between
        for a, b in module_spans:
            if a <= mid <= b:
                label = "unattributed (inside an executable)"
                break
        total[label] = total.get(label, 0.0) + du
    return [[k, v] for k, v in sorted(total.items(), key=lambda kv: -kv[1])[:top]]
