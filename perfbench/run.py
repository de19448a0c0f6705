"""Run one cell of the benchmark once.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result: one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics`` and ``device`` (and, in
a traced run, ``breakdown``). No accelerator, or fewer chips than the cell
asks for, is exit code 2 and no result line. This process never touches
JAX: the chips belong to the fit worker or the replica, and afterwards to
the reference's process.

Nothing here names a cell, a configuration, an architecture or a kind of
cell: the cell's files are found by the names ``BENCHMARK.json`` gives
(``pb/spec.py``), its driver by the traffic mix's ``kind``
(``kinds/<kind>.py``) and its architecture by the configuration's
``model_type`` (``families/<model_type>.py``).

Other entries (none is used by the driver):

    --check-seeds 0,1,2    the output check alone, per seed; with
                           ``--check-control 1`` (the default) the control
                           (the reference in float8) beside it. One line a
                           seed. How the limits were set.
    --rehearse             walk the same path at a toy size on the CPU
                           (``--bench-root tests/perfbench/toy``); says it
                           is not a chip result and prints no metrics.
    --bench-root DIR       read BENCHMARK.json and the cell's files from
                           DIR (how a test adds a cell, and how
                           ``variant.py`` makes one with another rate).
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time
from typing import Any, Dict, List, Optional

T_START = time.time()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, ROOT)

from pb.harness import check_text, say  # noqa: E402


class NoChip(RuntimeError):
    pass


def reduce_trace(ctx: Dict[str, Any], run: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    from pb import xplane

    tr = run.get("trace")
    if not tr or "dir" not in tr:
        return None
    path = xplane.find_xplane(tr["dir"])
    if path is None:
        return None
    events = xplane.load(path)
    red = xplane.reduce(events)
    red["file_bytes"] = os.path.getsize(path)
    shutil.rmtree(tr["dir"], ignore_errors=True)
    return red


def one_run(spec: Any, args: Any, seed: int, control: bool) -> Dict[str, Any]:
    from pb import plug

    cell = spec.cell(args.workload)
    cfg = spec.config(cell["config"])
    mix = spec.traffic(cell["traffic"])
    kind = plug.module("kinds", mix["kind"])
    # under the benchmark's root: the checkout for the driver, and for a test
    # a directory of its own, so that two tests never share one
    out_dir = os.path.join(spec.root, ".perfbench_out", cell["name"])
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    ctx: Dict[str, Any] = {
        "cell": cell, "config": cfg, "mix": mix, "dims": spec.dims(cfg), "chips": int(cell["chips"]),
        "seed": int(seed), "seconds": float(args.seconds), "trace": bool(args.trace),
        "rehearse": bool(args.rehearse), "out_dir": out_dir, "limits": spec.limits(cell["name"]),
        "control": control, "t_start": T_START,
    }
    from ray_lightning_tpu import fabric
    from ray_lightning_tpu.utils.compile_cache import place_compile_cache

    if args.rehearse:
        fabric.init(num_cpus=8, num_tpus=0)
    else:
        fabric.init()
        have = int(fabric.cluster_resources().get("TPU", 0))
        if have < ctx["chips"]:
            fabric.shutdown()
            raise NoChip(f"the cell asks for {ctx['chips']} chip(s), this host has {have}")
    place_compile_cache()
    run = kind.run(ctx)
    run["ctx"] = ctx
    run["reduced_trace"] = reduce_trace(ctx, run) if args.trace else None
    return run


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--check-seeds", default="")
    ap.add_argument("--check-control", type=int, default=1)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--bench-root", default=ROOT)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "ray_lightning_tpu")):
        print("perfbench: no ray_lightning_tpu/ beside perfbench/: nothing to measure", file=sys.stderr)
        return 2
    from pb.plug import PlugError
    from pb.spec import Spec, SpecError

    try:
        spec = Spec(args.bench_root)
        spec.cell(args.workload)
    except SpecError as exc:
        print(f"perfbench: {exc}", file=sys.stderr)
        return 2
    if args.seconds is None:
        args.seconds = float(spec.bench["run_seconds"])
    if args.rehearse:
        say("REHEARSAL on the CPU at a toy size: this is NOT a chip result, and no metric is printed.")
    try:
        if args.check_seeds:
            return check_entry(spec, args)
        run = one_run(spec, args, args.seed, control=False)
    except NoChip as exc:
        print(f"perfbench: no accelerator: {exc}", file=sys.stderr)
        return 2
    except (SpecError, PlugError) as exc:
        print(f"perfbench: {exc}", file=sys.stderr)
        return 2
    return report(spec, args, run)


def check_entry(spec: Any, args: Any) -> int:
    """The output check alone over a list of seeds, the control beside
    it: one line a seed (how the limits in ``limits/<cell>.json`` were
    set; ``PERF.md`` has the readings)."""
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    path = os.path.join(ROOT, "chiprun_out", f"check_{args.workload}.jsonl")
    bad = 0
    for s in [int(x) for x in args.check_seeds.split(",") if x.strip()]:
        t = time.time()
        run = one_run(spec, args, s, control=bool(args.check_control))
        line = {"seed": s, "program": run["numbers"], "control": run["ctx"].get("control_numbers"),
                "leaf_gaps": run["ctx"].get("delta_leaf_gaps"), "wall_s": time.time() - t,
                "checks_ok": all(c["ok"] for c in run["checks"]), "e2e": run["e2e"],
                "reference_s": run["reference"].get("seconds")}
        bad += not line["checks_ok"]
        say("CHECK " + json.dumps(line))
        with open(path, "a") as f:
            f.write(json.dumps(line) + "\n")
    return 1 if bad else 0


def report(spec: Any, args: Any, run: Dict[str, Any]) -> int:
    from pb import costs

    ctx = run["ctx"]
    cell = ctx["cell"]["name"]
    correct = all(c["ok"] for c in run["checks"])
    device = dict(run["device"], memory_peak_bytes=run["memory_peak_bytes"])
    e2e_defs = spec.end_to_end(cell)
    metrics: Dict[str, Any] = {}
    if not args.trace:
        for m in e2e_defs:
            if m["name"] in run["e2e"]:
                metrics[m["name"]] = {"value": run["e2e"][m["name"]], "unit": m["unit"]}
    red = run.get("reduced_trace")
    breakdown = None
    if args.trace:
        rctx = {
            "cell": cell, "chips": ctx["chips"], "dims": ctx["dims"], "mix": ctx["mix"],
            "config": ctx["config"], "program": run["program"], "e2e": run["e2e"], "trace": red,
            "seconds": ctx["seconds"], "costs": costs,
            "peaks": None if ctx["rehearse"] else costs.peaks(device["kind"]),
        }
        for m in spec.per_layer(cell, list(run["e2e"])):
            rctx["params"] = spec.metric_params(m["name"])
            value = spec.reader(m["name"])(rctx)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        if red and red.get("devices"):
            from pb import xplane

            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            breakdown = {
                "device_ops": red["device_ops"],
                "idle_gaps": xplane.name_gaps(red["gaps"], red["module_spans"], run["between"]),
            }
    say("end-to-end of this run: " + json.dumps(run["e2e"]))
    result: Dict[str, Any] = {
        "correct": bool(correct), "attempted": int(run["attempted"]), "failed": int(run["failed"]),
        "metrics": metrics, "device": device,
    }
    if breakdown:
        result["breakdown"] = breakdown
    # what the run printed and compared with nothing (how late the generator ran is among them,
    # traced or not), then, last, each number compared beside its limit
    result["numbers"] = run["numbers"]
    result["checks"] = {c["check"]: {"value": c["value"], "limit": c["limit"]} for c in run["checks"]}
    for c in run["checks"]:
        print(check_text(c), file=sys.stderr, flush=True)
    if args.rehearse:
        say("REHEARSAL finished: correct=%s attempted=%s failed=%s (not a chip result; no metrics)"
            % (correct, run["attempted"], run["failed"]))
        return 0 if correct else 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
