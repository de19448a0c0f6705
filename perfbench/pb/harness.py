"""What every kind of cell needs from the harness: lines of output, the
reference's process, and the end of every process a run started."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from typing import Any, Dict, List

PB = os.path.dirname(os.path.abspath(__file__))


def say(msg: str) -> None:
    print(msg, flush=True)


def check_text(c: Dict[str, Any]) -> str:
    """A compared number beside its limit, as a line of the run."""
    return f"check {c['check']}: value={c['value']} limit={c['limit']} -> {'ok' if c['ok'] else 'FAILED'}"


def check_line(results: List[Dict[str, Any]], name: str, value: Any, limit: Any, ok: bool) -> None:
    """One number compared, printed beside its limit, and kept."""
    results.append({"check": name, "value": value, "limit": limit, "ok": bool(ok)})
    say(check_text(results[-1]))


def run_reference(ctx: Dict[str, Any], ref_in: Dict[str, Any]) -> Dict[str, Any]:
    """The plain reference in a process of its own (``pb/refproc.py``),
    after the program's has ended: it has the chip to itself, and the
    program's peak memory stays the program's."""
    ref_in = dict(ref_in, seed=ctx["seed"], dims=ctx["dims"], require_tpu=not ctx["rehearse"],
                  control=bool(ctx.get("control")))
    with open(os.path.join(ctx["out_dir"], "ref_in.json"), "w") as f:
        json.dump(ref_in, f)
    env = dict(os.environ)
    # the families are found by import: hand on where this process finds them
    env["PYTHONPATH"] = os.pathsep.join([p for p in sys.path if p] + [env.get("PYTHONPATH", "")]).rstrip(os.pathsep)
    if ctx["rehearse"]:
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={ctx['chips']}"
    t = time.time()
    proc = subprocess.run(
        [sys.executable, os.path.join(PB, "refproc.py"), ctx["out_dir"]],
        env=env, timeout=900, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
    )
    if proc.returncode != 0:
        raise RuntimeError(f"reference process exited {proc.returncode}: {proc.stderr[-2000:]}")
    with open(os.path.join(ctx["out_dir"], "reference.json")) as f:
        out = json.load(f)
    out["wall_s"] = time.time() - t
    return out


def _children() -> List[Any]:
    import psutil

    def ours(p: Any) -> bool:
        # multiprocessing's resource tracker serves this process until
        # it exits, and exits with it: not a leftover.
        try:
            return "resource_tracker" not in " ".join(p.cmdline())
        except psutil.Error:
            return False

    return [p for p in psutil.Process().children(recursive=True) if ours(p)]


def wait_children_gone(timeout: float = 20.0) -> List[Any]:
    """Wait for every process this one started to end; those still alive."""
    import psutil

    _, alive = psutil.wait_procs(_children(), timeout=timeout)
    return alive


def teardown() -> str:
    """Stop every actor and wait for it; say what is left."""
    import psutil

    from ray_lightning_tpu import fabric

    fabric.shutdown()
    left = []
    alive = wait_children_gone(20)
    for p in alive:
        left.append(" ".join(p.cmdline())[:120])
        p.kill()
    psutil.wait_procs(alive, timeout=10)
    return "none" if not left else f"killed {left}"
