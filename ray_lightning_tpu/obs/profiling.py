"""On-demand jax.profiler capture.

Wraps ``jax.profiler`` start/stop into :func:`capture_profile` — the
duration-based form behind the ``profile(duration_s)`` RPC on serve
replicas and TrainWorkers: start a trace, sleep while the process's OWN
worker threads keep the device busy, stop, report the artifact files.
The captured trace opens in Perfetto / TensorBoard's profile plugin.
The profiler takes seconds to start and to write, so an actor that must
keep answering calls runs the capture in a thread of its own
(:class:`BackgroundCapture`). While a session is active, every
``obs.trace.span`` in the process is also a ``TraceAnnotation`` in it.

Everything degrades gracefully: when the profiler is unavailable (or a
capture is already running — jax allows one at a time per process) the
result says so instead of raising, because a profile RPC against a busy
replica must never take the replica down.
"""
from __future__ import annotations

import os
import tempfile
import threading
import time
from typing import Any, Dict, List, Optional

#: One capture at a time per process (jax.profiler's own constraint).
_ACTIVE = threading.Lock()


def profiler_available() -> bool:
    try:
        import jax.profiler  # noqa: F401

        return True
    except Exception:  # noqa: BLE001 - any import-time failure
        return False


def _trace_files(outdir: str) -> List[str]:
    found: List[str] = []
    for root, _, files in os.walk(outdir):
        for f in files:
            found.append(os.path.join(root, f))
    return sorted(found)


def capture_profile(
    duration_s: float = 1.0, outdir: Optional[str] = None
) -> Dict[str, Any]:
    """Capture ``duration_s`` of whatever this process's threads are
    doing; returns ``{ok, dir, files, duration_s}`` (or ``{ok: False,
    error}``). The caller's thread only sleeps — the work being profiled
    runs on the process's other threads (serve loop, train loop)."""
    duration_s = max(0.01, float(duration_s))
    if not profiler_available():
        return {"ok": False, "error": "jax.profiler unavailable"}
    if not _ACTIVE.acquire(blocking=False):
        return {"ok": False, "error": "a profile capture is already running"}
    try:
        import jax

        out = outdir or tempfile.mkdtemp(prefix="rlt_profile_")
        os.makedirs(out, exist_ok=True)
        try:
            jax.profiler.start_trace(out)
            time.sleep(duration_s)
        finally:
            jax.profiler.stop_trace()
        return {
            "ok": True,
            "dir": out,
            "files": _trace_files(out),
            "duration_s": duration_s,
        }
    except Exception as exc:  # noqa: BLE001 - report, never kill the host
        return {"ok": False, "error": f"{type(exc).__name__}: {exc}"}
    finally:
        _ACTIVE.release()


class BackgroundCapture:
    """:func:`capture_profile` in a thread of its own: the caller (a
    serial actor's RPC thread) returns at once and collects the result
    with a later call."""

    def __init__(
        self, duration_s: float = 1.0, outdir: Optional[str] = None
    ) -> None:
        self._result: Optional[Dict[str, Any]] = None
        self._thread = threading.Thread(
            target=self._run, args=(duration_s, outdir),
            name="rlt-profile-capture", daemon=True,
        )
        self._thread.start()

    def _run(self, duration_s: float, outdir: Optional[str]) -> None:
        self._result = capture_profile(duration_s, outdir)

    def result(self, wait_s: float = 0.0) -> Optional[Dict[str, Any]]:
        """The capture's report, or None while it is still running
        (after waiting up to ``wait_s`` for it)."""
        self._thread.join(timeout=max(0.0, float(wait_s)))
        return self._result
