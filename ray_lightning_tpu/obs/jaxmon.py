"""JAX compile-event telemetry via jax.monitoring listeners.

``jax.monitoring.register_event_duration_secs_listener`` reports every
jaxpr trace / MLIR lowering / backend compile with its wall time; this
module folds those into the process registry as::

    rlt_jax_compile_events_total{event="backend_compile"}
    rlt_jax_compile_seconds_total{event="backend_compile"}

and keeps a host-side :class:`CompileStats` counter so code can take
cheap before/after snapshots. That turns contracts like the serve
engine's "compile count frozen after construction" into a METRIC —
``ServeReplica.stats()`` ships ``compiles_since_init``, which must read
0 in steady state — instead of something only the test suite can see.

Listeners receive (event_name, duration) only — no executable name — so
attribution is per event KIND. Listener registration is process-global
and irrevocable (there is no unregister short of clearing every
listener), hence the idempotent :func:`install_compile_listener`.
"""
from __future__ import annotations

import threading
from typing import Dict, Optional

from ray_lightning_tpu.obs.registry import MetricsRegistry, get_registry

#: jax.monitoring event-name suffix -> short label.
_EVENTS = {
    "/jax/core/compile/backend_compile_duration": "backend_compile",
    "/jax/core/compile/jaxpr_trace_duration": "jaxpr_trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lowering",
}


class CompileStats:
    """Host-side mirror of the compile counters (cheap snapshots)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._counts: Dict[str, int] = {}
        self._secs: Dict[str, float] = {}

    def record(self, label: str, dur: float) -> None:
        with self._lock:
            self._counts[label] = self._counts.get(label, 0) + 1
            self._secs[label] = self._secs.get(label, 0.0) + float(dur)

    def snapshot(self) -> Dict[str, Dict[str, float]]:
        with self._lock:
            return {
                k: {"count": self._counts[k], "total_s": round(self._secs[k], 4)}
                for k in sorted(self._counts)
            }

    def count(self, label: str = "backend_compile") -> int:
        with self._lock:
            return self._counts.get(label, 0)


_STATS: Optional[CompileStats] = None
_INSTALL_LOCK = threading.Lock()


def install_compile_listener(
    registry: Optional[MetricsRegistry] = None,
) -> CompileStats:
    """Install the listener once per process; returns the shared
    :class:`CompileStats`. Safe to call from every subsystem that wants
    compile telemetry (trainer loop, serve replica, tools)."""
    global _STATS
    with _INSTALL_LOCK:
        if _STATS is not None:
            return _STATS
        stats = CompileStats()
        reg = registry or get_registry()
        counter = reg.counter(
            "rlt_jax_compile_events_total",
            "JAX compile-pipeline events by kind",
        )
        seconds = reg.counter(
            "rlt_jax_compile_seconds_total",
            "Wall seconds spent in JAX compile-pipeline events by kind",
        )

        def _listener(name: str, dur: float, **kw: object) -> None:  # noqa: ARG001
            label = _EVENTS.get(name)
            if label is None:
                return
            stats.record(label, dur)
            counter.inc(1, event=label)
            seconds.inc(float(dur), event=label)

        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(_listener)
        _STATS = stats
        return stats


def compile_stats() -> Optional[CompileStats]:
    """The installed stats, or None when no listener was installed yet."""
    return _STATS
