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

Beside it, the other thing that stalls a host loop from inside the
runtime: the cyclic collector. :func:`install_gc_hook` times every
collection through ``gc.callbacks`` into a :class:`GcStats` (per
generation: collections, summed pause, longest pause), mirrored as
``rlt_gc_pause_seconds_total{gen}`` where the registry is read.
"""
from __future__ import annotations

import gc
import threading
import time
from typing import Any, Dict, List, Optional

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


class GcStats:
    """Collector pauses by generation, from ``gc.callbacks``.

    The callback runs inside whichever thread tripped the collector,
    possibly while that thread holds a lock of the registry or of a
    ``SpanTotals``; it therefore takes no lock and touches no registry:
    it adds to plain integers (collections do not nest, and the
    interpreter lock orders the stores). :meth:`mirror` moves the totals
    into a registry counter from the reader's side.
    """

    def __init__(self) -> None:
        #: generation -> [collections, summed ns, longest ns]
        self._gen: List[List[int]] = [[0, 0, 0] for _ in range(3)]
        self._t0 = 0
        self._mirrored = [0.0, 0.0, 0.0]
        self._mirror_lock = threading.Lock()

    def _callback(self, phase: str, info: Dict[str, Any]) -> None:
        if phase == "start":
            self._t0 = time.perf_counter_ns()
            return
        dur = time.perf_counter_ns() - self._t0
        row = self._gen[min(int(info.get("generation", 2)), 2)]
        row[0] += 1
        row[1] += dur
        if dur > row[2]:
            row[2] = dur

    def snapshot(self) -> Dict[str, Dict[str, float]]:
        """``{gen: {"n", "s", "max_s"}}`` since the hook was installed;
        never decreasing."""
        return {
            str(g): {"n": n, "s": ns * 1e-9, "max_s": mx * 1e-9}
            for g, (n, ns, mx) in enumerate(self._gen)
        }

    def mirror(self, seconds: Any) -> None:
        with self._mirror_lock:
            for g, row in self.snapshot().items():
                seconds.inc(row["s"] - self._mirrored[int(g)], gen=g)
                self._mirrored[int(g)] = row["s"]


_GC: Optional[GcStats] = None
_GC_USERS = 0


def install_gc_hook() -> GcStats:
    """Hook the collector once per process; returns the shared
    :class:`GcStats`. Every caller pairs it with one
    :func:`remove_gc_hook`."""
    global _GC, _GC_USERS
    with _INSTALL_LOCK:
        if _GC is None:
            _GC = GcStats()
            gc.callbacks.append(_GC._callback)
        _GC_USERS += 1
        return _GC


def remove_gc_hook() -> None:
    """Drop one user of the hook; the last one takes it out of
    ``gc.callbacks`` (the totals start again at the next install)."""
    global _GC, _GC_USERS
    with _INSTALL_LOCK:
        if _GC is None:
            return
        _GC_USERS -= 1
        if _GC_USERS <= 0:
            try:
                gc.callbacks.remove(_GC._callback)
            except ValueError:
                pass
            _GC, _GC_USERS = None, 0


def gc_stats() -> Optional[GcStats]:
    """The installed collector totals, or None when no hook is in."""
    return _GC
