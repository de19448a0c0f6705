"""Trainer + fabric telemetry: step breakdown, tokens/s + MFU, heartbeats.

:class:`TrainTelemetry` is fed by ``TrainingLoop``'s fit loop with one
record per dispatched chunk, split into the three host-observable
segments of a step's wall time::

    data_wait : blocking on the staged-batch iterator (host assembly +
                H2D backpressure; the device's compute surfaces here
                only when no log drains between dispatches)
    step      : the compiled-step call (dispatch; near-zero when async)
    drain     : log fetch, callbacks, mid-epoch val — everything between
                the step returning and the next batch pull. A log drain
                blocks on the dispatch it fetches from, so with a drain
                every dispatch (``log_every_n_steps`` <= the fold) the
                device's compute surfaces HERE.

The segments are consecutive intervals of one clock, so they sum to the
chunk's wall time by construction (the test asserts it to guard the
instrumentation against drift as the loop evolves). Each is a span of
the loop (``obs.trace.span`` into :attr:`TrainTelemetry.spans`:
``fit.stage``, ``fit.dispatch``, and ``fit.callbacks`` with the
blocking fetch inside it as ``fit.drain_wait``; drain =
``fit.drain_wait`` + ``fit.callbacks``), so a profiler session shows
them beside the device's operations. Aggregates feed the process
registry (``rlt_train_*``) and ship to the driver in
``trainer_state["telemetry"]``.

Throughput: when the module exposes ``batch_size`` and a config with
``max_seq`` (GPTLM does), the loop reports tokens/s; with a known chip
peak (utils/flops) that becomes MFU. On CPU / unknown chips MFU is
omitted rather than fabricated.

:func:`heartbeats_to_registry` folds ``fabric.heartbeats()`` payloads
(rss, cpu, last-call age per worker) into the same registry, so one
Prometheus scrape covers serve, trainer, and fabric.
"""
from __future__ import annotations

import time
from typing import Any, Dict, Optional

from ray_lightning_tpu.obs.jaxmon import compile_stats, gc_stats
from ray_lightning_tpu.obs.registry import MetricsRegistry, get_registry
from ray_lightning_tpu.obs.trace import SpanTotals


class TrainTelemetry:
    """Per-fit step-time breakdown + throughput, registry-backed."""

    def __init__(self, registry: Optional[MetricsRegistry] = None) -> None:
        reg = registry or get_registry()
        self._steps = reg.counter(
            "rlt_train_steps_total", "Optimizer micro-steps executed"
        )
        self._seg = reg.counter(
            "rlt_train_seconds_total",
            "Fit-loop wall seconds by segment (data_wait/step/drain)",
        )
        self._tps = reg.gauge(
            "rlt_train_tokens_per_sec", "Training throughput (global tokens/s)"
        )
        self._mfu = reg.gauge(
            "rlt_train_mfu", "Model FLOPs utilization (0-1), when peak known"
        )
        #: The fit loop's spans (fit.stage / fit.dispatch /
        #: fit.drain_wait / fit.callbacks): monotone totals, shipped in
        #: snapshot()["spans"].
        self.spans = SpanTotals()
        # Host mirrors (snapshot() must not depend on registry internals).
        self.steps = 0
        self.chunks = 0
        self.data_wait_s = 0.0
        self.step_s = 0.0
        self.drain_s = 0.0
        self.wall_s = 0.0
        self.tokens_per_sec: Optional[float] = None
        self.mfu: Optional[float] = None
        self.tokens_total = 0
        # Watchdog progress stamps (obs.health.fit_stall_check): the fit
        # is stalled when neither construction nor the last chunk is
        # recent and the fit has not finished.
        self.created_t = time.monotonic()
        self.last_progress_t: Optional[float] = None
        self.fit_done = False

    def record_chunk(
        self, n_steps: int, data_wait: float, step: float, drain: float
    ) -> None:
        self.last_progress_t = time.monotonic()
        self.steps += int(n_steps)
        self.chunks += 1
        self.data_wait_s += data_wait
        self.step_s += step
        self.drain_s += drain
        self.wall_s += data_wait + step + drain
        self._steps.inc(int(n_steps))
        self._seg.inc(data_wait, segment="data_wait")
        self._seg.inc(step, segment="step")
        self._seg.inc(drain, segment="drain")

    def record_throughput(
        self,
        tokens: int,
        wall_s: float,
        flops_per_token: Optional[float] = None,
        peak_flops_total: Optional[float] = None,
    ) -> None:
        """Tokens processed over ``wall_s``; MFU when both the per-token
        FLOPs estimate and the aggregate chip peak are known."""
        if wall_s <= 0 or tokens <= 0:
            return
        self.tokens_total += int(tokens)
        self.tokens_per_sec = round(tokens / wall_s, 3)
        self._tps.set(self.tokens_per_sec)
        if flops_per_token and peak_flops_total:
            self.mfu = round(
                self.tokens_per_sec * flops_per_token / peak_flops_total, 4
            )
            self._mfu.set(self.mfu)

    def snapshot(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {
            "steps": self.steps,
            "chunks": self.chunks,
            "data_wait_s": round(self.data_wait_s, 4),
            "step_s": round(self.step_s, 4),
            "drain_s": round(self.drain_s, 4),
            "wall_s": round(self.wall_s, 4),
        }
        if self.wall_s > 0:
            out["data_wait_frac"] = round(self.data_wait_s / self.wall_s, 4)
            out["step_frac"] = round(self.step_s / self.wall_s, 4)
            out["drain_frac"] = round(self.drain_s / self.wall_s, 4)
        if self.tokens_per_sec is not None:
            out["tokens_per_sec"] = self.tokens_per_sec
            out["tokens_total"] = self.tokens_total
        if self.mfu is not None:
            out["mfu"] = self.mfu
        stats = compile_stats()
        if stats is not None:
            out["compile_events"] = stats.snapshot()
        # The same block ServeReplica.stats() ships: monotone since the
        # fit began, so two snapshots differ by exactly the time between.
        out["spans"] = self.spans.snapshot()
        gc_totals = gc_stats()
        if gc_totals is not None:
            out["spans"]["gc"] = gc_totals.snapshot()
        return out


def flops_per_token(
    n_params: int, n_layer: int, d_model: int, seq: int
) -> float:
    """PaLM-style training FLOPs/token: 6N + the attention term."""
    return 6.0 * n_params + 12.0 * n_layer * d_model * seq


def peak_flops_total(device_kind: str, n_devices: int) -> Optional[float]:
    """Aggregate peak bf16 FLOP/s across ``n_devices`` chips; None when
    the chip kind is unknown (CPU) — callers skip MFU then."""
    from ray_lightning_tpu.utils.flops import peak_flops_for

    peak = peak_flops_for(device_kind)
    return None if peak is None else peak * max(1, int(n_devices))


def heartbeats_to_registry(
    heartbeats: Dict[str, Dict[str, Any]],
    registry: Optional[MetricsRegistry] = None,
) -> None:
    """Fold ``fabric.heartbeats()`` into worker-labelled gauges."""
    reg = registry or get_registry()
    gauges = {
        "rss_bytes": reg.gauge(
            "rlt_fabric_worker_rss_bytes", "Worker resident set size"
        ),
        "cpu_s": reg.gauge(
            "rlt_fabric_worker_cpu_seconds", "Worker process CPU seconds"
        ),
        "uptime_s": reg.gauge(
            "rlt_fabric_worker_uptime_seconds", "Worker process uptime"
        ),
        "calls_handled": reg.gauge(
            "rlt_fabric_worker_calls_handled", "RPCs completed by the worker"
        ),
        "calls_in_flight": reg.gauge(
            "rlt_fabric_worker_calls_in_flight",
            "RPCs currently executing (0 or 1; the actor loop is serial)",
        ),
        "last_call_age_s": reg.gauge(
            "rlt_fabric_worker_last_call_age_seconds",
            "Seconds since the worker last finished an RPC",
        ),
        "age_s": reg.gauge(
            "rlt_fabric_worker_heartbeat_age_seconds",
            "Driver-side age of the worker's last heartbeat",
        ),
    }
    for actor_id, hb in heartbeats.items():
        for key, gauge in gauges.items():
            val = hb.get(key)
            if val is not None:
                gauge.set(float(val), actor=actor_id)
    # Drop series whose actor is absent from this snapshot: a killed or
    # crashed worker leaves heartbeats(), and its gauges must leave the
    # scrape with it instead of reporting stale values forever.
    for gauge in gauges.values():
        for label_key in gauge.samples():
            labels = dict(label_key)
            actor = labels.get("actor")
            if actor is not None and actor not in heartbeats:
                gauge.remove(**labels)
