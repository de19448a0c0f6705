"""ray_lightning_tpu.obs — the cross-layer observability subsystem.

The repo's third subsystem (after the trainer and the serving engine):
one place where serve, trainer, and fabric report what they are doing,
and one place operators read it back. Two halves:

PASSIVE (telemetry — the eyes):

- :mod:`obs.trace` — request tracing: typed lifecycle spans in a bounded
  per-replica ring buffer (:class:`RequestTracer`), exported as Chrome
  trace-event JSON (:func:`to_chrome_trace`) that opens in Perfetto; and
  what a THREAD is doing (``obs.trace.span`` into ``SpanTotals``):
  monotone totals in ``stats()["spans"]``, and annotations on the
  profiler's clock while a ``jax.profiler`` session is active.
- :mod:`obs.anatomy` — request anatomy (:func:`assemble_anatomy`,
  :func:`render_anatomy`): one request's cross-process phase ledger
  stitched from every tracer ring + the journal + the event rings, with
  an explicit coverage contract (phases + unaccounted == observed
  latency, exactly) — ``rlt why``'s and ``/why``'s engine, and the
  phase vocabulary behind the fleet latency decomposition and SLO
  breach attribution.
- :mod:`obs.registry` — counter/gauge/histogram registry
  (:class:`MetricsRegistry`, :func:`get_registry` for the process
  default) rendered in Prometheus text format.
- :mod:`obs.events` — structured event log (:class:`EventLog`,
  :func:`get_event_log`): a bounded process-wide ring of typed events
  (admissions, cancels, epoch boundaries, actor deaths, verdicts).
- :mod:`obs.telemetry` — trainer step breakdown, tokens/s + MFU, fabric
  heartbeat aggregation (:class:`TrainTelemetry`).
- :mod:`obs.jaxmon` — JAX compile-event counters
  (:func:`install_compile_listener`): the frozen-compile contract as a
  metric, not just a test; and the cyclic collector's pauses
  (``install_gc_hook``).
- :mod:`obs.profiling` — on-demand ``jax.profiler`` capture
  (:func:`capture_profile`) behind the ``profile(duration_s)`` RPCs, in
  a thread of its own where the caller is a serial actor.

ACTIVE (judgment — something looks through the eyes):

- :mod:`obs.health` — the watchdog + SLO engine (:class:`Watchdog`):
  passive telemetry in, per-component ``healthy|degraded|unhealthy``
  verdicts out, backing a real ``/healthz`` (200/503) and the
  ``rlt_health{component=...}`` gauges.
- :mod:`obs.blackbox` — the flight recorder (:func:`dump_bundle`,
  :class:`FlightRecorder`): self-contained forensic bundles (metrics,
  events, traces, health, stacks) dumped automatically on unhealthy
  transitions and fit crashes, or on demand via ``debug_dump`` RPCs and
  ``rlt doctor``.
- :mod:`obs.httpd` — the /metrics + /stats + /healthz + /debug/bundle
  (+ /fleet + /events + /traces) HTTP endpoint
  (:class:`MetricsHTTPServer`) behind ``rlt serve --serve.metrics_port``.
- :mod:`obs.journal` — deterministic capture & replay
  (:class:`WorkloadJournal`, :func:`load_journal`,
  :func:`replay_journal`): the serve session's externally-sourced
  request stream journaled into a bounded ring (+ optional JSONL
  spill), re-drivable bit-exactly via ``rlt replay`` — every incident
  a local repro, every captured trace a benchmark.
- :mod:`obs.fleet` — the fleet aggregator (:class:`FleetPoller`,
  :class:`FleetSnapshot`): a driver-side puller condensing every
  replica's stats/health into one bounded-history snapshot stream —
  the ``/fleet`` route's and ``rlt top``'s feed, and the signal plane a
  router/autoscaler consumes.

Import cost: everything here is stdlib-only at import time; jax loads
only when profiling/monitoring is actually used, so the fabric can ship
this module into workers whose platform env is not yet applied.
"""
from ray_lightning_tpu.obs.anatomy import (
    assemble_anatomy,
    anatomy_from_client,
    aggregate_phases,
    breach_attribution,
    format_attribution,
    render_anatomy,
)
from ray_lightning_tpu.obs.blackbox import (
    FlightRecorder,
    dump_bundle,
    read_bundle,
)
from ray_lightning_tpu.obs.events import EventLog, get_event_log
from ray_lightning_tpu.obs.fleet import (
    FleetPoller,
    FleetSnapshot,
    aggregate_fleet,
    summarize_replica,
)
from ray_lightning_tpu.obs.health import (
    ComponentHealth,
    HealthReport,
    SLORule,
    Watchdog,
    parse_slo_rules,
)
from ray_lightning_tpu.obs.httpd import MetricsHTTPServer
from ray_lightning_tpu.obs.jaxmon import compile_stats, install_compile_listener
from ray_lightning_tpu.obs.journal import (
    WorkloadJournal,
    load_journal,
    replay_journal,
)
from ray_lightning_tpu.obs.profiling import capture_profile, profiler_available
from ray_lightning_tpu.obs.registry import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    get_registry,
    parse_prometheus_text,
)
from ray_lightning_tpu.obs.telemetry import (
    TrainTelemetry,
    heartbeats_to_registry,
)
from ray_lightning_tpu.obs.trace import (
    RequestTracer,
    merge_chrome_trace,
    to_chrome_trace,
)

__all__ = [
    "ComponentHealth",
    "Counter",
    "EventLog",
    "FleetPoller",
    "FleetSnapshot",
    "FlightRecorder",
    "Gauge",
    "HealthReport",
    "Histogram",
    "MetricsHTTPServer",
    "MetricsRegistry",
    "RequestTracer",
    "SLORule",
    "TrainTelemetry",
    "Watchdog",
    "WorkloadJournal",
    "aggregate_fleet",
    "aggregate_phases",
    "anatomy_from_client",
    "assemble_anatomy",
    "breach_attribution",
    "capture_profile",
    "compile_stats",
    "dump_bundle",
    "format_attribution",
    "get_event_log",
    "get_registry",
    "heartbeats_to_registry",
    "install_compile_listener",
    "load_journal",
    "merge_chrome_trace",
    "parse_prometheus_text",
    "parse_slo_rules",
    "profiler_available",
    "read_bundle",
    "render_anatomy",
    "replay_journal",
    "summarize_replica",
    "to_chrome_trace",
]
