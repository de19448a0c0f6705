"""Fleet aggregation: one queryable surface over N serving replicas.

PR 4/5 made each PROCESS observable (its own registry, tracer, event
ring, watchdog); this module rolls the fleet up. A driver-side
:class:`FleetPoller` periodically pulls every replica's stats snapshot
and health verdict (plus the fabric heartbeat table) through one
``pull_fn`` and condenses them into a :class:`FleetSnapshot`:

- ``replicas``: one compact row per replica — queue depth, active
  slots, tokens/s, TTFT p50/p95, spec accept rate, prefix hit rate,
  health verdict, and goodput (emitted tokens per device-second, from
  the cost ledger) — the exact surface a router/autoscaler consumes;
- ``fleet``: the roll-up — replica/healthy counts, total queue depth,
  aggregate tokens/s, fleet goodput (sum of emitted tokens over sum of
  device-seconds, NOT a mean of ratios), worst TTFT p95;
- ``heartbeats``: the fabric's worker heartbeat table, verbatim.

Snapshots land in a bounded history ring (so ``/fleet`` can show a
short trend without unbounded memory) and, when a registry is wired,
in ``rlt_fleet_*`` gauges next to the per-replica series. The poller
owns one daemon thread; a pull that raises is recorded (``errors``
counter + an event) and skipped — a dead replica must not kill the
control plane that would report it dead.

Consumed by ``rlt serve --serve.metrics_port`` (the ``/fleet`` route),
``rlt top`` (the live terminal dashboard), and ``rlt doctor`` bundles
(``fleet.json``). What an aggressive poll cadence costs a replica's loop
on the chip is not measured (ROADMAP D5).
"""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

#: Map verdict strings onto the rlt_fleet_replica_health gauge.
_VERDICT_SCORE = {"healthy": 1.0, "degraded": 0.5, "unhealthy": 0.0}


def summarize_replica(
    stats: Dict[str, Any],
    health: Optional[Dict[str, Any]] = None,
    index: int = 0,
) -> Dict[str, Any]:
    """One replica's dashboard row from its stats snapshot + health
    report — the compact, stable schema FleetSnapshot.replicas carries
    (full snapshots stay on the replica; the fleet plane only ships
    what a router/autoscaler/dashboard acts on)."""
    cost = dict(stats.get("cost") or {})
    verdict = (health or {}).get("verdict")
    if verdict is None:
        verdict = stats.get("health", "unknown")
    # Tiered prefix cache: fraction of block probes each tier served
    # (device probes = the walk's total, since device is probed first).
    tiers = dict((stats.get("prefix") or {}).get("tiers") or {})
    dev = tiers.get("device") or {}
    probes = int(dev.get("hits", 0)) + int(dev.get("misses", 0))
    tier_hit = {
        t: (round(int(r.get("hits", 0)) / probes, 4) if probes else 0.0)
        for t, r in tiers.items()
    } or None
    # Effective cache size: resident prefix bytes summed over every
    # enabled tier (device + host + disk) — a replica's capacity to
    # hold warm prefixes, the router's affinity tiebreaker.
    prefix_bytes = sum(int(r.get("bytes", 0)) for r in tiers.values())
    kvf = stats.get("kvfleet")
    kvs = stats.get("kvstore")
    return {
        "replica": int(index),
        "health": str(verdict),
        # Fleet KV plane: the replica's role (prefill/decode/mixed)
        # plus a compact transfer row — what `rlt top`'s role/fetch
        # columns and the role-aware router/autoscaler consume.
        "role": str(stats.get("role") or "mixed"),
        "kvfleet": (
            {
                k: kvf.get(k, 0)
                for k in (
                    "fetches", "fetch_bytes", "fetch_timeouts",
                    "fetch_stale", "ships", "served_fetches",
                    "pending_fetches", "store_fetches",
                    "store_fetch_misses", "layerwise", "layer_ships",
                    "ship_partial_drops",
                )
            }
            if isinstance(kvf, dict)
            else None
        ),
        # Persistent object-store tier: counters for dashboards PLUS
        # the recent_writes/recent_dropped rings verbatim — the router
        # refresh loop reads those rings off this row to keep the
        # directory's store-held half current, so they must survive
        # summarization.
        "kvstore": (
            {
                k: kvs.get(k)
                for k in (
                    "backend", "budget_mb", "hits", "misses", "writes",
                    "write_errors", "bytes_written", "bytes_read",
                    "evictions", "corrupt", "recent_writes",
                    "recent_dropped",
                )
            }
            if isinstance(kvs, dict)
            else None
        ),
        # Quality signals for the router/autoscaler: cumulative
        # SLO-breach count (PR 5's declarative rules) and the engine's
        # dropped-digest report (the directory's eviction feed).
        "slo_breaches": int(stats.get("slo_breaches") or 0),
        "kv_dropped": stats.get("kv_dropped"),
        "queue_depth": int(stats.get("queue_depth", 0)),
        "active_slots": int(stats.get("active_slots", 0)),
        "num_slots": int(stats.get("num_slots", 0)),
        "occupancy": float(stats.get("occupancy", 0.0)),
        "tokens_per_sec": float(stats.get("tokens_per_sec", 0.0)),
        "decode_tokens_per_sec": float(
            stats.get("decode_tokens_per_sec", 0.0)
        ),
        "ttft_p50_s": stats.get("ttft_p50_s"),
        "ttft_p95_s": stats.get("ttft_p95_s"),
        "spec_accept_rate": stats.get("spec_accept_rate"),
        "prefix_hit_rate": stats.get("prefix_hit_rate"),
        "prefix_tier_hit_rate": tier_hit,
        "prefix_bytes": prefix_bytes,
        # Paged KV: pool state + occupancy (None on dense replicas) —
        # the capacity signal a page-aware router/autoscaler reads.
        "kv_pages": (
            {
                k: kv[k]
                for k in (
                    "free", "resident", "aliased", "occupancy",
                    "fragmentation_tokens",
                )
            }
            if isinstance(kv := stats.get("kv_pages"), dict)
            else None
        ),
        # Fused-dispatch row: piggybacked prefill traffic + the fold
        # ladder's per-depth dispatch counts (None when piggyback is
        # off / the ladder has one rung) — `rlt top`'s pb column.
        "piggyback": (
            {
                "chunks": pb.get("chunks", 0),
                "dispatches": pb.get("dispatches", 0),
                "chunk_rows": pb.get("chunk_rows", 0),
            }
            if isinstance(pb := stats.get("piggyback"), dict)
            else None
        ),
        "fold_k": (
            {
                "ladder": fk.get("ladder") or [],
                "dispatches": fk.get("dispatches") or {},
            }
            if isinstance(fk := stats.get("fold_k"), dict)
            else None
        ),
        "submitted": int(stats.get("submitted", 0)),
        "finished": int(stats.get("finished", 0)),
        "compiles_since_init": int(stats.get("compiles_since_init", 0)),
        # Anatomy latency decomposition: the replica's windowed
        # per-phase percentile block verbatim (None when the phase
        # ledger is off or idle) — aggregate_fleet folds these into the
        # fleet-wide decomposition `rlt top` and `/fleet` show.
        "phases": stats.get("phases"),
        # Active SLO-breach reasons (with their phase attribution
        # suffix) — the fleet roll-up surfaces the first one as the
        # dashboard's `why:` line.
        "slo_reasons": [
            reason
            for name, ch in sorted(
                ((health or {}).get("components") or {}).items()
            )
            if name.startswith("slo:")
            and ch.get("verdict") == "unhealthy"
            for reason in ch.get("reasons", [])
        ] or None,
        # Goodput inputs ride along so the fleet ratio can be computed
        # as sum/sum instead of a mean of per-replica ratios.
        "cost_emitted_tokens": int(cost.get("emitted_tokens", 0)),
        "cost_device_seconds": float(cost.get("device_seconds", 0.0)),
        "goodput_tokens_per_device_s": float(
            cost.get("goodput_tokens_per_device_s", 0.0)
        ),
    }


def aggregate_fleet(rows: List[Dict[str, Any]]) -> Dict[str, Any]:
    """The fleet roll-up over per-replica rows (sum/sum goodput, worst
    TTFT p95, healthy count)."""
    toks = sum(r["cost_emitted_tokens"] for r in rows)
    dev = sum(r["cost_device_seconds"] for r in rows)
    p95s = [r["ttft_p95_s"] for r in rows if r.get("ttft_p95_s") is not None]
    kvf_rows = [r.get("kvfleet") or {} for r in rows]
    kvs_rows = [r.get("kvstore") or {} for r in rows]
    phases_block = _aggregate_phase_rows(rows)
    breach = next(
        (
            reason
            for r in rows
            for reason in (r.get("slo_reasons") or ())
        ),
        None,
    )
    return {
        "replicas": len(rows),
        "healthy": sum(1 for r in rows if r["health"] == "healthy"),
        # Fleet KV plane roll-up: cross-replica fetch/ship traffic
        # (zeros on fleets without the plane).
        "kvfleet_fetches": sum(
            int(k.get("fetches", 0)) for k in kvf_rows
        ),
        "kvfleet_fetch_timeouts": sum(
            int(k.get("fetch_timeouts", 0)) + int(k.get("fetch_stale", 0))
            for k in kvf_rows
        ),
        "kvfleet_ships": sum(int(k.get("ships", 0)) for k in kvf_rows),
        # Fused-dispatch roll-up: prefill chunk rows that rode decode
        # folds fleet-wide (zeros when piggybacking is off).
        "piggyback_dispatches": sum(
            int((r.get("piggyback") or {}).get("dispatches", 0))
            for r in rows
        ),
        "piggyback_chunk_rows": sum(
            int((r.get("piggyback") or {}).get("chunk_rows", 0))
            for r in rows
        ),
        # Persistent store roll-up (zeros on storeless fleets). Note:
        # replicas sharing one store dir each count their own traffic,
        # so these are fleet I/O totals, not unique-entry counts.
        "kvstore_hits": sum(
            int(k.get("hits") or 0) for k in kvs_rows
        ),
        "kvstore_misses": sum(
            int(k.get("misses") or 0) for k in kvs_rows
        ),
        "kvstore_writes": sum(
            int(k.get("writes") or 0) for k in kvs_rows
        ),
        "kvstore_write_errors": sum(
            int(k.get("write_errors") or 0) for k in kvs_rows
        ),
        "kvstore_evictions": sum(
            int(k.get("evictions") or 0) for k in kvs_rows
        ),
        "queue_depth": sum(r["queue_depth"] for r in rows),
        "active_slots": sum(r["active_slots"] for r in rows),
        "num_slots": sum(r["num_slots"] for r in rows),
        "tokens_per_sec": round(
            sum(r["tokens_per_sec"] for r in rows), 3
        ),
        "emitted_tokens": toks,
        "device_seconds": round(dev, 6),
        "goodput_tokens_per_device_s": (
            round(toks / dev, 3) if dev > 0 else 0.0
        ),
        "ttft_p95_s_worst": max(p95s) if p95s else None,
        # Anatomy decomposition roll-up: per-phase p50 (count-weighted
        # mean of replica p50s), p95/p99 (MAX across replicas — tails
        # don't average), hot_phase = the fleet's single largest p95 —
        # `rlt top`'s phase hot-spot column. None when no replica has a
        # phase window.
        "phases": phases_block,
        # The first active SLO-breach reason (attribution suffix
        # included) — `rlt top`'s `why:` line; None when nothing is
        # breaching.
        "breach_attribution": breach,
    }


def _aggregate_phase_rows(
    rows: List[Dict[str, Any]],
) -> Optional[Dict[str, Any]]:
    """Fold per-replica ``phases`` blocks into the fleet decomposition:
    weighted-mean centers, max tails, per-role split when the fleet is
    disaggregated."""
    by_phase: Dict[str, Dict[str, float]] = {}
    by_role: Dict[str, Dict[str, Dict[str, float]]] = {}
    for r in rows:
        blk = (r.get("phases") or {}).get("by_phase") or {}
        role = str(r.get("role") or "mixed")
        for phase, row in blk.items():
            if not isinstance(row, dict):
                continue
            c = int(row.get("count", 0))
            if c <= 0:
                continue
            agg = by_phase.setdefault(phase, {
                "count": 0, "_mean_w": 0.0, "_p50_w": 0.0,
                "p95_s": 0.0, "p99_s": 0.0,
            })
            agg["count"] += c
            agg["_mean_w"] += float(row.get("mean_s", 0.0)) * c
            agg["_p50_w"] += float(row.get("p50_s", 0.0)) * c
            agg["p95_s"] = max(agg["p95_s"], float(row.get("p95_s", 0.0)))
            agg["p99_s"] = max(agg["p99_s"], float(row.get("p99_s", 0.0)))
            role_agg = by_role.setdefault(role, {}).setdefault(
                phase, {"count": 0, "p95_s": 0.0}
            )
            role_agg["count"] += c
            role_agg["p95_s"] = max(
                role_agg["p95_s"], float(row.get("p95_s", 0.0))
            )
    if not by_phase:
        return None
    out_phases = {
        phase: {
            "p50_s": round(agg["_p50_w"] / agg["count"], 6),
            "p95_s": round(agg["p95_s"], 6),
            "p99_s": round(agg["p99_s"], 6),
            "mean_s": round(agg["_mean_w"] / agg["count"], 6),
            "count": int(agg["count"]),
        }
        for phase, agg in by_phase.items()
    }
    hot_phase, hot_row = max(
        out_phases.items(), key=lambda kv: kv[1]["p95_s"]
    )
    out: Dict[str, Any] = {
        "by_phase": out_phases,
        "hot_phase": hot_phase,
        "hot_phase_p95_s": hot_row["p95_s"],
    }
    if len(by_role) > 1:
        out["by_role"] = {
            role: {
                phase: {
                    "p95_s": round(agg["p95_s"], 6),
                    "count": int(agg["count"]),
                }
                for phase, agg in phases.items()
            }
            for role, phases in by_role.items()
        }
    return out


@dataclass
class FleetSnapshot:
    """One poll of the whole fleet (the ``/fleet`` payload unit)."""

    ts: float
    replicas: List[Dict[str, Any]]
    fleet: Dict[str, Any]
    heartbeats: Dict[str, Any] = field(default_factory=dict)

    def to_dict(self) -> Dict[str, Any]:
        return {
            "ts": self.ts,
            "replicas": self.replicas,
            "fleet": self.fleet,
            "heartbeats": self.heartbeats,
        }


#: pull_fn contract: () -> (stats_list, health_list_or_None,
#: heartbeats_or_None); stats_list[i] is replica i's stats snapshot.
PullFn = Callable[
    [],
    Tuple[
        List[Dict[str, Any]],
        Optional[List[Dict[str, Any]]],
        Optional[Dict[str, Any]],
    ],
]


class FleetPoller:
    """Background fleet aggregator: pull -> condense -> ring + gauges.

    ``history`` bounds the ring; ``interval_s`` is the poll cadence
    (production default seconds; tests run it far faster).
    ``to_dict()`` is the ``/fleet`` payload: the latest snapshot plus
    the history ring.

    ``supervisor_fn`` (optional, zero-arg -> list of rows — typically
    ``FleetSupervisor.rows``) embeds the recovery plane's per-replica
    state table in the ``/fleet`` payload, so ``rlt top`` and dashboards
    show restarts/draining next to the health/throughput rows.
    ``router_fn`` (optional, zero-arg -> dict — typically
    ``serve.router.Router.rows``) embeds the routing plane the same
    way: per-replica weights/routability plus the routed/shed totals.
    ``alerts_fn`` (optional, zero-arg -> dict — typically
    ``obs.watchtower.Watchtower.fleet_block``) embeds the alert
    engine's firing summary, so ``rlt top`` shows firing alerts
    without a second request.
    """

    def __init__(
        self,
        pull_fn: PullFn,
        interval_s: float = 2.0,
        history: int = 128,
        registry: Optional[Any] = None,
        events: Optional[Any] = None,
        supervisor_fn: Optional[
            Callable[[], List[Dict[str, Any]]]
        ] = None,
        router_fn: Optional[Callable[[], Dict[str, Any]]] = None,
        alerts_fn: Optional[Callable[[], Dict[str, Any]]] = None,
    ) -> None:
        self._pull = pull_fn
        self._supervisor_fn = supervisor_fn
        self._router_fn = router_fn
        self._alerts_fn = alerts_fn
        self.interval_s = float(interval_s)
        self.history = max(1, int(history))
        self._events = events
        self._lock = threading.Lock()
        self._ring: List[Dict[str, Any]] = []
        self._errors = 0
        self._polls = 0
        self._reg = None
        if registry is not None:
            self._reg = {
                "replicas": registry.gauge(
                    "rlt_fleet_replicas", "Replicas in the fleet snapshot"
                ),
                "healthy": registry.gauge(
                    "rlt_fleet_replicas_healthy",
                    "Replicas whose verdict is healthy",
                ),
                "queue": registry.gauge(
                    "rlt_fleet_queue_depth", "Fleet-wide queued requests"
                ),
                "tps": registry.gauge(
                    "rlt_fleet_tokens_per_sec",
                    "Fleet-wide emitted tokens per second",
                ),
                "goodput": registry.gauge(
                    "rlt_fleet_goodput_tokens_per_device_second",
                    "Fleet emitted tokens per estimated device-second",
                ),
                "health": registry.gauge(
                    "rlt_fleet_replica_health",
                    "Per-replica health (1 healthy, 0.5 degraded, "
                    "0 unhealthy)",
                ),
                "phase_p95": registry.gauge(
                    "rlt_fleet_phase_p95_seconds",
                    "Fleet-wide anatomy phase p95 (max across "
                    "replicas), by phase",
                ),
                "polls": registry.counter(
                    "rlt_fleet_polls_total", "Fleet snapshot pulls"
                ),
                "errors": registry.counter(
                    "rlt_fleet_poll_errors_total",
                    "Fleet pulls that raised and were skipped",
                ),
            }
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # -- one poll ---------------------------------------------------------
    def poll_now(self) -> FleetSnapshot:
        """Pull + condense one snapshot NOW (raises on pull failure —
        the loop wraps it; direct callers see the real error)."""
        stats_list, health_list, heartbeats = self._pull()
        health_list = health_list or []
        rows = [
            summarize_replica(
                stats,
                health_list[i] if i < len(health_list) else None,
                index=i,
            )
            for i, stats in enumerate(stats_list)
        ]
        snap = FleetSnapshot(
            ts=time.time(),
            replicas=rows,
            fleet=aggregate_fleet(rows),
            heartbeats=dict(heartbeats or {}),
        )
        with self._lock:
            self._ring.append(snap.to_dict())
            if len(self._ring) > self.history:
                del self._ring[: len(self._ring) - self.history]
            self._polls += 1
        if self._reg is not None:
            f = snap.fleet
            self._reg["replicas"].set(f["replicas"])
            self._reg["healthy"].set(f["healthy"])
            self._reg["queue"].set(f["queue_depth"])
            self._reg["tps"].set(f["tokens_per_sec"])
            self._reg["goodput"].set(f["goodput_tokens_per_device_s"])
            for r in rows:
                self._reg["health"].set(
                    _VERDICT_SCORE.get(r["health"], 0.0),
                    replica=r["replica"],
                )
            for phase, row in (
                (f.get("phases") or {}).get("by_phase") or {}
            ).items():
                self._reg["phase_p95"].set(row["p95_s"], phase=phase)
            self._reg["polls"].inc(1)
        return snap

    # -- read side --------------------------------------------------------
    def latest(self) -> Optional[Dict[str, Any]]:
        with self._lock:
            return dict(self._ring[-1]) if self._ring else None

    def history_list(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._ring)

    def to_dict(self) -> Dict[str, Any]:
        """The ``/fleet`` payload: latest snapshot + bounded history."""
        with self._lock:
            ring = list(self._ring)
            errors = self._errors
            polls = self._polls
        out = {
            "latest": ring[-1] if ring else None,
            "history": ring,
            "polls": polls,
            "errors": errors,
            "interval_s": self.interval_s,
        }
        if self._supervisor_fn is not None:
            try:
                out["supervisor"] = self._supervisor_fn()
            except Exception:  # noqa: BLE001 - the fleet payload must
                pass  # survive a supervisor mid-teardown
        if self._router_fn is not None:
            try:
                out["router"] = self._router_fn()
            except Exception:  # noqa: BLE001 - same for the router
                pass
        if self._alerts_fn is not None:
            try:
                out["alerts"] = self._alerts_fn()
            except Exception:  # noqa: BLE001 - and the alert engine
                pass
        return out

    # -- thread lifecycle -------------------------------------------------
    def start(self) -> "FleetPoller":
        self._thread = threading.Thread(
            target=self._loop, name="obs-fleet-poller", daemon=True
        )
        self._thread.start()
        return self

    def _loop(self) -> None:
        while not self._stop.is_set():
            try:
                self.poll_now()
            except Exception as exc:  # noqa: BLE001 - a dead replica
                # must not kill the plane that would report it dead.
                with self._lock:
                    self._errors += 1
                if self._reg is not None:
                    self._reg["errors"].inc(1)
                if self._events is not None:
                    self._events.record(
                        "fleet", "poll_error", level="warn",
                        error=f"{type(exc).__name__}: {exc}"[:200],
                    )
            self._stop.wait(self.interval_s)

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None
