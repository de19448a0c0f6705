"""Watchtower: retained telemetry + a burn-rate alert engine + canary.

The layer that notices a regression BEFORE a user does. Three pieces,
composed over :class:`obs.tsdb.RingTSDB`:

1. **Feeds** — each tick the watchtower samples the latest fleet
   snapshot (queue depth, tokens/s, goodput, healthy count, the
   SLO-breach ratio diffed from cumulative counters) into named series,
   and optionally ingests the driver's aggregated ``/metrics`` text so
   counter families land as ``:rate`` series.
2. **Alert engine** — declarative :class:`AlertRule`\\ s (static
   ``threshold``, ``absence``/flatline, and multi-window multi-burn-rate
   over the SLO-breach ratio, the SRE-literature shape: a FAST window
   catches a cliff, a SLOW window must agree so a blip doesn't page)
   evaluated each tick with a pending -> firing -> resolved state
   machine: a rule must breach ``for_ticks`` consecutive evaluations to
   fire (pending hold), stay clean ``resolve_ticks`` to resolve
   (hysteresis), and while firing re-notifies at most every
   ``renotify_s`` (dedup). Transitions emit ``alert_firing`` /
   ``alert_resolved`` events carrying the triggering value AND the top
   anatomy phases (PR 19's breach attribution) — the page says *what*
   and *why* in one line.
3. **Canary lane** — a tiny fixed-seed probe submitted periodically
   under the reserved ``_canary`` tenant at floor priority, its
   TTFT / decode rate / exactness recorded as dedicated series and
   checked against a recorded baseline envelope. A wedged-but-
   heartbeating replica or a perf regression after a weight push is
   caught with zero organic traffic. Canary traffic is excluded from
   organic accounting end to end (cost ledger, goodput, autoscaler
   pressure — see serve.metrics.CANARY_TENANT).

Sinks follow the kvstore ``s3://`` pattern: the :class:`LogSink` is
fully real; the :class:`WebhookSink` is webhook-SHAPED — URL parsing,
payload shaping, and delivery accounting are real so config and
journals round-trip it, but the default transport records the would-be
POST instead of opening a socket (inject ``post_fn`` to make it real).

All clocks are injectable; the engine is driven by ``Watchtower.tick``
(its own daemon thread in ``rlt serve``, a fake clock in tests).
"""
from __future__ import annotations

import json
import logging
import threading
import time
from collections import deque
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple
from urllib.parse import urlparse

from ray_lightning_tpu.obs.anatomy import (
    breach_attribution,
    format_attribution,
)
from ray_lightning_tpu.obs.tsdb import RingTSDB

logger = logging.getLogger("rlt.watchtower")

#: The reserved canary tenant — must match serve.metrics.CANARY_TENANT
#: (kept as a literal here so obs does not import serve).
CANARY_TENANT = "_canary"

#: Floor priority for canary probes: the pending heap pops the SMALLEST
#: priority first, so the probe never displaces organic work.
CANARY_PRIORITY = 1_000_000

_SEVERITY_RANK = {"error": 0, "warn": 1, "info": 2}

_VERDICT_SCORE = {"healthy": 1.0, "degraded": 0.5, "unhealthy": 0.0}


# -- rules ---------------------------------------------------------------
@dataclass
class AlertRule:
    """One declarative rule. ``kind``:

    - ``threshold``: latest sample of ``series`` (within ``window_s``)
      compared ``op`` (``>`` / ``<``) against ``threshold``;
    - ``absence``: no new sample on ``series`` for ``window_s`` (the
      feed died); with ``flatline=True`` also breaches when samples
      keep arriving but the value has not changed across the window;
    - ``burn_rate``: mean of ``series`` over ``fast_window_s`` exceeds
      ``fast_burn`` AND mean over ``slow_window_s`` exceeds
      ``slow_burn`` — both windows must agree.

    Lifecycle: ``for_ticks`` consecutive breaching evaluations to fire,
    ``resolve_ticks`` consecutive clean ones to resolve, ``renotify_s``
    between repeat notifications while firing.
    """

    name: str
    kind: str
    series: str
    op: str = ">"
    threshold: float = 0.0
    window_s: float = 30.0
    flatline: bool = False
    fast_window_s: float = 60.0
    slow_window_s: float = 600.0
    fast_burn: float = 0.1
    slow_burn: float = 0.05
    for_ticks: int = 2
    resolve_ticks: int = 2
    renotify_s: float = 300.0
    severity: str = "warn"

    def __post_init__(self) -> None:
        if self.kind not in ("threshold", "absence", "burn_rate"):
            raise ValueError(
                f"alert rule {self.name!r}: unknown kind {self.kind!r} "
                "(threshold | absence | burn_rate)"
            )
        if self.op not in (">", "<"):
            raise ValueError(
                f"alert rule {self.name!r}: op must be '>' or '<'"
            )
        if self.severity not in _SEVERITY_RANK:
            raise ValueError(
                f"alert rule {self.name!r}: severity {self.severity!r} "
                f"not in {sorted(_SEVERITY_RANK)}"
            )

    def to_dict(self) -> Dict[str, Any]:
        d = {
            "name": self.name, "kind": self.kind, "series": self.series,
            "for_ticks": self.for_ticks, "resolve_ticks": self.resolve_ticks,
            "renotify_s": self.renotify_s, "severity": self.severity,
        }
        if self.kind == "threshold":
            d.update(op=self.op, threshold=self.threshold,
                     window_s=self.window_s)
        elif self.kind == "absence":
            d.update(window_s=self.window_s, flatline=self.flatline)
        else:
            d.update(fast_window_s=self.fast_window_s,
                     slow_window_s=self.slow_window_s,
                     fast_burn=self.fast_burn, slow_burn=self.slow_burn)
        return d


def parse_alert_rules(obj: Any) -> List[AlertRule]:
    """Rules from config: a list of rule dicts, or a mapping
    ``{name: rule_dict}`` (the name key wins). Unknown fields are
    rejected loudly — a typoed threshold must not silently never fire."""
    if obj is None:
        return []
    rows: List[Dict[str, Any]] = []
    if isinstance(obj, dict):
        for name, row in obj.items():
            if not isinstance(row, dict):
                raise ValueError(
                    f"alert rule {name!r}: expected a mapping, got {row!r}"
                )
            rows.append({"name": str(name), **row})
    elif isinstance(obj, (list, tuple)):
        rows = [dict(r) for r in obj]
    else:
        raise ValueError(
            f"alert rules: expected a list or mapping, got {type(obj).__name__}"
        )
    allowed = set(AlertRule.__dataclass_fields__)
    out = []
    for row in rows:
        unknown = set(row) - allowed
        if unknown:
            raise ValueError(
                f"alert rule {row.get('name', '?')!r}: unknown fields "
                f"{sorted(unknown)} (allowed: {sorted(allowed)})"
            )
        out.append(AlertRule(**row))
    return out


def default_rules() -> List[AlertRule]:
    """The always-on fleet rules ``rlt serve`` installs (overridable
    via ``--serve.alerts_rules``)."""
    return [
        AlertRule(
            name="slo_burn_rate", kind="burn_rate",
            series="fleet.slo_breach_ratio",
            fast_window_s=60.0, slow_window_s=600.0,
            fast_burn=0.1, slow_burn=0.05,
            for_ticks=2, resolve_ticks=2, severity="error",
        ),
        AlertRule(
            name="replica_unhealthy", kind="threshold",
            series="fleet.unhealthy", op=">", threshold=0.0,
            window_s=30.0, for_ticks=3, resolve_ticks=2,
        ),
        AlertRule(
            name="telemetry_absent", kind="absence",
            series="fleet.replicas", window_s=30.0,
            for_ticks=1, resolve_ticks=1,
        ),
        AlertRule(
            name="kvstore_write_errors", kind="threshold",
            series="fleet.kvstore_write_errors:rate", op=">",
            threshold=0.0, window_s=60.0, for_ticks=2,
        ),
    ]


def canary_rules(baseline: Optional[Dict[str, Any]] = None) -> List[AlertRule]:
    """Rules the canary lane adds: exactness is always-on (a wrong
    token is a correctness incident, fires on the first probe), the
    latency/rate envelope rules need a recorded baseline."""
    rules = [
        AlertRule(
            name="canary_exactness", kind="threshold",
            series="canary.exact", op="<", threshold=1.0,
            window_s=900.0, for_ticks=1, resolve_ticks=1,
            severity="error",
        ),
        AlertRule(
            name="canary_absent", kind="absence",
            series="canary.exact", window_s=120.0,
            for_ticks=1, resolve_ticks=1,
        ),
    ]
    if baseline:
        rules.append(AlertRule(
            name="canary_envelope", kind="threshold",
            series="canary.deviation", op=">", threshold=1.0,
            window_s=900.0, for_ticks=2, resolve_ticks=2,
        ))
    return rules


# -- sinks ---------------------------------------------------------------
class LogSink:
    """The real sink: transitions land in the process log (and a small
    ring so ``/alerts`` can show recent deliveries)."""

    name = "log"

    def __init__(self, capacity: int = 256) -> None:
        self.delivered: deque = deque(maxlen=capacity)

    def notify(self, payload: Dict[str, Any]) -> None:
        self.delivered.append(dict(payload))
        msg = (
            f"alert {payload.get('state')}: {payload.get('rule')} "
            f"({payload.get('detail')})"
        )
        if payload.get("state") == "firing":
            logger.warning(msg)
        else:
            logger.info(msg)


class WebhookSink:
    """Webhook-SHAPED sink, stub transport (the kvstore ``s3://``
    pattern): the URL is parsed and validated, every notification is
    shaped into the POST that WOULD go out (json body, content-type)
    and recorded in ``sent`` — but no socket opens unless a real
    ``post_fn(url, body_bytes, headers)`` is injected."""

    name = "webhook"

    def __init__(
        self,
        url: str,
        post_fn: Optional[Callable[[str, bytes, Dict[str, str]], Any]] = None,
        capacity: int = 256,
    ) -> None:
        parsed = urlparse(str(url))
        if parsed.scheme not in ("http", "https") or not parsed.netloc:
            raise ValueError(
                f"webhook sink URL {url!r} is not http(s)://host[/path]"
            )
        self.url = str(url)
        self._post = post_fn
        self.sent: deque = deque(maxlen=capacity)
        self.errors = 0

    def notify(self, payload: Dict[str, Any]) -> None:
        body = json.dumps(payload, default=str).encode()
        headers = {"Content-Type": "application/json"}
        self.sent.append({"url": self.url, "body": body.decode()})
        if self._post is None:
            return  # stub transport: the request is shaped, not sent
        try:
            self._post(self.url, body, headers)
        except Exception as exc:  # noqa: BLE001 - a dead webhook must
            self.errors += 1  # never take down the alert engine
            logger.warning("webhook sink %s failed: %s", self.url, exc)


# -- engine --------------------------------------------------------------
@dataclass
class _RuleState:
    state: str = "ok"  # ok | pending | firing
    consecutive_bad: int = 0
    consecutive_ok: int = 0
    since_ts: Optional[float] = None
    fired_ts: Optional[float] = None
    last_notify_ts: Optional[float] = None
    value: Optional[float] = None
    detail: str = ""
    fires: int = 0
    resolves: int = 0


class AlertEngine:
    """Evaluates rules over the TSDB each tick and owns alert state."""

    def __init__(
        self,
        tsdb: RingTSDB,
        rules: Sequence[AlertRule],
        events: Optional[Any] = None,
        sinks: Sequence[Any] = (),
        registry: Optional[Any] = None,
        attribution_fn: Optional[Callable[[], str]] = None,
        clock: Callable[[], float] = time.time,
    ) -> None:
        self.tsdb = tsdb
        self.rules = list(rules)
        names = [r.name for r in self.rules]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate alert rule names in {names}")
        self._events = events
        self._sinks = list(sinks)
        self._attribution_fn = attribution_fn
        self._clock = clock
        self._lock = threading.Lock()
        self._state: Dict[str, _RuleState] = {
            r.name: _RuleState() for r in self.rules
        }
        self._evaluations = 0
        self._reg = None
        if registry is not None:
            self._reg = {
                "evals": registry.counter(
                    "rlt_alert_evaluations_total",
                    "Alert engine evaluation ticks",
                ),
                "transitions": registry.counter(
                    "rlt_alert_transitions_total",
                    "Alert state transitions, by target state",
                ),
                "firing": registry.gauge(
                    "rlt_alert_firing", "Rules currently in the firing state"
                ),
                "notifications": registry.counter(
                    "rlt_alert_notifications_total",
                    "Alert notifications delivered, by sink",
                ),
            }

    # -- rule conditions --------------------------------------------------
    def _eval_rule(
        self, rule: AlertRule, now: float
    ) -> Tuple[bool, Optional[float], str]:
        if rule.kind == "threshold":
            vals = self.tsdb.values(rule.series, rule.window_s, now=now)
            if not vals:
                return False, None, f"{rule.series}: no samples"
            v = vals[-1]
            bad = v > rule.threshold if rule.op == ">" else v < rule.threshold
            return bad, v, (
                f"{rule.series}={round(v, 6)} {rule.op} {rule.threshold}"
            )
        if rule.kind == "absence":
            last = self.tsdb.latest(rule.series)
            if last is None:
                # Startup grace: a series that never reported is the
                # feed not having started, not the feed having died.
                return False, None, f"{rule.series}: never reported"
            age = now - last[0]
            if age > rule.window_s:
                return True, last[1], (
                    f"{rule.series}: no samples for {round(age, 1)}s "
                    f"(window {rule.window_s}s)"
                )
            if rule.flatline:
                vals = self.tsdb.values(rule.series, rule.window_s, now=now)
                if len(vals) >= 3 and max(vals) == min(vals):
                    return True, vals[-1], (
                        f"{rule.series}: flatlined at {round(vals[-1], 6)} "
                        f"over {rule.window_s}s"
                    )
            return False, last[1], f"{rule.series}: live"
        # burn_rate: both windows must agree.
        fast = self.tsdb.values(rule.series, rule.fast_window_s, now=now)
        slow = self.tsdb.values(rule.series, rule.slow_window_s, now=now)
        if not fast or not slow:
            return False, None, f"{rule.series}: no samples"
        f_mean = sum(fast) / len(fast)
        s_mean = sum(slow) / len(slow)
        bad = f_mean > rule.fast_burn and s_mean > rule.slow_burn
        return bad, f_mean, (
            f"{rule.series}: fast({rule.fast_window_s}s)="
            f"{round(f_mean, 4)} vs {rule.fast_burn}, "
            f"slow({rule.slow_window_s}s)={round(s_mean, 4)} "
            f"vs {rule.slow_burn}"
        )

    # -- the tick ---------------------------------------------------------
    def evaluate(self, now: Optional[float] = None) -> List[Dict[str, Any]]:
        """One evaluation pass; returns the notifications emitted this
        tick (fire / re-notify / resolve payloads, for tests and the
        watchtower's own bookkeeping)."""
        now = self._clock() if now is None else float(now)
        notifications: List[Dict[str, Any]] = []
        with self._lock:
            self._evaluations += 1
            for rule in self.rules:
                st = self._state[rule.name]
                bad, value, detail = self._eval_rule(rule, now)
                st.value, st.detail = value, detail
                if bad:
                    st.consecutive_ok = 0
                    st.consecutive_bad += 1
                    if st.state == "ok":
                        st.state = "pending"
                        st.since_ts = now
                        self._transition("pending")
                    if (
                        st.state == "pending"
                        and st.consecutive_bad >= rule.for_ticks
                    ):
                        st.state = "firing"
                        st.fired_ts = now
                        st.fires += 1
                        st.last_notify_ts = now
                        self._transition("firing")
                        notifications.append(
                            self._notify(rule, st, "firing", now)
                        )
                    elif (
                        st.state == "firing"
                        and now - (st.last_notify_ts or now)
                        >= rule.renotify_s
                    ):
                        st.last_notify_ts = now
                        notifications.append(
                            self._notify(rule, st, "firing", now,
                                         renotify=True)
                        )
                else:
                    st.consecutive_bad = 0
                    if st.state == "pending":
                        st.state = "ok"
                        st.since_ts = None
                        self._transition("ok")
                    elif st.state == "firing":
                        st.consecutive_ok += 1
                        if st.consecutive_ok >= rule.resolve_ticks:
                            st.state = "ok"
                            st.resolves += 1
                            self._transition("ok")
                            notifications.append(
                                self._notify(rule, st, "resolved", now)
                            )
                            st.since_ts = st.fired_ts = None
                            st.last_notify_ts = None
            firing = sum(
                1 for s in self._state.values() if s.state == "firing"
            )
        if self._reg is not None:
            self._reg["evals"].inc(1)
            self._reg["firing"].set(firing)
        return notifications

    def _transition(self, to: str) -> None:
        if self._reg is not None:
            self._reg["transitions"].inc(1, to=to)

    def _notify(
        self,
        rule: AlertRule,
        st: _RuleState,
        state: str,
        now: float,
        renotify: bool = False,
    ) -> Dict[str, Any]:
        attribution = ""
        if self._attribution_fn is not None:
            try:
                attribution = self._attribution_fn() or ""
            except Exception:  # noqa: BLE001 - attribution is garnish;
                pass  # its failure must not eat the page
        payload = {
            "rule": rule.name,
            "kind": rule.kind,
            "series": rule.series,
            "severity": rule.severity,
            "state": state,
            "renotify": renotify,
            "value": st.value,
            "detail": st.detail,
            "since_ts": st.since_ts,
            "duration_s": (
                round(now - st.since_ts, 3) if st.since_ts else 0.0
            ),
            "attribution": attribution,
            "ts": now,
        }
        if self._events is not None:
            self._events.record(
                "watchtower",
                "alert_firing" if state == "firing" else "alert_resolved",
                level=(
                    rule.severity if state == "firing" else "info"
                ),
                rule=rule.name, series=rule.series, value=st.value,
                detail=st.detail, attribution=attribution,
                renotify=renotify, duration_s=payload["duration_s"],
            )
        for sink in self._sinks:
            try:
                sink.notify(payload)
                if self._reg is not None:
                    self._reg["notifications"].inc(
                        1, sink=getattr(sink, "name", "sink")
                    )
            except Exception as exc:  # noqa: BLE001 - one bad sink
                logger.warning(  # must not mute the others
                    "alert sink %s failed: %s",
                    getattr(sink, "name", sink), exc,
                )
        return payload

    # -- read side --------------------------------------------------------
    def firing(self) -> List[Dict[str, Any]]:
        """Currently-firing rules, worst first (severity, then oldest)."""
        by_rule = {r.name: r for r in self.rules}
        with self._lock:
            rows = [
                {"rule": name, "severity": by_rule[name].severity,
                 "series": by_rule[name].series, "value": st.value,
                 "detail": st.detail, "fired_ts": st.fired_ts}
                for name, st in self._state.items()
                if st.state == "firing"
            ]
        rows.sort(key=lambda r: (
            _SEVERITY_RANK.get(r["severity"], 9), r["fired_ts"] or 0.0,
        ))
        return rows

    def to_dict(self) -> Dict[str, Any]:
        with self._lock:
            states = {
                name: {
                    "state": st.state,
                    "consecutive_bad": st.consecutive_bad,
                    "consecutive_ok": st.consecutive_ok,
                    "value": st.value,
                    "detail": st.detail,
                    "since_ts": st.since_ts,
                    "fired_ts": st.fired_ts,
                    "fires": st.fires,
                    "resolves": st.resolves,
                }
                for name, st in self._state.items()
            }
            evaluations = self._evaluations
        return {
            "rules": [r.to_dict() for r in self.rules],
            "states": states,
            "firing": self.firing(),
            "evaluations": evaluations,
        }


# -- canary --------------------------------------------------------------
class CanaryLane:
    """Periodic fixed-seed probe through the REAL serving path.

    The probe is greedy (temperature 0, fixed seed) so its output is
    deterministic: exactness (generated tokens == the reference) is a
    correctness canary, TTFT / decode rate against the baseline
    envelope is a performance canary. The reference tokens come from
    the recorded baseline when one is given, else from the first
    successful probe (self-baseline).

    ``baseline`` (``--serve.canary_baseline``: a JSON file of this form,
    recorded from a healthy fleet's probe)::

        {"prompt": [...], "max_new_tokens": n, "tokens": [...],
         "ttft_s": f, "decode_tokens_per_s": f,
         "ttft_mult": 3.0, "decode_frac": 0.33}

    ``deviation`` is the worst envelope ratio (>1 = outside): TTFT over
    ``ttft_s * ttft_mult``, or the decode floor
    ``decode_tokens_per_s * decode_frac`` over the observed rate.
    """

    #: Default probe: a tiny deterministic prompt.
    DEFAULT_PROMPT = (1, 2, 3, 5, 8, 13)

    def __init__(
        self,
        client: Any,
        tsdb: RingTSDB,
        *,
        prompt: Optional[Sequence[int]] = None,
        max_new_tokens: int = 12,
        interval_s: float = 10.0,
        baseline: Optional[Dict[str, Any]] = None,
        timeout_s: float = 60.0,
        events: Optional[Any] = None,
        registry: Optional[Any] = None,
        clock: Callable[[], float] = time.time,
    ) -> None:
        self.client = client
        self.tsdb = tsdb
        self.baseline = dict(baseline) if baseline else None
        if self.baseline and self.baseline.get("prompt"):
            prompt = [int(t) for t in self.baseline["prompt"]]
            max_new_tokens = int(
                self.baseline.get("max_new_tokens", max_new_tokens)
            )
        self.prompt = list(prompt if prompt is not None else
                           self.DEFAULT_PROMPT)
        self.max_new_tokens = int(max_new_tokens)
        self.interval_s = float(interval_s)
        self.timeout_s = float(timeout_s)
        self._events = events
        self._clock = clock
        self._reference: Optional[List[int]] = (
            [int(t) for t in self.baseline["tokens"]]
            if self.baseline and self.baseline.get("tokens") else None
        )
        self._last_probe_ts: Optional[float] = None
        self.probes = 0
        self.errors = 0
        self.last: Optional[Dict[str, Any]] = None
        self._reg = None
        if registry is not None:
            self._reg = {
                "probes": registry.counter(
                    "rlt_canary_probes_total", "Canary probes run, by outcome"
                ),
                "ttft": registry.gauge(
                    "rlt_canary_ttft_seconds", "Latest canary probe TTFT"
                ),
                "decode": registry.gauge(
                    "rlt_canary_decode_tokens_per_second",
                    "Latest canary probe decode rate",
                ),
                "exact": registry.gauge(
                    "rlt_canary_exact",
                    "Latest canary probe exactness (1 = bit-exact)",
                ),
                "deviation": registry.gauge(
                    "rlt_canary_deviation",
                    "Latest canary probe worst envelope ratio "
                    "(>1 = outside the baseline envelope)",
                ),
            }

    def tick(self, now: Optional[float] = None) -> Optional[Dict[str, Any]]:
        """Run a probe when one is due (``interval_s`` since the last)."""
        now = self._clock() if now is None else float(now)
        if (
            self._last_probe_ts is not None
            and now - self._last_probe_ts < self.interval_s
        ):
            return None
        return self.probe(now=now)

    def probe(self, now: Optional[float] = None) -> Dict[str, Any]:
        """One probe through the organic submit/stream path, under the
        reserved tenant at floor priority."""
        now = self._clock() if now is None else float(now)
        self._last_probe_ts = now
        self.probes += 1
        t0 = time.monotonic()
        first: Optional[float] = None
        tokens: List[int] = []
        try:
            for tok in self.client.stream(
                self.prompt,
                max_new_tokens=self.max_new_tokens,
                temperature=0.0,
                seed=0,
                priority=CANARY_PRIORITY,
                tenant=CANARY_TENANT,
                timeout_s=self.timeout_s,
            ):
                if first is None:
                    first = time.monotonic()
                tokens.append(int(tok))
        except Exception as exc:  # noqa: BLE001 - a failed probe is a
            # SIGNAL (recorded, alertable), never a watchtower crash.
            self.errors += 1
            if self._reg is not None:
                self._reg["probes"].inc(1, outcome="error")
            if self._events is not None:
                self._events.record(
                    "watchtower", "canary_error", level="warn",
                    error=f"{type(exc).__name__}: {exc}"[:200],
                )
            self.tsdb.record("canary.error", 1.0, ts=now)
            self.last = {
                "ts": now, "ok": False,
                "error": f"{type(exc).__name__}: {exc}"[:200],
            }
            return self.last
        t1 = time.monotonic()
        ttft = (first - t0) if first is not None else (t1 - t0)
        decode_s = (t1 - first) if first is not None else 0.0
        decode_rate = (
            (len(tokens) - 1) / decode_s
            if len(tokens) > 1 and decode_s > 0 else 0.0
        )
        if self._reference is None:
            self._reference = list(tokens)  # self-baseline: first probe
        exact = int(tokens == self._reference)
        deviation = 0.0
        if self.baseline:
            base_ttft = float(self.baseline.get("ttft_s") or 0.0)
            mult = float(self.baseline.get("ttft_mult", 3.0))
            if base_ttft > 0:
                deviation = max(deviation, ttft / (base_ttft * mult))
            base_decode = float(
                self.baseline.get("decode_tokens_per_s") or 0.0
            )
            frac = float(self.baseline.get("decode_frac", 0.33))
            if base_decode > 0 and decode_rate > 0:
                deviation = max(
                    deviation, (base_decode * frac) / decode_rate
                )
        self.tsdb.record("canary.ttft_s", ttft, ts=now)
        self.tsdb.record("canary.decode_tokens_per_s", decode_rate, ts=now)
        self.tsdb.record("canary.exact", float(exact), ts=now)
        self.tsdb.record("canary.deviation", deviation, ts=now)
        if self._reg is not None:
            self._reg["probes"].inc(
                1, outcome="exact" if exact else "mismatch"
            )
            self._reg["ttft"].set(round(ttft, 6))
            self._reg["decode"].set(round(decode_rate, 3))
            self._reg["exact"].set(float(exact))
            self._reg["deviation"].set(round(deviation, 4))
        if not exact and self._events is not None:
            self._events.record(
                "watchtower", "canary_mismatch", level="error",
                tokens=tokens[:16], reference=(self._reference or [])[:16],
            )
        self.last = {
            "ts": now, "ok": True, "exact": exact,
            "ttft_s": round(ttft, 6),
            "decode_tokens_per_s": round(decode_rate, 3),
            "deviation": round(deviation, 4),
            "tokens": len(tokens),
        }
        return self.last

    def to_dict(self) -> Dict[str, Any]:
        return {
            "interval_s": self.interval_s,
            "prompt_tokens": len(self.prompt),
            "max_new_tokens": self.max_new_tokens,
            "baseline": bool(self.baseline),
            "probes": self.probes,
            "errors": self.errors,
            "last": self.last,
        }


# -- the tower -----------------------------------------------------------
class Watchtower:
    """TSDB + alert engine + canary, driven by one periodic tick.

    Feeds:

    - ``fleet_latest_fn`` (zero-arg -> the latest FleetPoller snapshot
      dict, or None): sampled into ``fleet.*`` / ``replica<i>.*``
      gauge series, with the SLO-breach ratio diffed from the
      cumulative breach/finished counters;
    - ``metrics_text_fn`` (zero-arg -> exposition text): counter
      families become ``:rate`` series (bounded by
      ``metrics_families`` prefixes).

    ``tick()`` is the unit of evaluation (tests drive it with a fake
    clock); ``start()`` runs it on a daemon thread every
    ``interval_s`` — the serve driver's wiring.
    """

    #: Metric-family prefixes retained from a /metrics ingest by
    #: default — the families the default rules and dashboards read.
    DEFAULT_FAMILIES = (
        "rlt_kvstore_write_errors",
        "rlt_serve_requests_total",
        "rlt_serve_tokens_emitted_total",
    )

    def __init__(
        self,
        *,
        tsdb: Optional[RingTSDB] = None,
        rules: Optional[Sequence[AlertRule]] = None,
        fleet_latest_fn: Optional[Callable[[], Optional[Dict[str, Any]]]] = None,
        metrics_text_fn: Optional[Callable[[], str]] = None,
        metrics_families: Optional[Sequence[str]] = DEFAULT_FAMILIES,
        canary: Optional[CanaryLane] = None,
        sinks: Sequence[Any] = (),
        events: Optional[Any] = None,
        registry: Optional[Any] = None,
        interval_s: float = 2.0,
        clock: Callable[[], float] = time.time,
    ) -> None:
        self.tsdb = tsdb if tsdb is not None else RingTSDB(registry=registry)
        self.canary = canary
        all_rules = list(
            rules if rules is not None else default_rules()
        )
        if canary is not None:
            have = {r.name for r in all_rules}
            all_rules += [
                r for r in canary_rules(canary.baseline)
                if r.name not in have
            ]
        self.engine = AlertEngine(
            self.tsdb, all_rules, events=events, sinks=sinks,
            registry=registry, attribution_fn=self._attribution,
            clock=clock,
        )
        self._fleet_latest_fn = fleet_latest_fn
        self._metrics_text_fn = metrics_text_fn
        self._families = (
            tuple(metrics_families) if metrics_families else None
        )
        self._events = events
        self._clock = clock
        self.interval_s = float(interval_s)
        self._lock = threading.Lock()
        self._last_snap_ts: Optional[float] = None
        self._last_slo: Optional[Tuple[int, int]] = None
        self._last_phases: Optional[Dict[str, Any]] = None
        self._ticks = 0
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # -- feeds ------------------------------------------------------------
    def observe_fleet(
        self, snap: Optional[Dict[str, Any]], now: Optional[float] = None
    ) -> None:
        """Sample one fleet snapshot into the TSDB (idempotent per
        snapshot ``ts`` — a tick faster than the poller re-sees the
        same snapshot and must not double-count the SLO delta)."""
        if not snap:
            return
        now = self._clock() if now is None else float(now)
        with self._lock:
            if snap.get("ts") == self._last_snap_ts:
                return
            self._last_snap_ts = snap.get("ts")
        fleet = snap.get("fleet") or {}
        rows = snap.get("replicas") or []
        rec = self.tsdb.record
        rec("fleet.replicas", fleet.get("replicas", len(rows)), ts=now)
        rec("fleet.healthy", fleet.get("healthy", 0), ts=now)
        rec(
            "fleet.unhealthy",
            int(fleet.get("replicas", len(rows)))
            - int(fleet.get("healthy", 0)),
            ts=now,
        )
        rec("fleet.queue_depth", fleet.get("queue_depth", 0), ts=now)
        rec("fleet.tokens_per_sec", fleet.get("tokens_per_sec", 0.0), ts=now)
        rec(
            "fleet.goodput_tokens_per_device_s",
            fleet.get("goodput_tokens_per_device_s", 0.0), ts=now,
        )
        if fleet.get("ttft_p95_s_worst") is not None:
            rec("fleet.ttft_p95_s", fleet["ttft_p95_s_worst"], ts=now)
        phases = fleet.get("phases") or None
        if phases:
            self._last_phases = phases
            if phases.get("hot_phase_p95_s") is not None:
                rec("fleet.hot_phase_p95_s",
                    phases["hot_phase_p95_s"], ts=now)
        self.tsdb.record_counter(
            "fleet.kvstore_write_errors",
            fleet.get("kvstore_write_errors", 0), ts=now,
        )
        self.tsdb.record_counter(
            "fleet.kvfleet_fetch_timeouts",
            fleet.get("kvfleet_fetch_timeouts", 0), ts=now,
        )
        # SLO-breach ratio: breaches opened per request finished over
        # the inter-snapshot interval (cumulative counters diffed).
        breaches = sum(int(r.get("slo_breaches") or 0) for r in rows)
        finished = sum(int(r.get("finished") or 0) for r in rows)
        with self._lock:
            prev = self._last_slo
            self._last_slo = (breaches, finished)
        if prev is not None:
            d_b = max(0, breaches - prev[0])
            d_f = max(0, finished - prev[1])
            ratio = (
                d_b / d_f if d_f > 0 else (1.0 if d_b > 0 else 0.0)
            )
            rec("fleet.slo_breach_ratio", min(1.0, ratio), ts=now)
        for r in rows:
            i = r.get("replica", 0)
            rec(f"replica{i}.queue_depth", r.get("queue_depth", 0), ts=now)
            rec(
                f"replica{i}.tokens_per_sec",
                r.get("tokens_per_sec", 0.0), ts=now,
            )
            rec(
                f"replica{i}.health",
                _VERDICT_SCORE.get(str(r.get("health")), 0.0), ts=now,
            )

    def _attribution(self) -> str:
        """Top anatomy phases for the latest fleet snapshot — rides
        every alert notification so the page names the hot phase."""
        with self._lock:
            phases = self._last_phases
        return format_attribution(breach_attribution(phases))

    # -- the tick ---------------------------------------------------------
    def tick(self, now: Optional[float] = None) -> List[Dict[str, Any]]:
        """Feed + canary + evaluate: one watchtower cycle. Returns the
        alert notifications emitted."""
        now = self._clock() if now is None else float(now)
        if self._fleet_latest_fn is not None:
            try:
                self.observe_fleet(self._fleet_latest_fn(), now=now)
            except Exception:  # noqa: BLE001 - a feed hiccup must not
                pass  # stop evaluation (absence rules cover a dead feed)
        if self._metrics_text_fn is not None:
            try:
                self.tsdb.ingest_prometheus(
                    self._metrics_text_fn(), ts=now,
                    families=self._families,
                )
            except Exception:  # noqa: BLE001 - same
                pass
        if self.canary is not None:
            self.canary.tick(now=now)
        with self._lock:
            self._ticks += 1
        return self.engine.evaluate(now=now)

    # -- read side --------------------------------------------------------
    def alerts_payload(self) -> Dict[str, Any]:
        """The ``/alerts`` route body."""
        with self._lock:
            ticks = self._ticks
        return {
            "ticks": ticks,
            "interval_s": self.interval_s,
            "alerts": self.engine.to_dict(),
            "canary": self.canary.to_dict() if self.canary else None,
            "tsdb": self.tsdb.to_dict(),
            "series": self.tsdb.series_names(),
        }

    def fleet_block(self) -> Dict[str, Any]:
        """The compact ``alerts`` block embedded in the ``/fleet``
        payload (``rlt top``'s ``alerts:`` line)."""
        firing = self.engine.firing()
        return {
            "firing": len(firing),
            "names": [
                f"{r['rule']}({r['severity']})" for r in firing
            ],
        }

    def query(self, params: Dict[str, List[str]]) -> Dict[str, Any]:
        """The ``/query`` route: ``?series=`` (required), optional
        ``since=`` (unix seconds) and ``step=`` (seconds)."""
        series = (params.get("series") or [None])[0]
        if not series:
            raise ValueError("missing ?series=<name>")
        since = params.get("since")
        step = params.get("step")
        return self.tsdb.query(
            series,
            since=float(since[0]) if since else None,
            step=float(step[0]) if step else None,
        )

    # -- thread lifecycle -------------------------------------------------
    def start(self) -> "Watchtower":
        self._thread = threading.Thread(
            target=self._loop, name="obs-watchtower", daemon=True
        )
        self._thread.start()
        return self

    def _loop(self) -> None:
        while not self._stop.is_set():
            try:
                self.tick()
            except Exception as exc:  # noqa: BLE001 - the watcher must
                # outlive anything it watches.
                logger.warning("watchtower tick failed: %s", exc)
                if self._events is not None:
                    self._events.record(
                        "watchtower", "tick_error", level="warn",
                        error=f"{type(exc).__name__}: {exc}"[:200],
                    )
            self._stop.wait(self.interval_s)

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None
