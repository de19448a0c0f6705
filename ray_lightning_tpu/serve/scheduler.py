"""Continuous-batching scheduler: iteration-level admission over a
DecodeEngine.

Orca-style scheduling loop: at every step boundary the scheduler (1)
drops cancelled/expired work, (2) admits queued requests into free engine
slots — bounded by ``max_prefills_per_step`` so a burst of prompt
prefills can't starve in-flight decode latency (the prefill/decode
interleave policy), (3) advances up to ``max_prefill_chunks_per_step``
chunks of in-progress chunked prefills (engines built with
``prefill_chunk`` — a long prompt's prefill then interleaves with decode
folds instead of freezing them for its whole admission), (4) runs one
decode iteration for everything resident. Requests carry per-request
sampling params, an optional priority (lower value = served first; FIFO
within a priority, with optional aging toward priority 0 via
``priority_age_s`` so sustained high-priority traffic can't starve the
rest forever), and an optional deadline.

The scheduler also keeps the COST LEDGER: per-request accounting
(queue seconds, prefill chunks, prefix-cache hits, decode folds,
speculative accept shares, emitted tokens, and an estimated
device-seconds figure — each step's wall time split over its resident
requests) accumulated from submit to terminal and emitted as one
record at finish/cancel/expire through ``ServeMetrics.record_cost``
(windowed ``cost`` stats + tenant-labelled ``rlt_serve_request_cost_*``
series) and a ``request_cost`` typed event. Emitted-token totals
balance exactly against the engine token counter (test-asserted), so
goodput — emitted tokens per device-second — is a true ratio.

The scheduler owns no threads: ``step()`` is driven by whoever hosts the
engine (ServeReplica's loop thread, or a test). ``submit`` /
``cancel`` are thread-safe so a replica's RPC surface can feed the loop.
The lock guards ONLY the queue/bookkeeping state: ``step()`` snapshots
its decisions under the lock and runs every engine call (prefill,
decode dispatch, harvest) outside it, so the RPC surface never stalls
behind device compute — with a folded engine a single dispatch can cover
``decode_fold`` tokens of wall time.
"""
from __future__ import annotations

import contextlib
import heapq
import itertools
import threading
import time
import uuid
from dataclasses import dataclass, field
from typing import TYPE_CHECKING, Any, Dict, List, Optional, Sequence

from ray_lightning_tpu.obs import trace as _trace
from ray_lightning_tpu.obs.trace import SpanTotals, span
from ray_lightning_tpu.serve.metrics import CANARY_TENANT, ServeMetrics

if TYPE_CHECKING:  # engine pulls jax; keep the package import light
    from ray_lightning_tpu.obs.events import EventLog
    from ray_lightning_tpu.obs.journal import WorkloadJournal
    from ray_lightning_tpu.obs.trace import RequestTracer
    from ray_lightning_tpu.serve.engine import DecodeEngine


@dataclass(frozen=True)
class SamplingParams:
    """Per-request decode knobs (the engine consumes them as traced
    per-slot arrays, so any mix shares one compiled step)."""

    max_new_tokens: int = 32
    temperature: float = 0.0
    top_k: Optional[int] = None
    top_p: Optional[float] = None
    seed: int = 0
    eos_token: Optional[int] = None


@dataclass
class Request:
    prompt: List[int]
    sampling: SamplingParams = field(default_factory=SamplingParams)
    request_id: str = ""
    priority: int = 0
    #: Optional tenant/API-key label: rides into the cost ledger and the
    #: tenant-labelled ``rlt_serve_request_cost_*`` series (None bills
    #: to the "default" tenant).
    tenant: Optional[str] = None
    #: Relative deadline in seconds from submission; queued requests past
    #: it are expired, in-flight ones are cancelled at the next boundary.
    deadline_s: Optional[float] = None
    submitted_at: float = 0.0
    #: Set when the request enters a slot (chunked prefill may still be
    #: running); the TTFT queue-vs-prefill breakdown pivots on it.
    admitted_at: float = 0.0
    #: Fleet KV plane: the router's warm-peer hint
    #: (``{"peer": idx, "digests": [hex...]}``) — when the local tiers
    #: miss, admission PARKS the request transfer-pending and fetches
    #: the chain from the peer instead of re-prefilling cold. Consumed
    #: (set None) after one attempt; timeout/staleness degrade to the
    #: cold prefill the hint replaced.
    kv_hint: Optional[Dict[str, Any]] = None
    #: Disaggregated prefill: the decode replica this request's
    #: finished-prefill KV pages ship to (prefill-role placement). None
    #: = decode locally (the classic path).
    ship_to: Optional[int] = None

    def expired(self, now: float) -> bool:
        return (
            self.deadline_s is not None
            and now - self.submitted_at > self.deadline_s
        )


@dataclass(frozen=True)
class TokenEvent:
    """One scheduler-step outcome for one request."""

    request_id: str
    token: Optional[int]  # None for lifecycle-only events
    done: bool
    #: "token" | "finished" | "cancelled" | "expired" | "migrated" |
    #: "shipped" ("migrated": evicted by a preemption drain FOR
    #: resubmission on a survivor — terminal on THIS engine, not for the
    #: request; the client follows its route table instead of failing
    #: the stream. "shipped": a prefill-role completion whose KV pages
    #: went to ``ship_to`` — the client resubmits there and the stream
    #: continues warm).
    reason: str = "token"
    #: The decode replica a "shipped" request's pages went to.
    ship_to: Optional[int] = None
    #: The shipped digest chain (hexes): the client's follow-up
    #: resubmission carries them back as a fetch hint, so a lost/raced
    #: ship self-heals (the decode replica fetches from the shipper)
    #: instead of silently re-prefilling cold.
    ship_digests: Optional[List[str]] = None


class Scheduler:
    def __init__(
        self,
        engine: DecodeEngine,
        metrics: Optional[ServeMetrics] = None,
        max_prefills_per_step: int = 1,
        max_prefill_chunks_per_step: int = 1,
        priority_age_s: Optional[float] = None,
        tracer: Optional["RequestTracer"] = None,
        events: Optional["EventLog"] = None,
        journal: Optional["WorkloadJournal"] = None,
        faults: Optional[Any] = None,
        kvfleet: Optional[Any] = None,
        role: str = "mixed",
        kvstore: Optional[Any] = None,
        kvstore_writethrough: bool = False,
    ) -> None:
        self.engine = engine
        #: Fleet KV plane (serve.kvfleet.KVFleetPlane): cross-replica
        #: prefix fetches + disaggregated prefill shipping. None = the
        #: isolated-cache engine (zero cost). ``role`` shapes step():
        #: a "prefill" replica ships every finished prefill's pages to
        #: its request's ``ship_to`` decode replica instead of decoding.
        self.kvfleet = kvfleet
        self.role = str(role)
        #: Persistent KV store (serve.kvstore.FleetKVStore): the tier
        #: of last resort. With ``kvstore_writethrough`` on, every
        #: completed prefill's exported pages write through (so they
        #: survive autoscale-retire and full fleet bounces); session
        #: parking exports land here too. None = no persistent tier.
        self.kvstore = kvstore
        self.kvstore_writethrough = bool(kvstore_writethrough)
        #: Deterministic fault injection (serve.faults.FaultInjector):
        #: step() reports named lifecycle points so a chaos plan can
        #: kill/delay this process at a FIXED logical step instead of a
        #: wall-clock instant. None = off (one attribute check).
        self.faults = faults
        self.metrics = metrics or ServeMetrics(engine.num_slots)
        # Label the phase histogram with this replica's fleet role (the
        # anatomy decomposition reports per-role tails).
        set_role = getattr(self.metrics, "set_role", None)
        if set_role is not None:
            set_role(self.role)
        #: Request tracer (obs.trace): lifecycle events recorded from the
        #: scheduler's vantage point; the engine shares the same tracer
        #: for its chunk/seed events. None = tracing off (zero cost).
        self.tracer = tracer
        if tracer is not None and getattr(engine, "tracer", None) is None:
            engine.tracer = tracer
        #: What step() does, by name (obs.trace.span), into the engine's
        #: totals: its dispatch / harvest spans and the scheduler's are
        #: consecutive pieces of one loop iteration.
        self.spans: SpanTotals = getattr(engine, "spans", None) or SpanTotals()
        # The fleet KV plane records its own phase-boundary marks (ship
        # landings, faults) — share this scheduler's tracer/injector so
        # its spans land in the same ring the anatomy ledger stitches.
        if kvfleet is not None:
            if getattr(kvfleet, "tracer", None) is None:
                kvfleet.tracer = tracer
            if getattr(kvfleet, "faults", None) is None:
                kvfleet.faults = faults
        #: Per-request phase ledger (obs.anatomy): at each terminal,
        #: fold the request's lifecycle timestamps into a compact
        #: {phase: seconds} map emitted to the metrics window (fleet
        #: latency decomposition) and the journal outcome record
        #: (offline autopsy). The per-request cost is a handful of float
        #: subtractions.
        self.phase_ledger = True
        #: Structured event log (obs.events): coarse lifecycle happenings
        #: (admission bursts, cancels, expiries) — one event per
        #: occurrence, never per token; the engine shares it for its
        #: prefix-pool evictions. None = off (zero cost).
        self.events = events
        if events is not None and getattr(engine, "events", None) is None:
            engine.events = events
        #: Workload journal (obs.journal): the deterministic capture of
        #: every externally-sourced input (submits with full sampling
        #: params, cancels) plus per-request emitted-token outcomes —
        #: the replay substrate. None = off (zero cost). Token values
        #: accumulate inline in step()'s existing loops (one list append
        #: per emission, no extra pass) and flush at the ledger close.
        self.journal = journal
        self._jr_tokens: Dict[str, List[int]] = {}
        self._jr_ttft: Dict[str, float] = {}
        self.max_prefills_per_step = max(1, int(max_prefills_per_step))
        #: Chunk-vs-fold interleave budget: prefill chunks advanced per
        #: step (chunked engines only; sits next to the admission budget).
        self.max_prefill_chunks_per_step = max(
            1, int(max_prefill_chunks_per_step)
        )
        #: Aging rate: a queued request's effective priority drops by 1
        #: toward 0 every ``priority_age_s`` seconds, so priority-1 work
        #: cannot starve forever under a sustained priority-0 stream.
        #: None = pure (priority, seq) ordering.
        self.priority_age_s = (
            None if priority_age_s is None else float(priority_age_s)
        )
        self._lock = threading.RLock()
        self._seq = itertools.count()
        #: (priority, seq, Request) min-heap: FIFO within a priority.
        self._pending: List[Any] = []
        self._cancelled: set = set()
        #: Subset of _cancelled evicted BY a preemption drain: their
        #: terminal events read "migrated" so the client keeps the
        #: stream open across the re-route instead of failing it.
        self._migrating: set = set()
        self._slot_req: Dict[int, Request] = {}
        #: Last-seen engine speculative-decoding counters (cumulative);
        #: step() diffs them into per-step metrics deltas.
        self._spec_seen = (0, 0, 0)
        #: Last-seen engine tiered prefix-cache counters (cumulative,
        #: per tier); step() diffs them into per-step metrics deltas —
        #: the tier-labelled rlt_serve_prefix_* series.
        self._prefix_seen: Dict[str, Dict[str, int]] = {}
        #: Last-seen engine KV page-allocator counters (paged engines);
        #: step() diffs them into per-step metrics deltas — the
        #: rlt_serve_kv_page_*_total series and the kv_pages gauges.
        self._kv_seen: Dict[str, int] = {}
        #: Out-of-pages backpressure latch: set while the queue head is
        #: parked waiting for pages, so the warn event fires once per
        #: park episode, not once per step.
        self._kv_parked = False
        #: Requests popped for admission but not yet registered in
        #: _slot_req (engine.admit runs OUTSIDE the lock); cancel() must
        #: still find them so a cancel racing an admission is honored at
        #: the next boundary instead of reported unknown.
        self._admitting: set = set()
        #: Cost ledger: per-request accounting accumulated from submit
        #: to terminal (queue_s, chunks, folds, emitted tokens, an
        #: estimated device-seconds share) and emitted as ONE record at
        #: finish/cancel/expire via metrics.record_cost + a typed event.
        self._acct: Dict[str, Dict[str, Any]] = {}
        #: Whose seconds the loop spends (``SpanTotals.riders``): open
        #: ledger records without a first token, and with one. Each
        #: moves where its record does — opened, first token, closed —
        #: so the spans' request-seconds add up to the ledger's phases.
        #: ``_since_*`` is the sum of the instants at which the open
        #: requests began to wait / to decode: what they have accrued
        #: by ``t`` is ``count * t - sum`` (:meth:`riders_open`).
        self._n_waiting = 0
        self._n_decoding = 0
        self._since_waiting = 0.0
        self._since_decoding = 0.0
        #: Preemption drain: a pending ``request_drain`` budget (s) the
        #: next step() consumes, and the plan it produced — engine work
        #: (prefix-block export) must run on the loop thread, so the RPC
        #: surface arms the drain and waits on the condition instead of
        #: touching the engine itself.
        self._drain_req: Optional[float] = None
        self._drain_result: Optional[Dict[str, Any]] = None
        self._drain_cv = threading.Condition()
        #: Prefix-block payloads handed off by a dying peer, queued here
        #: (RPC thread) and imported into the engine pool at the top of
        #: the next step() (loop thread) — engine state never mutates
        #: off the driving thread.
        self._pending_imports: List[Any] = []
        #: Transfer-pending PARK state: requests popped from the queue
        #: whose warm pages are in flight from a peer —
        #: request_id -> (priority, seq, Request). They re-queue under
        #: their ORIGINAL (priority, seq) when the fetch lands (warm
        #: admit) or fails (cold prefill), so parking never reorders
        #: the queue around them.
        self._transfer_pending: Dict[str, Any] = {}
        #: Session parking: a pending ``request_park`` (the idle
        #: conversation's full token stream) the next step() consumes —
        #: engine exports/evictions must run on the loop thread, so the
        #: RPC surface arms the park and waits on the condition, exactly
        #: like the preemption drain above.
        self._park_req: Optional[Any] = None
        self._park_result: Optional[Dict[str, Any]] = None
        self._park_cv = threading.Condition()

    # -- cost ledger ------------------------------------------------------
    def _riders(
        self, waiting: int = 0, since_w: float = 0.0,
        decoding: int = 0, since_d: float = 0.0,
    ) -> None:
        """A ledger record was opened, got its first token or was
        closed: move the counts, and tell the spans. A submit comes on
        the RPC thread, hence the lock."""
        with self._lock:
            self._n_waiting += waiting
            self._since_waiting += waiting * since_w
            self._n_decoding += decoding
            self._since_decoding += decoding * since_d
            self.spans.riders(self._n_waiting, self._n_decoding)

    def riders_open(self) -> Dict[str, float]:
        """Request-seconds the requests still open have accrued: those
        without a first token since their submit, the others since their
        first token. With the ledger's phases of the requests already
        closed it is what ``riders_s`` adds up to (any thread)."""
        with self._lock:
            now = time.monotonic()
            return {
                "waiting": self._n_waiting * now - self._since_waiting,
                "decoding": self._n_decoding * now - self._since_decoding,
            }

    def _acct_first_token(self, acct: Dict[str, Any], ttft: float) -> None:
        """The record's request has its first token, ``ttft`` seconds
        after its submit: it waits no longer, it decodes."""
        if "_ttft_s" in acct:
            return
        acct["_ttft_s"] = ttft
        t_sub = acct["submitted_at"]
        self._riders(-1, t_sub, +1, t_sub + ttft)

    def _acct_open(self, req: Request) -> None:
        self._riders(+1, req.submitted_at)
        self._acct[req.request_id] = {
            "request_id": req.request_id,
            "tenant": req.tenant,
            "prompt_tokens": len(req.prompt),
            "submitted_at": req.submitted_at,
            "queue_s": 0.0,
            "prefill_chunks": 0,
            "prefix_hit_tokens": 0,
            "decode_folds": 0,
            "spec_verifies": 0.0,
            "spec_accepted_tokens": 0.0,
            "emitted_tokens": 0,
            "device_s": 0.0,
        }

    def _acct_close(self, rid: str, outcome: str) -> None:
        """Finalize one request's ledger record and emit it (metrics
        window + Prometheus series + a typed event). Safe to call for
        unknown ids (already flushed / submitted before a restart)."""
        rec = self._acct.pop(rid, None)
        if rec is None:
            return
        rec["outcome"] = outcome
        t_sub = rec.pop("submitted_at")
        if "_ttft_s" in rec:
            self._riders(decoding=-1, since_d=t_sub + rec["_ttft_s"])
        else:
            self._riders(-1, t_sub)
        rec["total_s"] = round(time.monotonic() - t_sub, 6)
        rec["queue_s"] = round(rec["queue_s"], 6)
        rec["device_s"] = round(rec["device_s"], 6)
        rec["spec_verifies"] = round(rec["spec_verifies"], 3)
        rec["spec_accepted_tokens"] = round(
            rec["spec_accepted_tokens"], 3
        )
        # Compact phase ledger: the scheduler-local latency decomposition
        # (the cross-process phases — client_wait, ship transit,
        # stream_gap — only the anatomy stitcher can see). Underscore
        # stashes pop out of the record whether or not the ledger is on.
        fetch_s = rec.pop("_kv_fetch_s", 0.0)
        land_t = rec.pop("_kv_land_t", None)
        kv_src = rec.pop("_kv_src", None)
        rec.pop("_kv_park_t", None)
        admit_t = rec.pop("_admit_t", None)
        ttft = rec.pop("_ttft_s", None)
        phases: Optional[Dict[str, float]] = None
        if self.phase_ledger:
            phases = {}
            park_s = (
                max(0.0, admit_t - land_t)
                if admit_t is not None and land_t is not None
                else 0.0
            )
            phases["queue"] = max(
                0.0, rec["queue_s"] - fetch_s - park_s
            )
            if fetch_s > 0.0:
                phases["kv_fetch"] = fetch_s
                if kv_src:
                    phases["kv_fetch_source"] = kv_src
            if park_s > 0.0:
                phases["transfer_park"] = park_s
            if ttft is not None:
                phases["prefill"] = max(0.0, ttft - rec["queue_s"])
                tail = max(0.0, rec["total_s"] - ttft)
                phases["ship" if outcome == "shipped" else "decode"] = tail
            phases = {
                k: (round(v, 6) if isinstance(v, float) else v)
                for k, v in phases.items()
            }
            self.metrics.record_phases(
                phases, tenant=rec["tenant"], outcome=outcome
            )
        if outcome == "finished" and rec["emitted_tokens"] > 1 and ttft is not None:
            # Time per output token as the client reckons it: the tokens
            # after the first over the time after the first.
            self.metrics.record_tpot(
                (rec["total_s"] - ttft) / (rec["emitted_tokens"] - 1)
            )
        self.metrics.record_cost(rec)
        self._event(
            "request_cost",
            request_id=rid,
            tenant=rec["tenant"] or "default",
            outcome=outcome,
            emitted_tokens=rec["emitted_tokens"],
            device_s=rec["device_s"],
            queue_s=rec["queue_s"],
        )
        if self.journal is not None:
            # The outcome entry rides the ledger close: the emitted
            # token values (accumulated inline as they were harvested)
            # + this cost record — the recorded truth a replay asserts
            # bit-exactness against.
            self.journal.record_outcome(
                rid, outcome, cost=rec,
                tokens=self._jr_tokens.pop(rid, None),
                ttft_s=self._jr_ttft.pop(rid, None),
                phases=phases,
            )

    def _trace(
        self, rid: str, span: str, t: Optional[float] = None, **attrs: Any
    ) -> None:
        if self.tracer is not None:
            self.tracer.event(rid, span, t=t, attrs=attrs or None)

    def _event(self, name: str, level: str = "info", **kv: Any) -> None:
        if self.events is not None:
            self.events.record("scheduler", name, level=level, **kv)

    def _fault(self, point: str) -> None:
        if self.faults is not None:
            self.faults.hit(point)

    # -- intake (thread-safe) --------------------------------------------
    def submit(
        self,
        prompt: Sequence[int],
        sampling: Optional[SamplingParams] = None,
        *,
        request_id: Optional[str] = None,
        priority: int = 0,
        deadline_s: Optional[float] = None,
        tenant: Optional[str] = None,
        kv_hint: Optional[Dict[str, Any]] = None,
        ship_to: Optional[int] = None,
    ) -> str:
        """Queue a request; returns its id. Rejects (ValueError) requests
        that can never fit the engine, instead of queueing them to fail.

        ``kv_hint``/``ship_to`` are fleet-KV placement hints (see
        :class:`Request`) — routing metadata, not request identity, so
        the journal does NOT record them: a failover resubmission or a
        replay decodes locally, which is always correct."""
        sampling = sampling or SamplingParams()
        prompt = [int(t) for t in prompt]
        if not prompt or sampling.max_new_tokens < 1:
            raise ValueError("need a non-empty prompt and max_new_tokens >= 1")
        # Raises when the prompt can never be admitted (over every bucket,
        # or — chunked — leaving no room for a generated token).
        self.engine.check_prompt_len(len(prompt))
        if len(prompt) + sampling.max_new_tokens > self.engine.max_seq:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens "
                f"({sampling.max_new_tokens}) exceeds engine max_seq "
                f"{self.engine.max_seq}"
            )
        req = Request(
            prompt=prompt,
            sampling=sampling,
            request_id=request_id or uuid.uuid4().hex[:12],
            priority=int(priority),
            deadline_s=deadline_s,
            submitted_at=time.monotonic(),
            tenant=tenant,
            kv_hint=dict(kv_hint) if kv_hint else None,
            ship_to=None if ship_to is None else int(ship_to),
        )
        with self._lock:
            heapq.heappush(
                self._pending, (req.priority, next(self._seq), req)
            )
            depth = self._organic_depth_locked()
            self.metrics.record_submit(depth)
            self._acct_open(req)
        if self.journal is not None:
            s = req.sampling
            self.journal.record_submit(
                request_id=req.request_id,
                prompt=req.prompt,
                sampling={
                    "max_new_tokens": s.max_new_tokens,
                    "temperature": s.temperature,
                    "top_k": s.top_k,
                    "top_p": s.top_p,
                    "seed": s.seed,
                    "eos_token": s.eos_token,
                },
                priority=req.priority,
                deadline_s=req.deadline_s,
                tenant=req.tenant,
                t_mono=req.submitted_at,
            )
        if self.tracer is not None:
            self.tracer.event(
                req.request_id, _trace.SPAN_SUBMIT, t=req.submitted_at,
                attrs={"prompt_tokens": len(prompt), "priority": req.priority},
            )
            self.tracer.event(
                req.request_id, _trace.SPAN_QUEUED,
                attrs={"queue_depth": depth},
            )
        return req.request_id

    def cancel(self, request_id: str) -> bool:
        """Mark a request cancelled; queued ones are dropped and in-flight
        ones evicted at the next step boundary. Returns whether the id was
        known (queued or in flight)."""
        with self._lock:
            known = (
                request_id in self._admitting
                or request_id in self._transfer_pending
                or any(
                    r.request_id == request_id for _, _, r in self._pending
                )
                or any(
                    r.request_id == request_id
                    for r in self._slot_req.values()
                )
            )
            if known:
                self._cancelled.add(request_id)
        if self.journal is not None:
            self.journal.record_cancel(request_id, known)
        return known

    def queue_depth(self) -> int:
        """ORGANIC queue depth: pending requests excluding the reserved
        canary tenant. This is the number the metrics gauge — and
        through it the router's views and the autoscaler's pressure
        signal — sees, so a canary-only fleet reports zero load."""
        with self._lock:
            return self._organic_depth_locked()

    def _organic_depth_locked(self) -> int:
        """Under self._lock: len(self._pending) minus canary probes."""
        return sum(
            1 for _, _, r in self._pending if r.tenant != CANARY_TENANT
        )

    def has_work(self) -> bool:
        with self._lock:
            if (
                bool(self._pending)
                or self.engine.num_active > 0
                or self._drain_req is not None
                or self._park_req is not None
                or bool(self._pending_imports)
                or bool(self._transfer_pending)
            ):
                return True
        # Fleet KV inbox (peer fetches/ships): outside the lock — the
        # emptiness probe may cross a process boundary.
        return self.kvfleet is not None and self.kvfleet.pending()

    # -- preemption drain (thread-safe arm/wait; work runs in step()) -----
    def request_drain(self, budget_s: float) -> None:
        """Arm a graceful drain: the next step() classifies in-flight
        work into finish-in-grace vs migrate (cancelling + exporting the
        migrate set) and publishes the plan for :meth:`drain_result`."""
        with self._lock:
            self._drain_req = float(budget_s)

    def drain_result(
        self, timeout: Optional[float] = 10.0
    ) -> Optional[Dict[str, Any]]:
        """Block until the armed drain's plan is ready (None on
        timeout); consumes the plan."""
        with self._drain_cv:
            if self._drain_result is None:
                self._drain_cv.wait(timeout)
            plan, self._drain_result = self._drain_result, None
            return plan

    # -- session parking (thread-safe arm/wait; work runs in step()) ------
    def request_park(
        self, tokens: Sequence[int], request_id: Optional[str] = None
    ) -> None:
        """Arm a session park: the next step() exports the idle
        conversation's cached chain (loop thread — compiled pool
        reads), writes it through to the persistent store, and frees
        the local pages ONLY if every block landed (a partial write
        keeps the warm copies; pages are lost loudly, never silently).
        The restored turn hits the chain back through the ordinary
        store-fetch path, bit-exactly."""
        with self._lock:
            self._park_req = ([int(t) for t in tokens], request_id)

    def park_result(
        self, timeout: Optional[float] = 10.0
    ) -> Optional[Dict[str, Any]]:
        """Block until the armed park's record is ready (None on
        timeout); consumes the record."""
        with self._park_cv:
            if self._park_result is None:
                self._park_cv.wait(timeout)
            out, self._park_result = self._park_result, None
            return out

    def _apply_park(self) -> None:
        """Consume a pending park request (inside step(), loop
        thread): export -> store write-through -> local eviction."""
        with self._lock:
            req, self._park_req = self._park_req, None
        if req is None:
            return
        tokens, rid = req
        blocks: List[Any] = []
        stored = freed = 0
        if getattr(self.engine, "prefix_blocks", 0):
            blocks = self.engine.export_prefix_blocks(tokens)
        if blocks and self.kvstore is not None:
            stored = self.kvstore.put_blocks(blocks)
            if stored == len(blocks):
                evict = getattr(self.engine, "evict_prefix_chain", None)
                if evict is not None:
                    freed = evict([b[0] for b in blocks])
        result = {
            "digests": [b[0] for b in blocks],
            "blocks": len(blocks),
            "stored": stored,
            "freed": freed,
        }
        if rid is not None:
            self._trace(
                rid, _trace.SPAN_KV_PARK,
                blocks=len(blocks), stored=stored, freed=freed,
            )
        self._event(
            "kv_park",
            level="info" if stored == len(blocks) else "warn",
            request_id=rid, blocks=len(blocks), stored=stored,
            freed=freed,
        )
        with self._park_cv:
            self._park_result = result
            self._park_cv.notify_all()

    def enqueue_prefix_import(self, blocks: Any) -> int:
        """Queue a dying peer's exported prefix blocks for import at the
        top of the next step() (engine mutations stay on the loop
        thread). Returns the number of blocks queued."""
        with self._lock:
            self._pending_imports.append(blocks)
        return len(blocks)

    def _service_kvfleet(self) -> None:
        """One pump of the fleet KV plane (loop thread): answer peer
        fetches, apply inbound imports, and settle this scheduler's
        parked transfer-pending requests."""
        plane = self.kvfleet
        export_fn = getattr(self.engine, "export_blocks_by_digest", None)
        svc = plane.service(
            export_fn=export_fn if export_fn is not None else (
                lambda digests: []
            ),
            import_fn=self.engine.import_prefix_blocks,
            layer_import_fn=getattr(
                self.engine, "import_prefix_block_layer", None
            ),
            abort_fn=getattr(self.engine, "abort_layer_imports", None),
        )
        resumed: List[Any] = []
        store_rids = set(svc.get("store_fetched") or ())
        with self._lock:
            for rid, _n in svc["fetched"]:
                entry = self._transfer_pending.pop(rid, None)
                if entry is not None:
                    # The blocks are already in the pool; the request
                    # re-queues under its original (priority, seq) and
                    # its admission walk now hits warm.
                    heapq.heappush(self._pending, entry)
                    resumed.append((rid, "warm"))
        for rid in store_rids:
            self._trace(rid, _trace.SPAN_KV_RESTORE)
        with self._lock:
            for rid, reason in svc["failed"]:
                entry = self._transfer_pending.pop(rid, None)
                if entry is not None:
                    heapq.heappush(self._pending, entry)
                    resumed.append((rid, reason))
        t_land = time.monotonic()
        for rid, how in resumed:
            # Phase-boundary mark: the parked transfer settled (warm or
            # failed) — closes the ledger's kv_fetch phase; the land →
            # re-admit gap becomes transfer_park.
            acct = self._acct.get(rid)
            src = "store" if rid in store_rids else (
                (acct or {}).get("_kv_src") or "peer"
            )
            if acct is not None and "_kv_park_t" in acct:
                acct["_kv_fetch_s"] = t_land - acct["_kv_park_t"]
                acct["_kv_land_t"] = t_land
            self._trace(
                rid, _trace.SPAN_KV_LAND, t=t_land,
                source=src, ok=how == "warm",
                **({} if how == "warm" else {"reason": how}),
            )
            self._event(
                "kv_transfer_resume",
                level="info" if how == "warm" else "warn",
                request_id=rid, outcome=how,
            )

    def _apply_drain(self, events: List[TokenEvent]) -> None:
        """Consume a pending drain request (inside step(), loop thread).

        Policy: a resident request whose estimated completion fits in
        half the grace window (the other half is the respawn/failover
        margin) runs to completion; everything else — the rest of the
        residents and the whole queue — is cancelled here and listed as
        the MIGRATE set, each with its prompt's cached prefix blocks
        serialized for the survivor (the cross-replica KV handoff). The
        estimate is conservative: with no recent decode-rate sample,
        everything migrates — better a warm replay on a survivor than a
        stream the deadline truncates.
        """
        with self._lock:
            budget = self._drain_req
            if budget is None:
                return
            self._drain_req = None
            rate = float(
                self.metrics.snapshot().get("decode_tokens_per_sec") or 0.0
            )
            resident = list(self._slot_req.values())
            n_res = max(1, len(resident))
            finish: List[str] = []
            migrate: List[Any] = []
            for req in resident:
                acct = self._acct.get(req.request_id) or {}
                left = max(
                    0,
                    req.sampling.max_new_tokens
                    - int(acct.get("emitted_tokens", 0)),
                )
                est = (left * n_res / rate) if rate > 0 else None
                if est is not None and est <= 0.5 * budget:
                    finish.append(req.request_id)
                else:
                    migrate.append(req)
                    # The boundary eviction scan below this call picks
                    # it up in the SAME step; _migrating makes its
                    # terminal events read "migrated" (the client keeps
                    # the stream open across the re-route).
                    self._cancelled.add(req.request_id)
                    self._migrating.add(req.request_id)
            queued = [r for _, _, r in self._pending]
            # Transfer-pending parks are queued work too: their fetches
            # die with this replica, so they migrate like the queue
            # (any late fetch response is discarded harmlessly).
            queued += [r for _, _, r in self._transfer_pending.values()]
            self._pending = []
            self._transfer_pending = {}
            for req in queued:
                self._cancelled.discard(req.request_id)
                migrate.append(req)
                self.metrics.record_cancel(queue_depth=0)
                self._trace(req.request_id, _trace.SPAN_CANCEL)
                self._acct_close(req.request_id, "migrated")
                events.append(
                    TokenEvent(req.request_id, None, True, "migrated")
                )
        if self.journal is not None:
            # A drain-induced cancel must look like any other cancel to
            # a replay of this journal (the client-side journal, not
            # this one, is what resubmits the migrated request).
            for req in migrate:
                self.journal.record_cancel(req.request_id, True)
        # Engine work outside the lock: serialize each migrating
        # request's cached prefix so the survivor's admission walk hits
        # warm instead of re-prefilling cold.
        plan = {
            "budget_s": budget,
            "finish": finish,
            "migrate": [
                {
                    "request_id": req.request_id,
                    "blocks": self.engine.export_prefix_blocks(req.prompt)
                    if getattr(self.engine, "prefix_blocks", 0)
                    else [],
                }
                for req in migrate
            ],
        }
        self._event(
            "drain_plan", level="warn",
            budget_s=round(budget, 3), finish=len(finish),
            migrate=len(migrate),
            kv_blocks=sum(len(m["blocks"]) for m in plan["migrate"]),
        )
        with self._drain_cv:
            self._drain_result = plan
            self._drain_cv.notify_all()

    # -- the loop body (single driver thread) -----------------------------
    def step(self) -> List[TokenEvent]:
        """One iteration: evict cancelled/expired, admit (bounded),
        advance prefill chunks (bounded), run one engine fold. Queue
        decisions happen under the lock; every engine call runs OUTSIDE
        it, so submit()/cancel() never wait on device compute."""
        events: List[TokenEvent] = []
        t0 = time.monotonic()
        spans = self.spans
        with span(spans, "serve.sched.boundary"):
            # Peer KV handoff + preemption drain ride the loop thread:
            # apply queued block imports first, then consume any armed drain
            # request so its cancellations land in THIS step's boundary
            # scan (engine state never mutates off the driving thread).
            with self._lock:
                imports, self._pending_imports = self._pending_imports, []
            for blocks in imports:
                self.engine.import_prefix_blocks(blocks)
            if self.kvfleet is not None:
                # Fleet KV plane: serve peer fetches (compiled pool reads —
                # this thread), import inbound ships/fetch responses BEFORE
                # the admission scan below (so a shipped request admits
                # warm), and re-queue parked requests whose transfer landed
                # (warm) or failed (cold prefill — timeout/staleness never
                # lose the request, they only lose the shortcut).
                self._service_kvfleet()
            if self._drain_req is not None:
                self._apply_drain(events)
            if self._park_req is not None:
                self._apply_park()
            to_evict: List[Any] = []
            admits: List[Request] = []
            #: (priority, seq, Request, peer, digests): candidates popped
            #: for a cross-replica KV fetch instead of admission — the
            #: fetch RPC runs outside the lock; success parks them
            #: transfer-pending, refusal re-queues them for cold prefill.
            to_fetch: List[Any] = []
            #: (rid, outcome) terminals from ENGINE work this step; their
            #: ledger records flush after this step's device-seconds are
            #: attributed, so a request's final fold is in its bill.
            closed: List[Any] = []
            with self._lock:
                resident_rids = [
                    r.request_id for r in self._slot_req.values()
                ]
                # 0) Priority aging: re-score the queue so long-waiting
                # requests drift toward priority 0 (FIFO seq breaks ties, so
                # an aged request outranks younger same-priority arrivals).
                if self.priority_age_s is not None and self._pending:
                    self._pending = [
                        (
                            max(
                                0,
                                r.priority
                                - int(
                                    (t0 - r.submitted_at) / self.priority_age_s
                                ),
                            ),
                            s,
                            r,
                        )
                        for _, s, r in self._pending
                    ]
                    heapq.heapify(self._pending)
                # 1) Collect boundary evictions of in-flight cancels/expiries
                # (mid-prefill requests included — release drops their state
                # machine and unpins their prefix blocks).
                for slot, req in list(self._slot_req.items()):
                    rid = req.request_id
                    cancelled = rid in self._cancelled
                    if cancelled or req.expired(t0):
                        del self._slot_req[slot]
                        self._cancelled.discard(rid)
                        if rid in self._migrating:
                            self._migrating.discard(rid)
                            kind = "migrated"
                        else:
                            kind = "cancelled" if cancelled else "expired"
                        to_evict.append((slot, req, kind))
                # 2) Pop admission candidates: bounded prefills per step,
                # sized to the slots that are (or are about to be) free.
                # Paged engines add a PAGE budget: a candidate is admitted
                # only while the allocatable pages cover its whole life
                # (prompt + decode reserve — engine.pages_for); otherwise
                # the queue head PARKS in place (no pop, priority order
                # kept) until residents finish and free pages — out of
                # pages backpressures, it never deadlocks and never lets
                # an admission fail inside the engine.
                budget = min(
                    self.max_prefills_per_step,
                    len(self.engine.free_slots()) + len(to_evict),
                )
                paged = getattr(self.engine, "paged", False)
                pages_left = self.engine.pages_available() if paged else 0
                parked = False
                while len(admits) < budget and self._pending:
                    prio, seqno, req = self._pending[0]
                    if req.request_id in self._cancelled:
                        heapq.heappop(self._pending)
                        self._cancelled.discard(req.request_id)
                        self.metrics.record_cancel(
                            queue_depth=self._organic_depth_locked()
                        )
                        self._trace(req.request_id, _trace.SPAN_CANCEL)
                        self._event("cancel", request_id=req.request_id,
                                    where="queued")
                        self._acct_close(req.request_id, "cancelled")
                        events.append(
                            TokenEvent(req.request_id, None, True, "cancelled")
                        )
                        continue
                    if req.expired(t0):
                        heapq.heappop(self._pending)
                        self.metrics.record_expire(
                            queue_depth=self._organic_depth_locked()
                        )
                        self._trace(req.request_id, _trace.SPAN_EXPIRE)
                        self._event("expire", level="warn",
                                    request_id=req.request_id, where="queued")
                        self._acct_close(req.request_id, "expired")
                        events.append(
                            TokenEvent(req.request_id, None, True, "expired")
                        )
                        continue
                    if self.kvfleet is not None and req.kv_hint is not None:
                        # Cross-replica prefix sharing: the router said a
                        # peer holds this prompt's chain — or, with
                        # ``store: True``, that no live replica does but
                        # the persistent store does. One attempt per
                        # request (the hint is consumed here); only worth a
                        # fetch when the LOCAL tiers hold strictly less
                        # than the hint promises — the probe is a pure
                        # host-side digest walk, safe under the lock.
                        hint, req.kv_hint = req.kv_hint, None
                        digests = list(hint.get("digests") or [])
                        peer = hint.get("peer")
                        from_store = bool(hint.get("store"))
                        probe = getattr(
                            self.engine, "cached_prefix_blocks", None
                        )
                        if (
                            digests
                            and (peer is not None or from_store)
                            and probe is not None
                            and getattr(self.engine, "prefix_blocks", 0)
                            and probe(req.prompt) < len(digests)
                        ):
                            heapq.heappop(self._pending)
                            to_fetch.append((
                                prio, seqno, req,
                                None if from_store else int(peer),
                                digests,
                            ))
                            continue
                    if paged:
                        need = self.engine.pages_for(
                            len(req.prompt), req.sampling.max_new_tokens
                        )
                        if need > pages_left:
                            parked = True
                            break
                        pages_left -= need
                    heapq.heappop(self._pending)
                    admits.append(req)
                    self._admitting.add(req.request_id)
                if parked and not self._kv_parked:
                    self._event(
                        "kv_pages_backpressure", level="warn",
                        queue_depth=len(self._pending),
                        pages_available=pages_left,
                    )
                self._kv_parked = parked
            # -- engine work, lock NOT held --------------------------------
            for prio, seqno, req, peer, digests in to_fetch:
                # The fetch RPC (a queue put, possibly cross-process) runs
                # here; a refused fetch (budget, unknown peer, bandwidth
                # cap) re-queues for cold prefill NEXT step — bounded
                # in-flight bytes never turn into a queue. ``peer is None``
                # means the hint pointed at the persistent store, not a
                # live replica; same park→import→admit-warm path, different
                # resolver.
                ok = (
                    self.kvfleet.request_store_fetch(req.request_id, digests)
                    if peer is None
                    else self.kvfleet.request_fetch(req.request_id, peer, digests)
                )
                if ok:
                    with self._lock:
                        self._transfer_pending[req.request_id] = (
                            prio, seqno, req,
                        )
                    acct = self._acct.get(req.request_id)
                    if acct is not None:
                        acct["_kv_park_t"] = time.monotonic()
                        acct["_kv_src"] = "store" if peer is None else "peer"
                    self._trace(
                        req.request_id,
                        _trace.SPAN_KVSTORE_FETCH if peer is None
                        else _trace.SPAN_KV_FETCH,
                        peer=peer, blocks=len(digests),
                    )
                    self._event(
                        "kv_transfer_park", request_id=req.request_id,
                        peer=peer, blocks=len(digests),
                        store=peer is None,
                    )
                else:
                    with self._lock:
                        heapq.heappush(self._pending, (prio, seqno, req))
        newly: Dict[int, Request] = {}
        finished_rids: List[str] = []
        finished_slots: List[int] = []
        # Opened only on steps that admit or evict, so its count is the
        # count of admission bursts.
        with span(
            spans, "serve.sched.admit", n=len(admits),
            longest_prompt=max((len(r.prompt) for r in admits), default=0),
            evicted=len(to_evict),
        ) if admits or to_evict else contextlib.nullcontext():
            for slot, req, kind in to_evict:
                self.engine.release(slot)
                (self.metrics.record_expire if kind == "expired"
                 else self.metrics.record_cancel)(
                    queue_depth=self.queue_depth()
                )
                self._trace(
                    req.request_id,
                    _trace.SPAN_EXPIRE if kind == "expired"
                    else _trace.SPAN_CANCEL,
                    slot=slot,
                )
                self._event(
                    "expire" if kind == "expired" else "cancel",
                    level="warn" if kind == "expired" else "info",
                    request_id=req.request_id, where="slot", slot=slot,
                    migrated=kind == "migrated",
                )
                closed.append((req.request_id, kind))
                events.append(TokenEvent(req.request_id, None, True, kind))
            if admits:
                # One burst: every admission chain is dispatched before the
                # first token sync (engine.admit_many), so admission i's host
                # round trip overlaps admission i+1's prefill. Chunked
                # engines return first_tok=None here — the first token
                # arrives from prefill_step below once the final chunk runs.
                t_admit = time.monotonic()
                results = self.engine.admit_many(
                    [
                        dict(
                            prompt=req.prompt,
                            request_id=req.request_id,
                            max_new_tokens=req.sampling.max_new_tokens,
                            temperature=req.sampling.temperature,
                            top_k=req.sampling.top_k,
                            top_p=req.sampling.top_p,
                            seed=req.sampling.seed,
                            eos_token=req.sampling.eos_token,
                        )
                        for req in admits
                    ]
                )
                # One event per BURST, not per admission — the hot loop's
                # event budget.
                self._event(
                    "admit_burst", n=len(admits),
                    queue_depth=self.queue_depth(),
                )
                for req, (slot, first_tok, done) in zip(admits, results):
                    req.admitted_at = t_admit
                    self.metrics.record_admit(
                        t_admit - req.submitted_at, self.queue_depth()
                    )
                    acct = self._acct.get(req.request_id)
                    if acct is not None:
                        acct["queue_s"] = t_admit - req.submitted_at
                        acct["_admit_t"] = t_admit
                    # Record-time timestamp (not t_admit): the engine's own
                    # admission-block events (prefix_seed) land between
                    # queued and here, and a trace's timestamps must be
                    # monotonic in record order. queue_s keeps the exact
                    # admission clock.
                    self._trace(
                        req.request_id, _trace.SPAN_ADMITTED,
                        slot=slot,
                        queue_s=round(t_admit - req.submitted_at, 6),
                    )
                    if first_tok is None:
                        newly[slot] = req  # chunked prefill in progress
                        continue
                    now = time.monotonic()
                    self.metrics.record_first_token(
                        now - req.submitted_at, now - t_admit, 1, 0,
                        len(req.prompt),
                    )
                    self._trace(
                        req.request_id, _trace.SPAN_FIRST_TOKEN, t=now,
                        ttft_s=round(now - req.submitted_at, 6),
                    )
                    if acct is not None:
                        acct["emitted_tokens"] += 1
                        self._acct_first_token(acct, now - req.submitted_at)
                    if self.journal is not None:
                        self._jr_tokens[req.request_id] = [int(first_tok)]
                        self._jr_ttft[req.request_id] = (
                            now - req.submitted_at
                        )
                    events.append(
                        TokenEvent(
                            req.request_id, first_tok, done,
                            "finished" if done else "token",
                        )
                    )
                    if done:
                        self.metrics.record_finish(
                            queue_depth=self.queue_depth()
                        )
                        self._trace(req.request_id, _trace.SPAN_FINISH)
                        finished_rids.append(req.request_id)
                        closed.append((req.request_id, "finished"))
                    else:
                        newly[slot] = req
            if admits:
                # Fault point: requests hold slots, chunked ones have no
                # first token yet — dying here strands admitted-not-started
                # work (the failover set's hardest case).
                self._fault("post_admit")
        # 3) Advance chunked prefills. Two shapes: the classic
        # chunk-vs-fold interleave (separate prefill_step dispatches
        # competing with the fold for device time), or — with
        # piggyback_chunks on — NO separate dispatch at all: chunk rows
        # ride inside the decode fold below and their completions drain
        # from pop_chunk_events after it. (Snapshot the in-progress
        # count first: the fault hook below must fire on every step
        # that ADVANCED a chunk, not only the one that completed a
        # prefill — "mid-prefill" is the point.)
        with span(spans, "serve.sched.prefill_chunks"):
            piggyback = getattr(self.engine, "piggyback_chunks", 0) > 0
            prefilling = getattr(self.engine, "num_prefilling", 0)
            chunk_events = (
                []
                if piggyback
                else self.engine.prefill_step(self.max_prefill_chunks_per_step)
            )
            prefilled = self._finish_prefills(
                chunk_events, newly, events, finished_rids, finished_slots,
                closed,
            )
            if not piggyback and (chunk_events or prefilling):
                # Fault point: a multi-chunk prompt is part-way through its
                # prefill (device KV holds a partial range nobody can read
                # back — the request MUST be replayed from its submit).
                self._fault("mid_prefill_chunk")
        # 4) One engine fold for everything resident (up to decode_fold
        # tokens per slot fan out of a single dispatch+harvest).
        active = self.engine.num_active
        emitted = 0
        fold_results = self.engine.step()
        with span(spans, "serve.sched.account", tokens=len(fold_results)):
            if piggyback:
                # Piggybacked chunk rows rode INSIDE that fold dispatch;
                # their completions drain here and flow through the same
                # finish path (first-token metrics, writethrough, ship) —
                # one dispatch did all the work, the host accounting is
                # identical either way.
                pb_events = self.engine.pop_chunk_events()
                if pb_events:
                    chunk_events = list(chunk_events) + pb_events
                    prefilled += self._finish_prefills(
                        pb_events, newly, events, finished_rids,
                        finished_slots, closed, piggyback=True,
                    )
                if pb_events or prefilling:
                    # Same fault point as the separate-dispatch path, just
                    # after the fused fold that advanced the chunks.
                    self._fault("mid_prefill_chunk")
            # Tokens per request this fold: the shared granularity of the
            # decode-side trace events, the spec attribution, and the cost
            # ledger (one dict pass per fold, never per token).
            fold_tokens: Dict[str, int] = {}
            for _, rid, _, _ in fold_results:
                fold_tokens[rid] = fold_tokens.get(rid, 0) + 1
            if getattr(self.engine, "spec", "off") != "off":
                # Accept accounting: the engine's cumulative counters diffed
                # into this step's delta (zombie tokens already excluded at
                # harvest). One metrics record per step, never per token.
                v = self.engine.spec_verifies
                d = self.engine.spec_drafted_tokens
                a = self.engine.spec_accepted_tokens
                dv = v - self._spec_seen[0]
                if dv:
                    da = a - self._spec_seen[2]
                    self.metrics.record_spec(dv, d - self._spec_seen[1], da)
                    # Ledger attribution: the verify forwards are batched
                    # over slots, so per-request shares are estimates —
                    # accepted tokens proportional to tokens emitted this
                    # fold, verifies split evenly among the riders.
                    total = sum(fold_tokens.values())
                    for rid, n in fold_tokens.items():
                        acct = self._acct.get(rid)
                        if acct is not None:
                            acct["spec_verifies"] += dv / len(fold_tokens)
                            if total:
                                acct["spec_accepted_tokens"] += da * n / total
                    if self.tracer is not None:
                        for rid, n in fold_tokens.items():
                            self.tracer.event(
                                rid, _trace.SPAN_SPEC_VERIFY,
                                attrs={
                                    "tokens": n,
                                    "drafted": d - self._spec_seen[1],
                                    "accepted": da,
                                },
                            )
                self._spec_seen = (v, d, a)
            # Tiered prefix cache: diff the engine's cumulative per-tier
            # counters into one metrics record per step that saw tier
            # traffic (admissions walk the tiers; steady decode never does).
            tier_fn = getattr(self.engine, "prefix_tier_counters", None)
            if tier_fn is not None and getattr(self.engine, "prefix_blocks", 0):
                tiers = tier_fn()
                if tiers != self._prefix_seen:
                    seen = self._prefix_seen
                    self.metrics.record_prefix_tiers(
                        {
                            t: {
                                k: n - seen.get(t, {}).get(k, 0)
                                for k, n in kv.items()
                            }
                            for t, kv in tiers.items()
                        },
                        self.engine.prefix_tier_bytes(),
                    )
                    self._prefix_seen = tiers
            # Paged KV: diff the engine's cumulative page-allocator counters
            # into one metrics record per step that saw page traffic, and
            # refresh the state gauges (free/resident/aliased) alongside.
            if getattr(self.engine, "paged", False):
                kv = self.engine.kv_page_counters()
                if kv != self._kv_seen:
                    self.metrics.record_kv_pages(
                        {
                            k: n - self._kv_seen.get(k, 0)
                            for k, n in kv.items()
                        },
                        self.engine.kv_page_stats(),
                    )
                    self._kv_seen = kv
            for rid, n in fold_tokens.items():
                acct = self._acct.get(rid)
                if acct is not None:
                    acct["decode_folds"] += 1
                    acct["emitted_tokens"] += n
            if self.tracer is not None and fold_tokens:
                # One event per request per fold (not per token): "this fold,
                # this request rode it for n tokens" — the decode-side trace
                # granularity the hot loop can afford. Recorded before the
                # finish events below so a trace's fold events always precede
                # its terminal span.
                for rid, n in fold_tokens.items():
                    self.tracer.event(
                        rid, _trace.SPAN_DECODE_FOLD, attrs={"tokens": n}
                    )
            jr_on = self.journal is not None
            for slot, rid, tok, done in fold_results:
                emitted += 1
                if jr_on:
                    self._jr_tokens.setdefault(rid, []).append(int(tok))
                events.append(
                    TokenEvent(rid, tok, done, "finished" if done else "token")
                )
                if done:
                    self.metrics.record_finish(queue_depth=self.queue_depth())
                    self._trace(rid, _trace.SPAN_FINISH)
                    finished_slots.append(slot)
                    finished_rids.append(rid)
                    closed.append((rid, "finished"))
            if fold_results:
                # Fault point: a decode fold's tokens are harvested (and
                # journaled below) but the step has not returned — mid-decode
                # death with partially-streamed outputs.
                self._fault("fold_boundary")
            with self._lock:
                self._slot_req.update(newly)
                for req in admits:
                    self._admitting.discard(req.request_id)
                for slot in finished_slots:
                    self._slot_req.pop(slot, None)
                # Purge cancels that raced a same-fold finish: the id left
                # _slot_req above, so the next eviction scan would never see
                # it — without this, a cancel landing while the lock-free
                # engine section ran would pin the id in _cancelled forever
                # and spuriously evict a later request reusing it.
                self._cancelled.difference_update(finished_rids)
                self._migrating.difference_update(finished_rids)
            # Device-seconds attribution: this step's wall time split evenly
            # over the requests that held engine state through it (resident
            # slots + this step's admissions). An estimate by construction —
            # the fold executes all resident slots in one batched dispatch —
            # but it sums exactly to serving wall time, so fleet goodput
            # (tokens per device-second) is conserved.
            wall = time.monotonic() - t0
            participants = set(resident_rids)
            participants.update(req.request_id for req in admits)
            participants.update(fold_tokens)
            participants.update(ev[1].request_id for ev in chunk_events)
            if participants:
                share = wall / len(participants)
                for rid in participants:
                    acct = self._acct.get(rid)
                    if acct is not None:
                        acct["device_s"] += share
            for rid, outcome in closed:
                self._acct_close(rid, outcome)
            if any(outcome == "finished" for _, outcome in closed):
                # Fault point: the terminal ledger/journal flush happened but
                # the finish events never reach the replica's buffers — the
                # replica RECORDED an outcome the client never saw, so the
                # client-side journal must still classify it incomplete and
                # resubmit (dedup keeps the stream exact).
                self._fault("post_finish_pre_ack")
            # Token accounting must be EXACT (the ledger balances against
            # it): count only admissions that really emitted a first token —
            # chunked admissions return None and their token is counted at
            # prefill completion.
            admit_tokens = sum(
                1 for _, first_tok, _ in (results if admits else [])
                if first_tok is not None
            )
            self.metrics.record_step(
                wall, active,
                emitted + prefilled + admit_tokens, self.queue_depth(),
            )
        return events

    def _finish_prefills(
        self,
        chunk_events: List[Any],
        newly: Dict[int, Any],
        events: List[TokenEvent],
        finished_rids: List[str],
        finished_slots: List[int],
        closed: List[Tuple[str, str]],
        piggyback: bool = False,
    ) -> int:
        """Process completed/advanced prefill chunk events: first-token
        metrics + traces, journal tokens, TokenEvents, write-through,
        and the disaggregated-prefill ship loop. Shared verbatim by the
        separate-dispatch path (prefill_step) and the piggyback path
        (pop_chunk_events after the fused fold)."""
        prefilled = 0
        #: (slot, task, Request): completed prefills whose KV pages
        #: ship to a decode replica instead of decoding here — the
        #: disaggregated-prefill handoff (collected in the loop, engine
        #: work below it so the fold never decodes a shipped slot).
        to_ship: List[Any] = []
        for slot, task, tok, done in chunk_events:
            prefilled += 1
            now = time.monotonic()
            req = newly.get(slot) or self._slot_req.get(slot)
            if req is not None:
                self.metrics.record_first_token(
                    now - req.submitted_at,
                    now - (req.admitted_at or now),
                    task.chunks,
                    task.matched_tokens,
                    len(task.tokens),
                )
                self._trace(
                    task.request_id, _trace.SPAN_FIRST_TOKEN, t=now,
                    ttft_s=round(now - req.submitted_at, 6),
                    chunks=task.chunks,
                    prefix_hit_tokens=task.matched_tokens,
                    # The prefill-mode detail the anatomy ledger surfaces:
                    # piggyback chunks rode inside decode folds, solo
                    # chunks had their own dispatches.
                    mode="piggyback" if piggyback else "solo",
                )
            acct = self._acct.get(task.request_id)
            if acct is not None:
                acct["prefill_chunks"] = task.chunks
                acct["prefix_hit_tokens"] = task.matched_tokens
                acct["emitted_tokens"] += 1
                if req is not None:
                    self._acct_first_token(acct, now - req.submitted_at)
            if self.journal is not None and tok is not None:
                self._jr_tokens.setdefault(
                    task.request_id, []
                ).append(int(tok))
                if req is not None:
                    self._jr_ttft.setdefault(
                        task.request_id, now - req.submitted_at
                    )
            events.append(
                TokenEvent(
                    task.request_id, tok, done,
                    "finished" if done else "token",
                )
            )
            if done:
                self.metrics.record_finish(queue_depth=self.queue_depth())
                self._trace(task.request_id, _trace.SPAN_FINISH)
                finished_rids.append(task.request_id)
                closed.append((task.request_id, "finished"))
                newly.pop(slot, None)
            elif (
                self.kvfleet is not None
                and req is not None
                and req.ship_to is not None
            ):
                # Disaggregated prefill: the first token streamed above
                # (the client's cursor dedups it when the decode
                # replica re-emits the identical stream); the slot's KV
                # pages ship below instead of decoding here.
                to_ship.append((slot, task, req))
                newly.pop(slot, None)
                finished_slots.append(slot)
                finished_rids.append(task.request_id)
        if (
            self.kvstore_writethrough
            and self.kvstore is not None
            and getattr(self.engine, "prefix_blocks", 0)
        ):
            # Write-through: every completed prefill's chain goes to
            # the persistent store so the pages survive this replica's
            # retirement (the prefill pool is the autoscaler's favorite
            # victim). Shipped slots reuse the export below; put errors
            # count loudly in kvstore_write_errors_total, never raise.
            shipped_slots = {s for s, _t, _r in to_ship}
            for slot, task, _tok, _done in chunk_events:
                if slot in shipped_slots:
                    continue
                wt = self.engine.export_prefix_blocks(task.tokens)
                if wt:
                    self.kvstore.put_blocks(wt)
        for slot, task, req in to_ship:
            # Release FIRST (the fold below must not decode a shipped
            # slot; the finished prompt's blocks already entered the
            # pool at prefill completion, so they survive the release
            # as digest-keyed cache pages), then export + ship. A
            # failed ship only costs the decode replica a cold prefill
            # — the client's resubmission carries a fetch hint back to
            # THIS replica, whose pool still holds the pages.
            self.engine.release(slot)
            blocks = (
                self.engine.export_prefix_blocks(task.tokens)
                if getattr(self.engine, "prefix_blocks", 0)
                else []
            )
            if (
                self.kvstore_writethrough
                and self.kvstore is not None
                and blocks
            ):
                self.kvstore.put_blocks(blocks)
            layerwise = bool(getattr(self.kvfleet, "layerwise_ship", False))
            self.kvfleet.ship(req.ship_to, req.request_id, blocks)
            if self.journal is not None:
                # A ship looks like a cancel to a replay of THIS
                # journal (truncation after the recorded first token);
                # the decode replica's journal carries the decode, and
                # the CLIENT journal is what re-drives the request
                # there.
                self.journal.record_cancel(req.request_id, True)
            self.metrics.record_cancel(queue_depth=self.queue_depth())
            self._trace(
                req.request_id, _trace.SPAN_SHIPPED,
                target=req.ship_to, blocks=len(blocks),
                layerwise=layerwise,
            )
            self._event(
                "kv_ship", request_id=req.request_id,
                target=req.ship_to, blocks=len(blocks),
                layerwise=layerwise,
            )
            closed.append((req.request_id, "shipped"))
            events.append(
                TokenEvent(
                    req.request_id, None, True, "shipped",
                    ship_to=req.ship_to,
                    ship_digests=[b[0] for b in blocks],
                )
            )
        return prefilled

    def run_until_idle(self, max_steps: int = 100_000) -> List[TokenEvent]:
        """Drive step() until queue and slots drain (tests)."""
        out: List[TokenEvent] = []
        for _ in range(max_steps):
            if not self.has_work():
                break
            out.extend(self.step())
        return out
