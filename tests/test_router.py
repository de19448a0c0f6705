"""Front-door router tests: health/affinity routing, admission control
with graceful shedding, the shared retry budget, hedged streams, and
queue-driven autoscaling (serve/router.py + the ServeClient hooks).

Fast tests drive the policy layer against in-memory fake replicas (the
exact RPC surface the client touches — no fabric processes, no engines);
the slow chaos/e2e tests at the bottom run real replica fleets.
"""
import threading
import time

import numpy as np
import pytest

from ray_lightning_tpu import fabric, obs
from ray_lightning_tpu.serve.router import (
    RequestRejectedError,
    RetryBudget,
    Router,
    RouterAutoscaler,
)


# ---------------------------------------------------------------------------
# Fake replicas (the client's RPC surface, in memory)
# ---------------------------------------------------------------------------
class _RemoteShim:
    def __init__(self, fn):
        self._fn = fn

    def remote(self, *args, **kwargs):
        return self._fn(*args, **kwargs)


class _FakeReplica:
    """In-memory 'replica': deterministic token function + a
    configurable stats/health surface the router's views pull."""

    def __init__(self, burst=4, stats=None, stall=False):
        self.dead = False
        self.burst = burst
        #: Answer polls but never emit tokens: the gray failure — the
        #: process is healthy by every probe, only the stream stalls.
        self.stall = stall
        self.stats_row = dict(stats or {})
        self.submits = []
        self.cancels = []
        self.stopped = False
        self.requests = {}
        #: RPC-shape accounting: serial submit calls vs batched
        #: submit_many calls (the PR18 wire-amortization assertions).
        self.submit_rpcs = 0
        self.batch_rpcs = 0

    @staticmethod
    def tokens_for(prompt, seed, n):
        return [(sum(prompt) + 7 * seed + i) % 97 for i in range(n)]

    def is_alive(self):
        # Process liveness (the supervisor's no-RPC probe).
        return not self.dead

    def _check(self):
        if self.dead:
            raise fabric.ActorDiedError("fake replica dead")

    def _rpc_submit(self, prompt, request_id=None, **kw):
        self._check()
        self.submit_rpcs += 1
        return self._admit(prompt, request_id, kw)

    def _admit(self, prompt, request_id, kw):
        self.submits.append((request_id, dict(kw)))
        self.requests[request_id] = self.tokens_for(
            prompt, kw.get("seed", 0), kw.get("max_new_tokens", 32)
        )
        return request_id

    def _rpc_submit_many(self, reqs):
        # The batched wire shape (ServeReplica.submit_many): ONE RPC,
        # same per-request bookkeeping as submit, rid list back.
        self._check()
        self.batch_rpcs += 1
        rids = []
        for req in reqs:
            req = dict(req)
            prompt = req.pop("prompt")
            rid = req.pop("request_id", None)
            rids.append(self._admit(prompt, rid, req))
        return rids

    def _rpc_result(self, rid, cursor, wait_s=0.0):
        self._check()
        if self.stall:
            return {"tokens": [], "done": False, "status": "running"}
        toks = self.requests[rid]
        out = toks[cursor: cursor + self.burst]
        return {
            "tokens": out,
            "done": cursor + len(out) >= len(toks),
            "status": "finished",
        }

    def _rpc_cancel(self, rid):
        self._check()
        self.cancels.append(rid)
        return True

    def _rpc_stats(self):
        self._check()
        return dict(self.stats_row)

    def _rpc_health(self):
        self._check()
        return {
            "verdict": self.stats_row.get("health", "healthy"),
            "healthy": self.stats_row.get("health", "healthy")
            == "healthy",
        }

    def _rpc_stop(self):
        self._check()
        self.stopped = True

    def _rpc_ping(self):
        self._check()
        return "ok"

    def __getattr__(self, name):
        fn = object.__getattribute__(self, "__dict__").get(name)
        if fn is not None:
            return fn
        try:
            return _RemoteShim(
                object.__getattribute__(self, f"_rpc_{name}")
            )
        except AttributeError:
            raise AttributeError(name) from None


def _client(replicas, **kw):
    from ray_lightning_tpu.obs.registry import MetricsRegistry
    from ray_lightning_tpu.serve.client import ServeClient

    events = obs.EventLog()
    reg = MetricsRegistry()
    return (
        ServeClient(replicas, registry=reg, events=events, **kw),
        reg,
        events,
    )


def _router(client=None, reg=None, **kw):
    from ray_lightning_tpu.obs.registry import MetricsRegistry

    reg = reg or MetricsRegistry()
    return Router(
        client=client, registry=reg, events=obs.EventLog(),
        refresh_s=0.0, **kw
    ), reg


#: Idle-healthy stats row (summarize_replica's input schema).
def _stats(queue=0, active=0, slots=2, rate=100.0, health="healthy",
           prefix_bytes=0):
    row = {
        "queue_depth": queue,
        "active_slots": active,
        "num_slots": slots,
        "decode_tokens_per_sec": rate,
        "health": health,
    }
    if prefix_bytes:
        row["prefix"] = {
            "tiers": {
                "device": {"hits": 0, "misses": 0, "bytes": prefix_bytes}
            }
        }
    return row


# ---------------------------------------------------------------------------
# RetryBudget
# ---------------------------------------------------------------------------
def test_retry_budget_caps_retries_as_fraction_of_submits():
    t = [0.0]
    b = RetryBudget(ratio=0.5, window_s=10.0, floor=1, clock=lambda: t[0])
    # floor only: 1 retry allowed, then exhausted.
    assert b.try_spend() is True
    assert b.try_spend() is False
    # 4 submits raise the allowance to floor + 2 = 3.
    for _ in range(4):
        b.note_submit()
    assert b.allowed() == 3
    assert b.try_spend() is True
    assert b.try_spend() is True
    assert b.try_spend() is False
    # The window slides: old submits AND old retries age out.
    t[0] = 11.0
    assert b.allowed() == 1
    assert b.try_spend() is True
    assert b.try_spend() is False


def test_rpc_retry_budget_exhausted_fails_over_instead_of_retrying(
    start_fabric,
):
    """The satellite: per-call retries were unbounded in aggregate — N
    streams each retrying within their own cap is still a storm. With
    the shared budget spent, a transient failure fails over NOW, with a
    warn event and the rlt_serve_retry_budget_exhausted_total count."""
    start_fabric(num_cpus=1)

    class _Flaky(_FakeReplica):
        def _rpc_result(self, rid, cursor, wait_s=0.0):
            raise ConnectionError("transient forever")

    flaky, good = _Flaky(), _FakeReplica()
    client, reg, events = _client(
        [flaky, good],
        rpc_retries=5, backoff_base_s=0.001,
        retry_budget_ratio=0.0, retry_budget_floor=0,
    )
    h = client.submit([2, 3], max_new_tokens=4, seed=1, replica=0)
    got = list(client.stream_handle(h))
    assert got == _FakeReplica.tokens_for([2, 3], 1, 4)
    # Zero backoff retries happened: the budget refused the first one.
    assert reg.counter(
        "rlt_serve_failover_rpc_retries_total"
    ).value() == 0
    assert reg.counter(
        "rlt_serve_retry_budget_exhausted_total"
    ).value() >= 1
    assert "rpc_retry_budget_exhausted" in [
        e["name"] for e in events.tail(32)
    ]


# ---------------------------------------------------------------------------
# Router policy: health/state weighting
# ---------------------------------------------------------------------------
class _StatsClient:
    """Just the pull surface Router.refresh needs."""

    def __init__(self, rows):
        self.rows = rows  # list of stats dicts

    def stats(self):
        return [dict(r) for r in self.rows]

    def health(self):
        return [
            {
                "verdict": r.get("health", "healthy"),
                "healthy": r.get("health", "healthy") == "healthy",
            }
            for r in self.rows
        ]


def test_router_excludes_unhealthy_and_supervisor_states():
    """Verdicts and supervisor states finally have a consumer: an
    unhealthy replica and a DRAINING/PREEMPTING one get no new traffic;
    a degraded one is demoted but still routable."""
    rows = [_stats(), _stats(health="unhealthy"), _stats()]
    states = {2: "preempting"}
    router, reg = _router(
        _StatsClient(rows),
        state_fn=lambda: [
            {"replica": i, "state": states.get(i, "healthy")}
            for i in range(3)
        ],
    )
    picks = {router.pick([1, 2, 3], alive=[0, 1, 2]) for _ in range(8)}
    assert picks == {0}  # 1 unhealthy, 2 preempting
    # Weight gauge: published per replica, zero for the excluded ones.
    router.refresh(force=True)
    g = reg.gauge("rlt_router_replica_weight")
    assert g.value(replica=0) > 0.0
    assert g.value(replica=1) == 0.0
    assert g.value(replica=2) == 0.0
    # Degraded: demoted, not excluded — an idle degraded replica loses
    # to an idle healthy one but still wins over a loaded healthy one.
    rows[1]["health"] = "degraded"
    states.clear()
    router.refresh(force=True)
    assert router.pick([1], alive=[0, 1]) == 0
    rows[0].update(queue_depth=8, active_slots=2)
    router.refresh(force=True)
    assert router.pick([1], alive=[0, 1]) == 1


def test_router_reweight_counts_rebalances():
    rows = [_stats(), _stats()]
    router, reg = _router(_StatsClient(rows))
    router.refresh(force=True)
    rows[1]["health"] = "unhealthy"
    router.refresh(force=True)
    assert reg.counter(
        "rlt_router_rebalances_total"
    ).value(reason="excluded") == 1
    rows[1]["health"] = "healthy"
    router.refresh(force=True)
    assert reg.counter(
        "rlt_router_rebalances_total"
    ).value(reason="restored") == 1


def test_router_load_balances_and_falls_back_without_views():
    # No client, no poller: unknown replicas get a neutral default view
    # (routable, unloaded) and equal-score picks rotate over both.
    router, reg = _router(None)
    picks = [router.pick([1], alive=[0, 1]) for _ in range(4)]
    assert sorted(set(picks)) == [0, 1]
    assert reg.counter(
        "rlt_router_routed_total"
    ).value(reason="weighted") == 4
    # With views: the least-loaded replica wins outright.
    router2, _ = _router(
        _StatsClient([_stats(queue=6, active=2), _stats()])
    )
    assert all(
        router2.pick([1], alive=[0, 1]) == 1 for _ in range(4)
    )
    # Availability safety: when the router's (possibly stale) views say
    # NOBODY is routable but the client's alive list disagrees, the
    # router must not be LESS available than the round-robin it
    # replaced — it falls back to the alive list.
    router3, reg3 = _router(
        _StatsClient([
            _stats(health="unhealthy"), _stats(health="unhealthy"),
        ])
    )
    assert router3.pick([1], alive=[0, 1]) in (0, 1)
    assert reg3.counter(
        "rlt_router_routed_total"
    ).value(reason="fallback") == 1


# ---------------------------------------------------------------------------
# Prefix affinity
# ---------------------------------------------------------------------------
def test_router_prefix_affinity_routes_to_the_warm_replica():
    """Shared-prefix traffic lands where the prefix is warm: after a
    chain is observed on replica 1, same-prefix requests stick to it
    while unrelated prompts keep balancing — and the routed counter
    records the affinity decisions."""
    router, reg = _router(
        _StatsClient([_stats(), _stats()]), prefix_block=4
    )
    prefix = [5, 6, 7, 8, 1, 2, 3, 4]  # two full blocks
    router.observe_route(prefix, 1)
    assert router.affinity_entries() == 2
    for _ in range(4):
        assert router.pick(prefix + [9, 9], alive=[0, 1]) == 1
    assert reg.counter(
        "rlt_router_routed_total"
    ).value(reason="affinity") == 4
    # Unrelated prompts still spread over both.
    other = [list(range(10 + i, 20 + i)) for i in range(4)]
    assert {router.pick(p, alive=[0, 1]) for p in other} == {0, 1}
    # A lost/retired replica's chains are forgotten — no ghost chasing.
    router.forget_replica(1)
    assert router.affinity_entries() == 0


def test_router_affinity_weighted_by_effective_cache():
    """Equal matched chains, unequal caches: the replica whose tiers
    hold more resident bytes (the rlt_serve_prefix_bytes signal) wins
    the tie — its chain is likelier to still be warm."""
    router, _ = _router(
        _StatsClient([
            _stats(prefix_bytes=1 << 10),
            _stats(prefix_bytes=10 << 20),
        ]),
        prefix_block=4,
    )
    prompt = [1, 2, 3, 4, 9, 9]
    # The chain was seen on BOTH (e.g. a failover replayed it): the
    # affinity map holds the newest owner; route there.
    router.observe_route(prompt, 0)
    router.observe_route(prompt, 1)
    assert router.pick(prompt, alive=[0, 1]) == 1


def test_client_submit_feeds_the_affinity_map(start_fabric):
    start_fabric(num_cpus=1)
    r0, r1 = _FakeReplica(stats=_stats()), _FakeReplica(stats=_stats())
    client, reg, _ = _client([r0, r1])
    router, _ = _router(client, prefix_block=4)
    client.router = router
    prompt = [3, 1, 4, 1, 5, 9, 2, 6]
    h1 = client.submit(prompt, max_new_tokens=2)
    # The same prefix now routes to wherever the first landed.
    h2 = client.submit(prompt[:4] + [7, 7, 7, 7], max_new_tokens=2)
    assert h2.replica == h1.replica
    assert router.affinity_entries() > 0


def test_affinity_routing_hits_more_prefix_tokens_than_round_robin():
    """The router's decisions driving two REAL engines: the same sixteen
    requests over four shared prefixes, routed round-robin and then by
    prefix affinity. Affinity pays one cold prefill a prefix, round-robin
    one a (prefix, replica) pair — so the fleet's prefix-hit tokens are
    strictly higher under affinity, at identical outputs. Counts only."""
    import jax

    from ray_lightning_tpu.models.gpt import init_gpt_params
    from ray_lightning_tpu.serve.engine import DecodeEngine
    from ray_lightning_tpu.serve.scheduler import SamplingParams, Scheduler

    cfg = _ft_cfg()
    params = init_gpt_params(jax.random.PRNGKey(0), cfg)
    g = np.random.default_rng(0)
    prefixes = [g.integers(0, 97, size=8).tolist() for _ in range(4)]
    # Every prefix visited four times, interleaved so that alternating
    # replicas splits each prefix's visits over both.
    order = [0, 1, 0, 2, 0, 3, 1, 0, 2, 1, 3, 2, 0, 1, 3, 2]
    jobs = [prefixes[k] + g.integers(0, 97, size=4).tolist() for k in order]

    def run(pick):
        scheds = [
            Scheduler(DecodeEngine(
                params, cfg, num_slots=2, max_seq=32,
                prefill_buckets=[16], prefill_chunk=4,
                prefix_blocks=32, prefix_block=4, decode_fold=2,
            ))
            for _ in range(2)
        ]
        outs = []
        for i, prompt in enumerate(jobs):
            sched = scheds[pick(i, prompt)]
            rid = sched.submit(prompt, SamplingParams(max_new_tokens=4))
            outs.append([
                e.token for e in sched.run_until_idle()
                if e.request_id == rid and e.token is not None
            ])
        engines = [s.engine for s in scheds]
        return (
            outs,
            sum(e.prefix_hit_tokens for e in engines),
            sum(e.prefix_prompt_tokens for e in engines),
        )

    router, _ = _router(
        _StatsClient([_stats(), _stats()]), prefix_block=4
    )

    def by_affinity(_, prompt):
        replica = router.pick(prompt, alive=[0, 1])
        router.observe_route(prompt, replica)
        return replica

    out_rr, hit_rr, tot_rr = run(lambda i, _: i % 2)
    out_aff, hit_aff, tot_aff = run(by_affinity)
    assert out_aff == out_rr and all(len(o) == 4 for o in out_rr)
    assert tot_aff == tot_rr == 16 * 12
    assert hit_aff > hit_rr > 0, (hit_aff, hit_rr)


# ---------------------------------------------------------------------------
# Admission control: typed rejection + retry-after
# ---------------------------------------------------------------------------
def test_router_rejects_infeasible_deadline_up_front(start_fabric):
    """The satellite regression: a submit whose deadline cannot be met
    even at the target's windowed decode rate is rejected AT THE DOOR
    (typed outcome, retry-after hint, journaled) — today it would queue
    on a replica and come back as a late server-side 'expired'."""
    start_fabric(num_cpus=1)
    # 10 tokens/s measured: 50 tokens cannot fit a 1s deadline.
    r0 = _FakeReplica(stats=_stats(rate=10.0))
    client, reg, events = _client([r0])
    router, rreg = _router(client, reg=reg)
    client.router = router
    with pytest.raises(RequestRejectedError) as exc_info:
        client.submit([1, 2, 3], max_new_tokens=50, deadline_s=1.0)
    exc = exc_info.value
    assert exc.reason == "deadline_infeasible"
    assert exc.retry_after_s > 0
    # The request never left the driver.
    assert r0.submits == []
    # Typed outcome in the driver journal: submit + rejected.
    entries = client.journal.dump()["entries"]
    assert [e["kind"] for e in entries] == ["submit", "outcome"]
    assert entries[1]["outcome"] == "rejected"
    assert reg.counter(
        "rlt_router_shed_total"
    ).value(reason="deadline_infeasible") == 1
    assert "request_rejected" in [e["name"] for e in events.tail(16)]
    # A feasible deadline on the same fleet is admitted.
    h = client.submit([1, 2, 3], max_new_tokens=4, deadline_s=30.0)
    assert list(client.stream_handle(h)) == _FakeReplica.tokens_for(
        [1, 2, 3], 0, 4
    )


def test_router_sheds_lowest_priority_when_saturated(start_fabric):
    """Fleet saturated (every routable queue >= factor x slots): low-
    priority work is shed with a retry-after hint; priority-0 work is
    still admitted (the point of shedding is protecting it)."""
    start_fabric(num_cpus=1)
    sat = _stats(queue=20, active=2, slots=2, rate=100.0)
    r0 = _FakeReplica(stats=sat)
    r1 = _FakeReplica(stats=dict(sat))
    client, reg, _ = _client([r0, r1])
    router, _ = _router(client, reg=reg, shed_queue_factor=4.0)
    client.router = router
    with pytest.raises(RequestRejectedError) as exc_info:
        client.submit([1], max_new_tokens=4, priority=1)
    assert exc_info.value.reason == "saturated"
    assert 0 < exc_info.value.retry_after_s <= 30.0
    assert reg.counter(
        "rlt_router_shed_total"
    ).value(reason="saturated") == 1
    # Priority 0, no deadline: still admitted.
    h = client.submit([1], max_new_tokens=4, priority=0)
    assert h.request_id in (r0.requests | r1.requests)
    # Shed can be disabled: the same submit routes.
    router.shed = False
    h2 = client.submit([1], max_new_tokens=4, priority=1)
    assert h2.request_id in (r0.requests | r1.requests)


# ---------------------------------------------------------------------------
# Hedged streaming reads
# ---------------------------------------------------------------------------
def test_stream_hedges_off_a_stalled_replica_bit_exact(start_fabric):
    """The gray failure: replica 0 answers every poll (healthy by all
    probes) but its stream stalls. With hedge_after_s armed the stream
    re-drives on replica 1 under the same id/seed — output identical to
    an undisturbed run, the slow copy cancelled, replica 0 NOT excluded
    (it is healthy; only this stream was slow)."""
    start_fabric(num_cpus=1)
    r0 = _FakeReplica(stall=True, stats=_stats())
    r1 = _FakeReplica(stats=_stats())
    client, reg, events = _client([r0, r1], hedge_after_s=0.05)
    prompt = [4, 4, 4]
    h = client.submit(prompt, max_new_tokens=6, seed=3, replica=0)
    got = list(client.stream_handle(h, poll_s=0.01, timeout_s=30))
    assert got == _FakeReplica.tokens_for(prompt, 3, 6)
    # The hedge target received the journal record verbatim, same id.
    (rid1, kw1) = r1.submits[0]
    assert rid1 == h.request_id and kw1["seed"] == 3
    # The slow copy was cancelled best-effort; nothing got excluded.
    assert r0.cancels == [h.request_id]
    assert client.excluded() == []
    assert reg.counter(
        "rlt_router_hedges_total"
    ).value(reason="slow_stream") == 1
    assert "request_hedged" in [e["name"] for e in events.tail(16)]


def test_stream_does_not_hedge_without_a_peer(start_fabric):
    start_fabric(num_cpus=1)
    r0 = _FakeReplica(stall=True, stats=_stats())
    client, reg, _ = _client([r0], hedge_after_s=0.02)
    h = client.submit([1], max_new_tokens=4, replica=0)
    with pytest.raises(TimeoutError):
        list(client.stream_handle(h, poll_s=0.01, timeout_s=0.3))
    assert reg.counter("rlt_router_hedges_total").value() == 0


# ---------------------------------------------------------------------------
# Route-table correctness under composition (drain + migrate + reweight)
# ---------------------------------------------------------------------------
def test_stream_follows_migration_while_router_reweights(start_fabric):
    """The composition satellite: a streaming request is live-migrated
    off a PREEMPTING replica (drain plan) while the router re-weights
    and the supervisor drains the source — the stream completes exactly,
    nothing is lost, and NO new submit routes to the draining source."""
    start_fabric(num_cpus=1)

    class _Draining(_FakeReplica):
        def _rpc_begin_drain(self, budget_s=None, wait_s=15.0):
            self._check()
            return {
                "budget_s": budget_s,
                "finish": [],
                "migrate": [
                    {"request_id": rid, "blocks": []}
                    for rid in list(self.requests)
                ],
            }

    r0 = _Draining(stall=True, stats=_stats())  # stalled: must migrate
    r1 = _FakeReplica(stats=_stats())
    client, reg, _ = _client([r0, r1])
    states = {0: "healthy", 1: "healthy"}
    router, _ = _router(
        client, reg=reg,
        state_fn=lambda: [
            {"replica": i, "state": s} for i, s in states.items()
        ],
    )
    client.router = router
    prompt = [7, 7, 1]
    h = client.submit(prompt, max_new_tokens=5, seed=2, replica=0)
    # The preemption notice lands: the supervisor flips the state and
    # runs the drain (exclude + migrate), the router re-weights.
    states[0] = "preempting"
    router.refresh(force=True)
    res = client.preempt_drain(0)
    assert res["migrated"] == [h.request_id]
    # The stream follows the route table onto the survivor, bit-exact.
    got = list(client.stream_handle(h, poll_s=0.01, timeout_s=30))
    assert got == _FakeReplica.tokens_for(prompt, 2, 5)
    # While draining/preempting, NOTHING new routes to replica 0 — via
    # the router's state filter AND the client's exclusion.
    for i in range(4):
        h2 = client.submit([9, i], max_new_tokens=2)
        assert h2.replica == 1
    assert all(rid != h.request_id for rid, _ in r0.submits[1:])
    # Router rows say why: replica 0 is out of rotation.
    rows = {r["replica"]: r for r in router.rows()["replicas"]}
    assert rows[0]["routable"] is False
    assert rows[1]["routable"] is True


# ---------------------------------------------------------------------------
# Autoscaling: client surface + controller
# ---------------------------------------------------------------------------
def test_client_add_and_retire_replica_graceful(start_fabric):
    """Scale-up appends a pinged replica at a stable index; scale-down
    retires GRACEFULLY — excluded first, open requests migrated onto
    survivors (bit-exact streams), the actor stopped, and the index left
    as a tombstone (restore() cannot resurrect it)."""
    start_fabric(num_cpus=1)
    r0, r1 = _FakeReplica(stats=_stats()), _FakeReplica(stats=_stats())
    spawned = []

    def respawn(i, fresh_capacity=False):
        rep = _FakeReplica(stats=_stats())
        spawned.append((i, rep, fresh_capacity))
        return rep, []

    client, reg, events = _client([r0, r1], respawn_fn=respawn)
    idx = client.add_replica()
    assert idx == 2 and spawned[0][0] == 2 and spawned[0][2] is True
    assert client.alive_replicas() == [0, 1, 2]
    h = client.submit([8, 8], max_new_tokens=3, replica=2)
    assert list(client.stream_handle(h)) == _FakeReplica.tokens_for(
        [8, 8], 0, 3
    )
    # Retire replica 2 with a request STILL OPEN on it (stalled): the
    # drain times out, the request live-migrates, nothing is lost.
    new_rep = spawned[0][1]
    new_rep.stall = True
    h2 = client.submit([6, 1], max_new_tokens=4, seed=5, replica=2)
    res = client.retire_replica(2, drain_timeout_s=0.05)
    assert res["migrated"] == [h2.request_id] and res["lost"] == []
    got = list(client.stream_handle(h2, poll_s=0.01, timeout_s=30))
    assert got == _FakeReplica.tokens_for([6, 1], 5, 4)
    assert new_rep.stopped is True
    assert client.is_retired(2)
    assert client.alive_replicas() == [0, 1]
    client.restore(2)  # a tombstone stays a tombstone
    assert client.alive_replicas() == [0, 1]
    # Index-aligned surfaces say retired, not unreachable/unhealthy.
    assert client.stats()[2] == {"retired": True, "health": "retired"}
    assert client.health()[2]["verdict"] == "retired"
    names = [e["name"] for e in events.tail(32)]
    assert "replica_added" in names and "replica_retired" in names


def test_supervisor_skips_retired_replicas(start_fabric):
    """A scale-down tombstone must not look like a death: the
    supervisor never probes or restarts it (no restart storm after a
    deliberate retire)."""
    start_fabric(num_cpus=1)
    from ray_lightning_tpu.serve.supervisor import FleetSupervisor

    r0, r1 = _FakeReplica(stats=_stats()), _FakeReplica(stats=_stats())
    client, _, _ = _client([r0, r1], respawn_fn=lambda i, **k: (None, []))
    client.retire_replica(1, drain_timeout_s=0.0)
    sup = FleetSupervisor(client, clock=lambda: 0.0)
    summary = sup.tick()
    rows = {r["replica"]: r for r in sup.rows()}
    assert rows[1]["state"] == "retired"
    assert summary["restarted"] == 0 and summary["failed_over"] == 0
    assert rows[0]["state"] == "healthy"


class _ScaleClient:
    """The autoscaler's client surface, recording scale actions."""

    def __init__(self, n=1):
        self.n = n
        self.added = []
        self.retired = []

    def alive_replicas(self):
        return list(range(self.n))

    def add_replica(self):
        idx = self.n
        self.n += 1
        self.added.append(idx)
        return idx

    def retire_replica(self, idx, **kw):
        self.n -= 1
        self.retired.append(idx)
        return {"migrated": [], "lost": []}


class _ViewStub:
    """Router stand-in: views + shed counter the controller reads."""

    def __init__(self):
        self.queue = 0
        self.shed_count = 0

    def views(self):
        return {
            i: {"queue_depth": self.queue, "active_slots": 0}
            for i in range(8)
        }


def test_autoscaler_scales_up_and_down_within_bounds():
    from ray_lightning_tpu.obs.registry import MetricsRegistry

    client = _ScaleClient(n=1)
    stub = _ViewStub()
    reg = MetricsRegistry()
    auto = RouterAutoscaler(
        client, router=stub, min_replicas=1, max_replicas=3,
        sustain_ticks=2, down_sustain_ticks=3,
        registry=reg, events=obs.EventLog(),
    )
    # Sustained overload: one tick is not enough (noise immunity)...
    stub.queue = 16
    assert auto.tick()["scaled"] is None
    # ... the second scales up; pressure persisting scales again.
    assert auto.tick()["scaled"] == ("up", 1)
    auto.tick()
    assert auto.tick()["scaled"] == ("up", 2)
    # At max_replicas: sustained pressure never exceeds the bound.
    for _ in range(6):
        assert auto.tick()["scaled"] is None
    assert client.n == 3
    # A shed burst alone (queue drained BY shedding) also counts as
    # pressure — but we are at max, so nothing happens.
    stub.queue = 0
    stub.shed_count = 5
    auto.tick()
    assert client.n == 3
    # Sustained idle: scale down LIFO to min_replicas, never below.
    for _ in range(3):
        auto.tick()
    assert client.retired == [2]
    for _ in range(6):
        auto.tick()
    assert client.n == 1 and client.retired == [2, 1]
    assert reg.counter(
        "rlt_router_rebalances_total"
    ).value(reason="scale_up") == 2
    assert reg.counter(
        "rlt_router_rebalances_total"
    ).value(reason="scale_down") == 2


# ---------------------------------------------------------------------------
# Observability plumbing: /fleet payload, rlt top, journal header
# ---------------------------------------------------------------------------
def test_fleet_payload_and_top_render_router_rows():
    from ray_lightning_tpu.cli import render_fleet
    from ray_lightning_tpu.obs.fleet import FleetPoller

    router_rows = {
        "replicas": [
            {"replica": 0, "weight": 0.83, "routable": True,
             "state": "healthy", "health": "healthy", "queue_depth": 0},
            {"replica": 1, "weight": 0.0, "routable": False,
             "state": "draining", "health": "unhealthy",
             "queue_depth": 2},
        ],
        "routed": 41, "shed": 7, "affinity_entries": 3, "config": {},
    }
    poller = FleetPoller(
        pull_fn=lambda: ([_stats(), _stats()], None, None),
        router_fn=lambda: router_rows,
    )
    poller.poll_now()
    payload = poller.to_dict()
    assert payload["router"]["routed"] == 41
    frame = render_fleet(payload)
    assert "router:" in frame
    assert "shed=7" in frame and "excluded=r1" in frame
    assert "weight" in frame and "0.83" in frame


def test_router_rows_carry_weights_and_totals():
    router, _ = _router(
        _StatsClient([_stats(), _stats(health="unhealthy")])
    )
    router.pick([1, 2], alive=[0, 1])
    rows = router.rows()
    assert rows["routed"] == 1 and rows["shed"] == 0
    by_idx = {r["replica"]: r for r in rows["replicas"]}
    assert by_idx[0]["routable"] is True and by_idx[0]["weight"] > 0
    assert by_idx[1]["routable"] is False and by_idx[1]["weight"] == 0.0
    assert rows["config"]["shed_queue_factor"] == 4.0


def test_journal_header_records_router_policy_and_replay_surfaces_it():
    """The provenance satellite: the recorded policy rides the journal
    header and comes back out of a replay — a replayed capture knows
    what shaped its traffic (filtered to the known knob vocabulary)."""
    from ray_lightning_tpu.obs.journal import replay_journal
    from ray_lightning_tpu.serve.router import router_config_from_header

    header = {
        "version": 1,
        "router": {
            "shed": True, "shed_queue_factor": 4.0,
            "affinity": True, "prefix_block": 16,
            "autoscale_max": 4, "bogus_knob": 1,
        },
    }
    cfg = router_config_from_header(header)
    assert cfg == {
        "shed": True, "shed_queue_factor": 4.0,
        "affinity": True, "prefix_block": 16, "autoscale_max": 4,
    }
    assert router_config_from_header(None) == {}
    assert router_config_from_header({"version": 1}) == {}

    class _Idle:
        def has_work(self):
            return False

    res = replay_journal(
        {"header": header, "entries": []}, scheduler=_Idle()
    )
    assert res["router_config"] == cfg


def test_engine_header_carries_the_router_section():
    """ServeReplica passes the driver's resolved router knobs into its
    journal header (router_config ctor kwarg -> engine_header(router=))
    so every captured journal knows the policy that shaped it."""
    import dataclasses
    import types

    from ray_lightning_tpu.obs.journal import engine_header

    @dataclasses.dataclass
    class _Cfg:
        vocab_size: int = 8

    eng = types.SimpleNamespace(
        cfg=_Cfg(), num_slots=2, max_seq=16, prefill_buckets=[8],
        decode_fold=1, pipeline=True, prefill_chunk=0, prefix_blocks=0,
        prefix_block=16, spec="off", spec_depth=4, spec_window=32,
        mesh_desc=None,
    )
    knobs = {"shed": True, "shed_queue_factor": 4.0}
    header = engine_header(eng, router=knobs)
    assert header["router"] == knobs
    assert "router" not in engine_header(eng)  # router off: no section


def test_serve_cli_knows_the_router_knobs():
    from ray_lightning_tpu.cli import _SERVE_KEYS

    assert {
        "router", "router_refresh_s", "router_affinity", "router_shed",
        "shed_queue_factor", "retry_budget", "hedge_after_s",
        "autoscale_min", "autoscale_max", "autoscale_interval_s",
    } <= _SERVE_KEYS


# ---------------------------------------------------------------------------
# End to end (slow): routed chaos + real autoscale, real replicas
# ---------------------------------------------------------------------------
FT_CFG = None


def _ft_cfg():
    global FT_CFG
    if FT_CFG is None:
        from ray_lightning_tpu.models.gpt import GPTConfig

        FT_CFG = GPTConfig(
            vocab_size=97, n_layer=1, n_head=4, n_kv_head=2, d_model=32,
            max_seq=64, attn_impl="reference", compute_dtype="float32",
        )
    return FT_CFG


def _write_ckpt(tmp_path, params):
    import dataclasses
    import os

    from ray_lightning_tpu.utils.state_stream import (
        state_stream_to_file,
        to_state_stream,
    )

    path = os.path.join(tmp_path, "router.ckpt")
    state_stream_to_file(
        to_state_stream(
            {
                "params": params,
                "gpt_config": dataclasses.asdict(_ft_cfg()),
            }
        ),
        path,
    )
    return path


def _baseline(params, engine_kw, jobs):
    from ray_lightning_tpu.serve.engine import DecodeEngine
    from ray_lightning_tpu.serve.scheduler import (
        SamplingParams,
        Scheduler,
    )

    eng = DecodeEngine(params, _ft_cfg(), **engine_kw)
    sched = Scheduler(eng)
    out = []
    for prompt, sampling in jobs:
        rid = sched.submit(prompt, SamplingParams(**sampling))
        out.append([
            e.token for e in sched.run_until_idle()
            if e.request_id == rid and e.token is not None
        ])
    return out


@pytest.mark.slow
def test_chaos_kill_under_routed_load_zero_lost_bit_exact(
    start_fabric, tmp_path,
):
    """The acceptance chaos slice under ROUTED load: the router (health
    weights + affinity) places every request, a fault kills one replica
    mid-decode — zero lost, every surviving stream bit-identical to an
    uninterrupted oracle, and the router learns the death (affinity
    entries for the dead replica dropped; new traffic routes around)."""
    import jax

    from ray_lightning_tpu.models.gpt import init_gpt_params
    from ray_lightning_tpu.serve.client import start_replicas
    from ray_lightning_tpu.serve.supervisor import FleetSupervisor

    start_fabric(num_cpus=4)
    params = init_gpt_params(jax.random.PRNGKey(0), _ft_cfg())
    ckpt = _write_ckpt(tmp_path, params)
    rng = np.random.default_rng(7)
    jobs = [
        (rng.integers(0, 97, size=8).tolist(),
         {"max_new_tokens": 8, "seed": i})
        for i in range(6)
    ]
    engine_kw = dict(
        num_slots=2, max_seq=64, prefill_buckets=[16], decode_fold=2
    )
    expected = _baseline(params, engine_kw, jobs)
    client = start_replicas(
        2, ckpt_path=ckpt, env={"JAX_PLATFORMS": "cpu"}, **engine_kw
    )
    sup = FleetSupervisor(
        client, interval_s=0.2, restart_backoff_s=0.2,
        restart_limit=3, probe_timeout_s=60.0,
    ).start()
    router = Router(
        client=client, state_fn=sup.rows, refresh_s=0.2,
        prefix_block=8,
    )
    client.router = router
    try:
        client.inject_fault(
            0,
            [{"point": "fold_boundary", "action": "kill", "after": 2}],
        )
        handles = [client.submit(p, **s) for p, s in jobs]
        outs = {}
        lost = []

        def pull(i, h):
            try:
                outs[i] = list(client.stream_handle(h, timeout_s=180))
            except Exception:  # noqa: BLE001 - a lost stream IS the bug
                lost.append(i)

        threads = [
            threading.Thread(target=pull, args=(i, h))
            for i, h in enumerate(handles)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=180)
        assert not lost
        assert [outs[i] for i in range(len(jobs))] == expected
        # The router saw the fleet: decisions counted, and subsequent
        # traffic routes cleanly (the dead replica excluded until its
        # supervisor restart re-includes it).
        assert router.routed >= len(jobs)
        h = client.submit(jobs[0][0], **jobs[0][1])
        assert list(client.stream_handle(h, timeout_s=180)) == expected[0]
    finally:
        sup.stop()
        client.shutdown()


@pytest.mark.slow
def test_chaos_wedge_under_routed_load_hedges_bit_exact(
    start_fabric, tmp_path,
):
    """The gray-failure slice of the chaos grid: one replica's loop
    thread WEDGES mid-decode (its RPC surface keeps answering — no
    probe sees a death), under routed load with hedging armed. Every
    stream that stalled on the wedged replica re-drives on the survivor
    bit-exactly; zero lost."""
    import jax

    from ray_lightning_tpu.models.gpt import init_gpt_params
    from ray_lightning_tpu.serve.client import start_replicas

    start_fabric(num_cpus=4)
    params = init_gpt_params(jax.random.PRNGKey(0), _ft_cfg())
    ckpt = _write_ckpt(tmp_path, params)
    rng = np.random.default_rng(13)
    jobs = [
        (rng.integers(0, 97, size=8).tolist(),
         {"max_new_tokens": 8, "seed": i})
        for i in range(6)
    ]
    engine_kw = dict(
        num_slots=2, max_seq=64, prefill_buckets=[16], decode_fold=2
    )
    expected = _baseline(params, engine_kw, jobs)
    client = start_replicas(
        2, ckpt_path=ckpt, env={"JAX_PLATFORMS": "cpu"},
        hedge_after_s=0.5, **engine_kw,
    )
    router = Router(client=client, refresh_s=0.2, prefix_block=8)
    client.router = router
    try:
        client.inject_fault(
            0,
            [{"point": "fold_boundary", "action": "wedge",
              "seconds": 600, "after": 1}],
        )
        handles = [client.submit(p, **s) for p, s in jobs]
        assert any(h.replica == 0 for h in handles)
        outs = {}
        lost = []

        def pull(i, h):
            try:
                outs[i] = list(client.stream_handle(h, timeout_s=120))
            except Exception:  # noqa: BLE001 - a lost stream IS the bug
                lost.append(i)

        threads = [
            threading.Thread(target=pull, args=(i, h))
            for i, h in enumerate(handles)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not lost
        assert [outs[i] for i in range(len(jobs))] == expected
        # The wedged replica's streams really were hedged (not failed
        # over: its process never died).
        from ray_lightning_tpu.obs.registry import get_registry

        assert get_registry().counter(
            "rlt_router_hedges_total"
        ).value(reason="slow_stream") >= 1
    finally:
        client.shutdown()


@pytest.mark.slow
def test_autoscaler_end_to_end_scale_up_then_graceful_retire(
    start_fabric, tmp_path,
):
    """Acceptance: autoscaler scale-up/scale-down exercised END TO END
    on real replicas — sustained queue pressure spawns a real replica
    through the retained recipe; a sustained-idle fleet retires it with
    ZERO requests lost (drained, leftovers migrated, streams exact)."""
    import jax

    from ray_lightning_tpu.models.gpt import init_gpt_params
    from ray_lightning_tpu.serve.client import start_replicas

    start_fabric(num_cpus=4)
    params = init_gpt_params(jax.random.PRNGKey(0), _ft_cfg())
    ckpt = _write_ckpt(tmp_path, params)
    rng = np.random.default_rng(11)
    jobs = [
        (rng.integers(0, 97, size=8).tolist(),
         {"max_new_tokens": 12, "seed": i})
        for i in range(8)
    ]
    engine_kw = dict(
        num_slots=2, max_seq=64, prefill_buckets=[16], decode_fold=2
    )
    expected = _baseline(params, engine_kw, jobs)
    client = start_replicas(
        1, ckpt_path=ckpt, env={"JAX_PLATFORMS": "cpu"}, **engine_kw
    )
    router = Router(client=client, refresh_s=0.05)
    client.router = router
    auto = RouterAutoscaler(
        client, router=router, min_replicas=1, max_replicas=2,
        sustain_ticks=1, down_sustain_ticks=1,
        up_queue_per_replica=1.0,
    )
    try:
        # Slow the lone replica so a burst builds real queue depth.
        client.inject_fault(
            0,
            [{"point": "fold_boundary", "action": "delay",
              "seconds": 0.1, "after": k} for k in range(1, 60)],
        )
        handles = [client.submit(p, **s) for p, s in jobs]
        # Queue pressure -> one sustained tick -> a REAL second replica.
        deadline = time.monotonic() + 60
        scaled = None
        while scaled is None and time.monotonic() < deadline:
            router.refresh(force=True)
            scaled = auto.tick()["scaled"]
            time.sleep(0.05)
        assert scaled == ("up", 1), scaled
        assert client.alive_replicas() == [0, 1]
        # New traffic reaches the new replica; everything stays exact.
        outs = [
            list(client.stream_handle(h, timeout_s=180))
            for h in handles
        ]
        assert outs == expected
        h = client.submit(jobs[0][0], replica=1, **jobs[0][1])
        assert (
            list(client.stream_handle(h, timeout_s=180)) == expected[0]
        )
        # Idle fleet -> graceful retire of the scaled-up replica, with
        # an open request parked on it: migrated, not lost.
        client.inject_fault(0, None)
        hold = client.submit(
            jobs[1][0], replica=1, max_new_tokens=12, seed=1
        )
        res = client.retire_replica(1, drain_timeout_s=0.0)
        assert res["lost"] == []
        got = list(client.stream_handle(hold, timeout_s=180))
        assert got == expected[1]
        assert client.alive_replicas() == [0]
        # The autoscaler respects min_replicas afterwards.
        router.refresh(force=True)
        for _ in range(3):
            assert auto.tick()["scaled"] is None
        assert client.alive_replicas() == [0]
    finally:
        client.shutdown()
