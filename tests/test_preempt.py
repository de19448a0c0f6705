"""Preemption-aware graceful drain tests: the signal plane (monitor +
sources), the `preempt` fault action, the scheduler's drain plan, the
cross-replica KV handoff, the client/supervisor PREEMPTING machinery,
the fabric worker's terminating heartbeat, trainer checkpoint-on-notice,
and the slow chaos tier (an injected preemption under 2-replica load
loses zero requests, streams bit-identical to an uninterrupted oracle,
and migrated requests land warm prefix hits on the survivor; a gang
follower variant drains and respawns the gang as a unit).

The load-bearing property stacks on PR 11's: the engine is
deterministic given its inputs, so a migrated request replayed from its
journal submit record emits the IDENTICAL stream — and PR 10 made KV
blocks serializable, so the dying replica can hand the survivor its
warm prefix instead of forcing a cold re-prefill.
"""
import json
import os
import signal
import threading
import time

import numpy as np
import pytest

from ray_lightning_tpu import fabric, obs
from ray_lightning_tpu.models.gpt import GPTConfig, init_gpt_params
from ray_lightning_tpu.serve.faults import FaultInjector
from ray_lightning_tpu.serve.preempt import (
    PreemptionMonitor,
    get_monitor,
    peek_state,
    reset_monitor,
)
from ray_lightning_tpu.serve.supervisor import FleetSupervisor

PT_CFG = GPTConfig(
    vocab_size=97,
    n_layer=1,
    n_head=4,
    n_kv_head=2,
    d_model=32,
    max_seq=64,
    attn_impl="reference",
    compute_dtype="float32",
)


@pytest.fixture(scope="module")
def pt_params():
    import jax

    return init_gpt_params(jax.random.PRNGKey(0), PT_CFG)


@pytest.fixture(autouse=True)
def _fresh_monitor():
    """The monitor is a process singleton: every test starts (and ends)
    without a leftover notice or installed SIGTERM hook."""
    reset_monitor()
    yield
    reset_monitor()


# ---------------------------------------------------------------------------
# PreemptionMonitor (pure)
# ---------------------------------------------------------------------------
def test_monitor_first_notice_wins_and_state_reads():
    now = {"t": 100.0}
    mon = PreemptionMonitor(grace_s=30.0, clock=lambda: now["t"])
    assert not mon.pending()
    assert mon.remaining() is None
    assert mon.state() == {"pending": False}
    d1 = mon.notice(source="sigterm")
    assert d1 == 130.0
    # Idempotent: a second source reporting the same reclamation must
    # not extend the window.
    d2 = mon.notice(grace_s=500.0, source="metadata:TERMINATE")
    assert d2 == d1
    now["t"] = 110.0
    st = mon.state()
    assert st["pending"] is True
    assert st["source"] == "sigterm"
    assert st["remaining_s"] == 20.0
    now["t"] = 200.0
    assert mon.remaining() == 0.0  # clamped, never negative
    mon.clear()
    assert not mon.pending() and mon.state() == {"pending": False}


def test_monitor_callback_and_event_fire_once():
    events = obs.EventLog()
    mon = PreemptionMonitor(grace_s=5.0, events=events)
    fired = []
    mon.add_callback(lambda m: fired.append(m.remaining()))
    mon.notice(source="test")
    mon.notice(source="test-again")  # no second event/callback
    assert len(fired) == 1
    names = [e["name"] for e in events.tail(8)]
    assert names.count("preemption_notice") == 1
    (ev,) = [e for e in events.tail(8) if e["name"] == "preemption_notice"]
    assert ev["level"] == "warn" and ev["source"] == "test"


def test_monitor_metadata_poller_fake_gce_shape():
    """The poller speaks the GCE maintenance-event shape: NONE/None =
    no event; anything else is a notice tagged with the event."""
    calls = {"n": 0}

    def fetch():
        calls["n"] += 1
        return None if calls["n"] < 3 else "TERMINATE_ON_HOST_MAINTENANCE"

    mon = PreemptionMonitor(grace_s=60.0)
    mon.start_metadata_poller(fetch, interval_s=0.01)
    deadline = time.monotonic() + 10
    while not mon.pending() and time.monotonic() < deadline:
        time.sleep(0.01)
    assert mon.pending()
    assert mon.state()["source"] == (
        "metadata:TERMINATE_ON_HOST_MAINTENANCE"
    )
    mon.stop_metadata_poller()


def test_monitor_sigterm_records_notice_without_exiting():
    mon = get_monitor(grace_s=3600.0)
    assert mon.install_sigterm()
    try:
        signal.raise_signal(signal.SIGTERM)
        # Still here: the handler recorded, it did not exit.
        assert mon.pending()
        assert mon.state()["source"] == "sigterm"
    finally:
        mon.uninstall_sigterm()


def test_singleton_peek_never_creates():
    assert peek_state() is None  # _fresh_monitor reset it
    m = get_monitor(grace_s=12.0)
    m.notice(source="x")
    assert peek_state()["pending"] is True
    assert get_monitor() is m


# ---------------------------------------------------------------------------
# The `preempt` fault action
# ---------------------------------------------------------------------------
def test_fault_action_preempt_notices_monitor_with_grace():
    inj = FaultInjector.parse(
        [{"point": "fold_boundary", "action": "preempt",
          "seconds": 3600.0}]
    )
    inj.hit("fold_boundary")
    st = peek_state()
    assert st and st["pending"] and st["source"] == "fault"
    assert 0 < st["remaining_s"] <= 3600.0
    # One-shot like every rule; the calling thread was not blocked.
    (rule,) = inj.describe()
    assert rule["fired"] is True


def test_fault_action_preempt_rejected_points_still_validated():
    with pytest.raises(ValueError, match="unknown fault action"):
        FaultInjector.parse([{"point": "fold_boundary", "action": "pre"}])


# ---------------------------------------------------------------------------
# Engine: cross-replica KV handoff (export -> import -> warm hit)
# ---------------------------------------------------------------------------
def _engine(params, **kw):
    from ray_lightning_tpu.serve.engine import DecodeEngine

    base = dict(
        num_slots=2, max_seq=64, prefill_chunk=4,
        prefix_blocks=8, prefix_block=4,
    )
    base.update(kw)
    return DecodeEngine(params, PT_CFG, **base)


def _run_one(sched, prompt, **sampling):
    from ray_lightning_tpu.serve.scheduler import SamplingParams

    rid = sched.submit(prompt, SamplingParams(**sampling))
    return [
        e.token for e in sched.run_until_idle()
        if e.request_id == rid and e.token is not None
    ]


def test_engine_export_import_gives_survivor_warm_hit(pt_params):
    """The first real cross-replica KV handoff: engine A serializes a
    request's cached prefix (digest-keyed, the PR 10 payload form),
    engine B imports it, and B's admission walk hits device-warm —
    with output still bit-identical to an uninterrupted engine."""
    from ray_lightning_tpu.serve.scheduler import Scheduler

    rng = np.random.default_rng(7)
    prompt = rng.integers(0, 97, size=14).tolist()

    a = _engine(pt_params)
    sa = Scheduler(a)
    out_a = _run_one(sa, prompt, max_new_tokens=6, seed=3)
    blocks = a.export_prefix_blocks(prompt)
    assert len(blocks) == 3  # 14 tokens / block 4 = 3 full blocks
    assert a.prefix_handoff_exports == 3
    # Wire-shaped: hex digests + host payloads (np arrays single-device).
    for hexd, kp, vp in blocks:
        bytes.fromhex(hexd)
        assert np.asarray(kp).shape == np.asarray(vp).shape

    b = _engine(pt_params)
    sb = Scheduler(b)
    # Through the scheduler's queue (the RPC-side path): applied at the
    # top of the next step — an IDLE loop still has work to do.
    assert sb.enqueue_prefix_import(blocks) == 3
    assert sb.has_work()
    sb.step()
    assert b.prefix_handoff_imports == 3
    out_b = _run_one(sb, prompt, max_new_tokens=6, seed=3)
    assert out_b == out_a  # exactness survives the handoff
    # Warm: the admission walk served prompt tokens from the imported
    # blocks (cap keeps the final chunk, so 2 of 3 blocks seed).
    assert b.prefix_hit_tokens >= 8
    assert b.tier_counters["device"]["hits"] >= 2
    # Idempotent re-import: already-pooled digests are touched, not
    # rewritten.
    assert b.import_prefix_blocks(blocks) == 3


def test_engine_import_falls_back_to_host_tier_when_pool_pinned(pt_params):
    """With no allocatable device block, imports land in the host tier
    (still one promotion from warm) instead of being dropped."""
    a = _engine(pt_params)
    from ray_lightning_tpu.serve.scheduler import Scheduler

    rng = np.random.default_rng(1)
    prompt = rng.integers(0, 97, size=14).tolist()
    _run_one(Scheduler(a), prompt, max_new_tokens=4)
    blocks = a.export_prefix_blocks(prompt)
    b = _engine(pt_params, prefix_blocks=2, prefix_host_mb=8.0)
    # Pin both pool blocks so _pool_alloc returns None.
    from ray_lightning_tpu.serve.engine import _PoolBlock

    for i in range(2):
        b._pool_free.remove(i)
        b._pool_map[bytes([i])] = i
        b._pool_meta[i] = _PoolBlock(digest=bytes([i]), refs=1, stamp=i)
    assert b.import_prefix_blocks(blocks) == len(blocks)
    for hexd, _, _ in blocks:
        assert bytes.fromhex(hexd) in b._host_map


# ---------------------------------------------------------------------------
# Scheduler drain plan
# ---------------------------------------------------------------------------
def test_scheduler_drain_finish_vs_migrate_and_queue(pt_params):
    """A huge budget keeps residents (their completion estimate fits in
    half the window) but still migrates the queue; a zero budget
    migrates everything — cancelled at the same step's boundary, with
    exported prefix blocks riding the plan."""
    from ray_lightning_tpu.serve.scheduler import SamplingParams, Scheduler

    rng = np.random.default_rng(0)
    eng = _engine(pt_params)
    sched = Scheduler(eng, max_prefills_per_step=2)
    prompts = [rng.integers(0, 97, size=14).tolist() for _ in range(3)]
    rids = [
        sched.submit(p, SamplingParams(max_new_tokens=20, seed=i))
        for i, p in enumerate(prompts)
    ]
    for _ in range(8):  # residents decoding, third request queued
        sched.step()
    assert eng.num_active == 2 and sched.queue_depth() == 1

    sched.request_drain(10 ** 6)
    assert sched.has_work()
    sched.step()
    plan = sched.drain_result(timeout=5.0)
    assert plan is not None and plan["budget_s"] == 10 ** 6
    assert sorted(plan["finish"]) == sorted(rids[:2])
    assert [m["request_id"] for m in plan["migrate"]] == [rids[2]]
    # The queued request never prefilled: nothing cached to export.
    assert plan["migrate"][0]["blocks"] == []
    assert eng.num_active == 2  # finishers keep their slots
    events = sched.run_until_idle()
    done = {
        e.request_id for e in events if e.done and e.reason == "finished"
    }
    assert set(rids[:2]) <= done  # the finish set really finished

    # Zero budget: everything migrates, with warm blocks for the
    # residents whose prefills completed.
    sched2 = Scheduler(_engine(pt_params), max_prefills_per_step=2)
    rids2 = [
        sched2.submit(p, SamplingParams(max_new_tokens=20, seed=i))
        for i, p in enumerate(prompts[:2])
    ]
    for _ in range(8):
        sched2.step()
    sched2.request_drain(0.0)
    step_events = sched2.step()
    plan2 = sched2.drain_result(timeout=5.0)
    assert sorted(m["request_id"] for m in plan2["migrate"]) == sorted(
        rids2
    )
    for m in plan2["migrate"]:
        assert len(m["blocks"]) == 3  # 14-token prompts, block 4
    assert plan2["finish"] == []
    # Evicted at THIS step's boundary: slots free, and the terminal
    # events read "migrated" (not "cancelled") so a client streaming
    # them keeps the stream open across the re-route.
    assert sched2.engine.num_active == 0
    migrated = {
        e.request_id for e in step_events
        if e.done and e.reason == "migrated"
    }
    assert migrated == set(rids2)


def test_zero_budget_drain_resumes_on_a_survivor_bit_exact(pt_params):
    """Drain and handoff end to end over two real engines, no fabric:
    requests cut off mid-decode by a zero-budget drain are resubmitted
    on a survivor under the same id and seed, after the plan's blocks
    were imported there — none is lost, every stream is the
    uninterrupted engine's token for token, and the survivor's admission
    walk is warm."""
    from ray_lightning_tpu.serve.scheduler import SamplingParams, Scheduler

    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 97, size=14).tolist() for _ in range(2)]
    oracle = Scheduler(_engine(pt_params), max_prefills_per_step=2)
    expected = [
        _run_one(oracle, p, max_new_tokens=20, seed=i)
        for i, p in enumerate(prompts)
    ]

    dying = Scheduler(_engine(pt_params), max_prefills_per_step=2)
    rids = [
        dying.submit(p, SamplingParams(max_new_tokens=20, seed=i))
        for i, p in enumerate(prompts)
    ]
    streamed = {rid: [] for rid in rids}
    for _ in range(8):  # both residents are decoding when the notice lands
        for e in dying.step():
            if e.token is not None:
                streamed[e.request_id].append(e.token)
    assert all(0 < len(t) < 20 for t in streamed.values()), streamed
    dying.request_drain(0.0)
    dying.step()
    plan = dying.drain_result(timeout=5.0)
    assert sorted(m["request_id"] for m in plan["migrate"]) == sorted(rids)
    assert plan["finish"] == []

    survivor = Scheduler(_engine(pt_params), max_prefills_per_step=2)
    handed_off = sum(
        survivor.enqueue_prefix_import(m["blocks"]) for m in plan["migrate"]
    )
    assert handed_off >= 2 * 3  # 14-token prompts, block 4
    for i, (rid, p) in enumerate(zip(rids, prompts)):
        survivor.submit(
            p, SamplingParams(max_new_tokens=20, seed=i), request_id=rid
        )
    resumed = {rid: [] for rid in rids}
    for e in survivor.run_until_idle():
        if e.token is not None:
            resumed[e.request_id].append(e.token)
    for rid, want in zip(rids, expected):
        assert resumed[rid] == want  # nothing lost, nothing changed
        # What the client had streamed before the cut is a prefix of it
        # (its cursor dedups those on the re-route).
        assert want[: len(streamed[rid])] == streamed[rid]
    assert survivor.engine.prefix_handoff_imports == handed_off
    assert survivor.engine.prefix_hit_tokens >= 2 * 8


# ---------------------------------------------------------------------------
# ServeClient preempt_drain (fake replicas — no fabric processes)
# ---------------------------------------------------------------------------
class _RemoteShim:
    def __init__(self, fn):
        self._fn = fn

    def remote(self, *args, **kwargs):
        return self._fn(*args, **kwargs)


class _FakeReplica:
    """The client-facing surface preempt_drain touches, with a
    deterministic token function (seed-chained like the real engine)."""

    def __init__(self, burst=4):
        self.dead = False
        self.burst = burst
        self.submits = []
        self.requests = {}
        self.imported = []
        self.drain_plan = None

    @staticmethod
    def tokens_for(prompt, seed, n):
        return [(sum(prompt) + 7 * seed + i) % 97 for i in range(n)]

    def _check(self):
        if self.dead:
            raise fabric.ActorDiedError("fake replica dead")

    def _rpc_submit(self, prompt, request_id=None, **kw):
        self._check()
        self.submits.append((request_id, dict(kw)))
        self.requests[request_id] = self.tokens_for(
            prompt, kw.get("seed", 0), kw.get("max_new_tokens", 32)
        )
        return request_id

    def _rpc_result(self, rid, cursor, wait_s=0.0):
        self._check()
        toks = self.requests[rid]
        out = toks[cursor: cursor + self.burst]
        return {
            "tokens": out,
            "done": cursor + len(out) >= len(toks),
            "status": "finished",
        }

    def _rpc_begin_drain(self, budget_s=None, wait_s=15.0):
        self._check()
        assert self.drain_plan is not None, "no drain scripted"
        return self.drain_plan

    def _rpc_import_prefix_blocks(self, blocks):
        self._check()
        self.imported.append(blocks)
        return len(blocks)

    def _rpc_stop(self):
        self._check()

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


def test_client_preempt_drain_migrates_with_kv_and_keeps_finishers(
    start_fabric,
):
    """The drain's client half: the migrate set is resubmitted onto the
    survivor under the same id (blocks imported FIRST, so the admission
    walk there is warm), the finish set stays routed to the dying
    replica, and counters/events tell the story."""
    start_fabric(num_cpus=1)
    r0, r1 = _FakeReplica(), _FakeReplica()
    client, reg, events = _client([r0, r1])
    prompt = [3, 1, 4, 1, 5]
    h_fin = client.submit(prompt, max_new_tokens=6, seed=1, replica=0)
    h_mig = client.submit(prompt, max_new_tokens=9, seed=2, replica=0)
    blocks = [("ab" * 16, np.zeros(2), np.zeros(2))]
    r0.drain_plan = {
        "budget_s": 10.0,
        "finish": [h_fin.request_id],
        "migrate": [
            {"request_id": h_mig.request_id, "blocks": blocks},
        ],
    }
    res = client.preempt_drain(0, budget_s=10.0)
    assert res["migrated"] == [h_mig.request_id]
    assert res["finish"] == [h_fin.request_id]
    assert res["lost"] == [] and res["kv_blocks"] == 1
    # Survivor got the blocks, then the verbatim journal resubmission
    # under the SAME id.
    assert len(r1.imported) == 1
    (rid1, kw1) = r1.submits[0]
    assert rid1 == h_mig.request_id and kw1["seed"] == 2
    # Routing: migrated -> survivor; finisher still on the dying
    # replica; NEW traffic excluded from it.
    assert client.requests_on(0) == 1 and client.requests_on(1) == 1
    assert client.excluded() == [0]
    # Streams: both exact, the migrated one from the survivor.
    assert list(client.stream_handle(h_mig)) == _FakeReplica.tokens_for(
        prompt, 2, 9
    )
    assert list(client.stream_handle(h_fin)) == _FakeReplica.tokens_for(
        prompt, 1, 6
    )
    assert reg.counter("rlt_serve_preempt_drains_total").value() == 1
    assert reg.counter("rlt_serve_preempt_requests_total").value(
        outcome="migrated"
    ) == 1
    assert reg.counter("rlt_serve_preempt_requests_total").value(
        outcome="finished_in_grace"
    ) == 1
    assert reg.counter("rlt_serve_preempt_kv_blocks_total").value() == 1
    assert "preempt_drain" in [e["name"] for e in events.tail(16)]


def test_client_prespawn_replacement_swaps_in_on_respawn(start_fabric):
    start_fabric(num_cpus=1)
    r0, r1 = _FakeReplica(), _FakeReplica()
    spawned = []

    def respawn_fn(i):
        fresh = _FakeReplica()
        spawned.append(fresh)
        return fresh, []

    client, _, events = _client([r0, r1], respawn_fn=respawn_fn)
    assert client.prespawn_replacement(0) is True
    assert len(spawned) == 1
    assert client.prespawn_replacement(0) is True  # idempotent: held
    assert len(spawned) == 1
    client.respawn_replica(0)
    # The held replacement was swapped in — no second spawn.
    assert len(spawned) == 1
    assert client._actor(0) is spawned[0]
    assert "replica_prespawned" in [e["name"] for e in events.tail(16)]


# ---------------------------------------------------------------------------
# Supervisor PREEMPTING state machine (fake client, injectable clock)
# ---------------------------------------------------------------------------
class _FakeClient:
    def __init__(self, n=2):
        self.n = n
        self.verdicts = {i: "healthy" for i in range(n)}
        self.alive = {i: True for i in range(n)}
        self.preempt = {i: None for i in range(n)}
        self.routed = {i: 0 for i in range(n)}
        self.excluded = set()
        self.lost_calls = []
        self.respawn_calls = []
        self.prespawn_calls = []
        self.drain_calls = []
        self.drain_raises = None

    @property
    def num_replicas(self):
        return self.n

    def _actor(self, idx):
        return None

    def replica_is_alive(self, idx):
        return self.alive[idx]

    def replica_heartbeat_age(self, idx):
        return None

    def health_one(self, idx, timeout=None):
        if not self.alive[idx]:
            raise fabric.ActorDiedError("dead")
        rep = {"verdict": self.verdicts[idx],
               "healthy": self.verdicts[idx] == "healthy"}
        if self.preempt[idx] is not None:
            rep["preempt"] = self.preempt[idx]
        return rep

    def exclude(self, idx):
        self.excluded.add(idx)

    def restore(self, idx):
        self.excluded.discard(idx)

    def on_replica_lost(self, idx, reason=""):
        self.lost_calls.append((idx, reason))
        self.excluded.add(idx)
        return {"resubmitted": [], "lost": []}

    def can_respawn(self):
        return True

    def prespawn_replacement(self, idx):
        self.prespawn_calls.append(idx)
        return True

    def preempt_drain(self, idx, budget_s=None):
        self.drain_calls.append((idx, budget_s))
        if self.drain_raises is not None:
            raise self.drain_raises
        return {"finish": ["f1"], "migrated": ["m1", "m2"], "lost": [],
                "kv_blocks": 3}

    def requests_on(self, idx):
        return self.routed[idx]

    def respawn_replica(self, idx):
        self.respawn_calls.append(idx)
        self.alive[idx] = True
        self.verdicts[idx] = "healthy"
        self.preempt[idx] = None
        self.excluded.discard(idx)


def _supervisor(fake, clock, **kw):
    from ray_lightning_tpu.obs.registry import MetricsRegistry

    events = obs.EventLog()
    reg = MetricsRegistry()
    kw.setdefault("restart_backoff_s", 1.0)
    kw.setdefault("restart_limit", 3)
    sup = FleetSupervisor(
        fake, registry=reg, events=events, clock=clock, **kw
    )
    return sup, reg, events


def test_supervisor_preempting_drains_prespawns_then_replaces():
    fake = _FakeClient()
    now = {"t": 0.0}
    sup, reg, events = _supervisor(fake, lambda: now["t"])
    fake.preempt[0] = {"pending": True, "remaining_s": 20.0,
                       "source": "fault"}
    fake.routed[0] = 2
    sup.tick()
    row = sup.rows()[0]
    assert row["state"] == "preempting" and row["preemptions"] == 1
    assert fake.excluded == {0}
    assert fake.drain_calls == [(0, 20.0)]
    assert fake.prespawn_calls == [0]
    assert fake.respawn_calls == []  # in-grace finishers still draining
    names = [e["name"] for e in events.tail(16)]
    assert "replica_preempting" in names
    assert "replica_preempt_drained" in names
    assert reg.counter(
        "rlt_fleet_replica_preemptions_total"
    ).value(replica=0) == 1
    assert reg.gauge("rlt_fleet_replica_state").value(replica=0) == 5.0
    # Finishers still streaming, deadline not reached: hold.
    now["t"] = 5.0
    sup.tick()
    assert fake.respawn_calls == []
    # Drained to zero: the replacement swaps in, no failover needed.
    fake.routed[0] = 0
    now["t"] = 6.0
    sup.tick()
    assert fake.respawn_calls == [0]
    assert fake.lost_calls == []
    row = sup.rows()[0]
    assert row["state"] == "healthy" and row["restarts"] == 1
    assert "replica_preempt_replaced" in [
        e["name"] for e in events.tail(16)
    ]


def test_supervisor_preempt_deadline_fails_over_leftovers():
    """Requests the grace window caught mid-stream fail over like a
    crash (journal replay), then the replacement swaps in anyway."""
    fake = _FakeClient()
    now = {"t": 0.0}
    sup, _, _ = _supervisor(fake, lambda: now["t"])
    fake.preempt[0] = {"pending": True, "remaining_s": 3.0,
                       "source": "sigterm"}
    fake.routed[0] = 2
    sup.tick()
    now["t"] = 2.0
    sup.tick()
    assert fake.respawn_calls == []  # inside the window, still open
    now["t"] = 4.0  # deadline passed with requests still routed
    sup.tick()
    assert fake.lost_calls and fake.lost_calls[0][0] == 0
    assert "grace expired" in fake.lost_calls[0][1]
    assert fake.respawn_calls == [0]
    assert sup.rows()[0]["state"] == "healthy"


def test_supervisor_preempt_early_death_degrades_to_crash_semantics():
    """A preempting replica that dies before the deadline (reclamation
    came early) fails over immediately — never worse than PR 11."""
    fake = _FakeClient()
    now = {"t": 0.0}
    sup, _, _ = _supervisor(fake, lambda: now["t"])
    fake.preempt[0] = {"pending": True, "remaining_s": 30.0,
                       "source": "fault"}
    fake.routed[0] = 1
    sup.tick()
    fake.alive[0] = False
    now["t"] = 1.0
    sup.tick()
    assert fake.lost_calls and "died in grace" in fake.lost_calls[0][1]
    assert fake.respawn_calls == [0]


def test_supervisor_gang_follower_preempt_drains_the_whole_gang():
    """A follower's heartbeat carrying a pending preemption dooms its
    gang: same PREEMPTING path, gang respawned as a unit."""
    fake = _FakeClient()
    follower_state = {"pending": True, "remaining_s": 15.0,
                      "source": "fault"}
    fake.gang_preempt_state = (
        lambda idx: follower_state if idx == 0 else None
    )
    now = {"t": 0.0}
    sup, _, events = _supervisor(fake, lambda: now["t"])
    sup.tick()
    assert sup.rows()[0]["state"] == "preempting"
    assert sup.rows()[1]["state"] == "healthy"
    assert fake.drain_calls == [(0, 15.0)]
    (ev,) = [
        e for e in events.tail(16) if e["name"] == "replica_preempting"
    ]
    assert ev["member"] == "follower"
    fake.routed[0] = 0
    now["t"] = 1.0
    sup.tick()
    assert fake.respawn_calls == [0]


# ---------------------------------------------------------------------------
# Fabric worker: terminating heartbeat
# ---------------------------------------------------------------------------
def test_worker_sigterm_pushes_terminating_heartbeat(monkeypatch):
    from ray_lightning_tpu.fabric import worker

    sent = []
    monkeypatch.setattr(worker, "_EXITING", False)
    monkeypatch.setattr(
        worker, "_TERM_NOTIFY", lambda: sent.append(True)
    )
    with pytest.raises(SystemExit):
        worker._on_sigterm()
    assert sent == [True]
    assert worker._EXITING is True
    # Re-entry (kill()'s follow-up SIGTERM) is a no-op: no second push.
    worker._on_sigterm()
    assert sent == [True]
    monkeypatch.setattr(worker, "_EXITING", False)


@pytest.mark.slow
def test_worker_heartbeat_carries_preempt_state(start_fabric):
    """End to end through a real worker process (slow tier — spawns an
    actor with a fast heartbeat): a preempt-armed follower-shaped
    actor's heartbeat shows the pending notice, and a SIGTERM'd worker
    leaves a worker_terminating event (clean terminate, not a flatline)
    in the driver's ring."""

    class _Idle:
        def ping(self):
            return "ok"

        def preempt(self):
            from ray_lightning_tpu.serve.preempt import get_monitor

            get_monitor().notice(grace_s=3600.0, source="fault")
            return True

    start_fabric(num_cpus=1)
    actor = fabric.remote(_Idle).options(
        num_cpus=1, env={"RLT_HEARTBEAT_S": "0.2"}
    ).remote()
    fabric.get(actor.ping.remote(), timeout=60)
    fabric.get(actor.preempt.remote(), timeout=30)
    deadline = time.monotonic() + 20
    entry = None
    while time.monotonic() < deadline:
        entry = fabric.heartbeats().get(actor.actor_id)
        if entry and entry.get("preempt"):
            break
        time.sleep(0.05)
    assert entry and entry["preempt"]["pending"] is True
    assert entry["preempt"]["source"] == "fault"
    # A raw SIGTERM (no shutdown message — the reclamation shape, not a
    # fabric kill): the worker's handler pushes its final terminating
    # heartbeat before exiting, and the driver classifies the death as
    # a clean terminate instead of a flatline.
    os.kill(int(entry["pid"]), signal.SIGTERM)
    deadline = time.monotonic() + 20
    while time.monotonic() < deadline:
        names = [
            (e["name"], e.get("actor")) for e in obs.get_event_log().tail(64)
        ]
        if ("worker_terminating", actor.actor_id) in names:
            break
        time.sleep(0.05)
    else:
        pytest.fail("no worker_terminating event after SIGTERM")
    try:
        fabric.kill(actor)
    except Exception:  # noqa: BLE001 - already exiting
        pass


# ---------------------------------------------------------------------------
# Trainer: checkpoint-on-notice + bit-exact resume
# ---------------------------------------------------------------------------
def _det_module(n=256, batch_size=4):
    import jax.numpy as jnp
    import optax

    from ray_lightning_tpu.trainer.data import ArrayDataset, DataLoader
    from ray_lightning_tpu.trainer.module import TPUModule

    class M(TPUModule):
        def __init__(self):
            super().__init__()
            g = np.random.default_rng(0)
            self.x = g.standard_normal((n, 3)).astype(np.float32)
            self.y = self.x @ np.array([1.0, -2.0, 0.5], np.float32)
            self.batch_size = batch_size

        def init_params(self, rng, batch):
            return {"w": jnp.zeros((3,))}

        def training_step(self, params, batch, rng):
            bx, by = batch
            loss = ((bx @ params["w"] - by) ** 2).mean()
            return loss, {"loss": loss}

        def configure_optimizers(self):
            return optax.adam(1e-2)

        def train_dataloader(self):
            return DataLoader(
                ArrayDataset(self.x, self.y), batch_size=self.batch_size
            )

    return M()


class _NoticeAt:
    """Callback: record a preemption notice once global_step reaches
    ``at`` (the loop's checkpoint-on-notice fires at that chunk
    boundary)."""

    def __init__(self, at):
        self.at = at
        self.fired = False

    def __getattr__(self, name):
        if name.startswith("on_"):
            return lambda *a, **k: None
        raise AttributeError(name)

    def on_train_batch_end(self, trainer, module, logs, batch_idx):
        if not self.fired and trainer.global_step >= self.at:
            self.fired = True
            get_monitor().notice(grace_s=3600.0, source="test")

    def state_dict(self):
        return {}

    def load_state_dict(self, state):
        pass


def _fit_kwargs(tmp_path, **kw):
    base = dict(
        max_epochs=2,
        seed=0,
        num_sanity_val_steps=0,
        check_val_every_n_epoch=10 ** 9,
        log_every_n_steps=1,
        default_root_dir=str(tmp_path),
        accumulate_grad_batches=2,
    )
    base.update(kw)
    return base


def test_trainer_preempt_checkpoint_resume_bit_exact(tmp_path):
    """Checkpoint-on-notice: a preemption mid-epoch saves a validated
    checkpoint at the step boundary, the fit exits cleanly, and
    max_restarts resumes it BIT-EXACTLY (continue-the-epoch at the next
    batch, partial grad-accumulation window kept) — final params
    identical to an uninterrupted run, zero steps lost."""
    from ray_lightning_tpu.trainer import Trainer

    base_dir = tmp_path / "base"
    m_base = _det_module()
    Trainer(**_fit_kwargs(base_dir)).fit(m_base)
    base_w = np.asarray(m_base.params["w"])

    pre_dir = tmp_path / "pre"
    m_pre = _det_module()
    t = Trainer(
        **_fit_kwargs(pre_dir),
        max_restarts=1,
        callbacks=[_NoticeAt(3)],
    )
    with pytest.warns(RuntimeWarning, match="fit preempted"):
        t.fit(m_pre)
    pre_w = np.asarray(m_pre.params["w"])
    assert np.array_equal(pre_w, base_w)
    # Zero steps lost: 256 samples / (4 * 8 virtual devices) = 8
    # batches per epoch, 2 epochs — same count as the uninterrupted run.
    assert t.global_step == 16
    # The preempt checkpoint exists, is named into the last* resume
    # group, and carries the exact epoch position.
    ckpts = [
        f for f in os.listdir(pre_dir / "checkpoints")
        if f.startswith("last-preempt-step")
    ]
    assert ckpts, os.listdir(pre_dir / "checkpoints")
    from ray_lightning_tpu.utils.state_stream import load_state_stream

    with open(pre_dir / "checkpoints" / ckpts[0], "rb") as f:
        state = load_state_stream(f.read())
    assert state["resume_batch"] >= 1
    assert state["mid_epoch"] is True
    assert state["global_step"] == state["resume_batch"]


def test_trainer_preempt_restart_observability(tmp_path):
    """The satellite: fit_restarting/fit_resume typed events + the
    rlt_train_fit_restarts_total counter — training recoveries visible
    in /events exactly like serving recoveries."""
    from ray_lightning_tpu.obs.events import get_event_log
    from ray_lightning_tpu.obs.registry import get_registry
    from ray_lightning_tpu.trainer import Trainer

    counter = get_registry().counter("rlt_train_fit_restarts_total")
    before = counter.value(cause="preempted")
    m = _det_module()
    t = Trainer(
        **_fit_kwargs(tmp_path), max_restarts=1, callbacks=[_NoticeAt(2)]
    )
    with pytest.warns(RuntimeWarning, match="fit preempted"):
        t.fit(m)
    assert counter.value(cause="preempted") == before + 1
    tail = get_event_log().tail(256)
    restarts = [e for e in tail if e["name"] == "fit_restarting"]
    resumes = [e for e in tail if e["name"] == "fit_resume"]
    saves = [e for e in tail if e["name"] == "fit_preempt_checkpoint"]
    assert restarts and restarts[-1]["cause"] == "preempted"
    assert restarts[-1]["level"] == "warn"
    assert resumes and "last-preempt-step" in resumes[-1]["ckpt"]
    assert saves and saves[-1]["step"] >= 2


def test_trainer_preempt_without_restarts_raises(tmp_path):
    """max_restarts=0: the preemption still checkpoints (the NEXT fit
    resumes from it) but the exception reaches the caller."""
    from ray_lightning_tpu.trainer import Trainer
    from ray_lightning_tpu.trainer.loop import TrainingPreempted

    m = _det_module()
    t = Trainer(**_fit_kwargs(tmp_path), callbacks=[_NoticeAt(2)])
    with pytest.raises(TrainingPreempted) as exc_info:
        t.fit(m)
    assert os.path.exists(exc_info.value.ckpt_path)


# ---------------------------------------------------------------------------
# End to end (slow): injected preemption under load -> graceful drain
# ---------------------------------------------------------------------------
def _write_ckpt(tmp_path, params):
    import dataclasses

    from ray_lightning_tpu.utils.state_stream import (
        state_stream_to_file,
        to_state_stream,
    )

    path = os.path.join(tmp_path, "pt.ckpt")
    state_stream_to_file(
        to_state_stream(
            {"params": params, "gpt_config": dataclasses.asdict(PT_CFG)}
        ),
        path,
    )
    return path


def _baseline(params, engine_kw, jobs):
    from ray_lightning_tpu.serve.engine import DecodeEngine
    from ray_lightning_tpu.serve.scheduler import SamplingParams, Scheduler

    eng = DecodeEngine(params, PT_CFG, **engine_kw)
    sched = Scheduler(eng)
    out = []
    for prompt, sampling in jobs:
        rid = sched.submit(prompt, SamplingParams(**sampling))
        toks = [
            e.token for e in sched.run_until_idle()
            if e.request_id == rid and e.token is not None
        ]
        out.append(toks)
    return out


@pytest.mark.slow
def test_chaos_preempt_graceful_drain_bit_exact(
    start_fabric, tmp_path, pt_params
):
    """The acceptance path: 2 replicas under load, a `preempt` fault on
    one (grace window, then a hard kill at the deadline — a real
    reclamation shape). Slowed decode folds make the doomed replica's
    in-flight work provably unable to finish in grace, so the drain
    LIVE-MIGRATES it: zero requests lost, zero duplicated tokens, every
    stream bit-identical to an uninterrupted oracle, and the migrated
    requests land WARM prefix hits on the survivor via the exported KV
    blocks (the first cross-replica handoff). The pre-spawned
    replacement swaps in and serves bit-exact."""
    start_fabric(num_cpus=4)
    ckpt = _write_ckpt(tmp_path, pt_params)
    rng = np.random.default_rng(3)
    jobs = []
    for i in range(6):
        prompt = rng.integers(0, 97, size=12).tolist()
        sampling = {"max_new_tokens": 40, "seed": i}
        if i == 3:
            sampling["temperature"] = 0.8  # one seeded-sampled rider
        jobs.append((prompt, sampling))
    base_kw = dict(
        num_slots=2, max_seq=64, decode_fold=2, prefill_chunk=8,
        prefix_blocks=8, prefix_block=8,
    )
    expected = _baseline(pt_params, base_kw, jobs)

    from ray_lightning_tpu.serve.client import start_replicas

    client = start_replicas(
        2,
        ckpt_path=ckpt,
        env={"JAX_PLATFORMS": "cpu"},
        **base_kw,
    )
    sup = FleetSupervisor(
        client, interval_s=0.2, restart_backoff_s=0.2,
        restart_limit=3, probe_timeout_s=60.0,
    ).start()
    try:
        # The reclamation: notice at the 2nd fold boundary with an 8s
        # window (the hard kill honors it), plus 1s-per-fold delays so
        # the resident requests' completion estimate can NEVER fit half
        # the window — the drain must migrate, not wait.
        plan = [{"point": "fold_boundary", "action": "preempt",
                 "after": 2, "seconds": 8.0}]
        plan += [
            {"point": "fold_boundary", "action": "delay",
             "seconds": 1.0, "after": k}
            for k in range(3, 20)
        ]
        client.inject_fault(0, plan)
        handles = [client.submit(p, **s) for p, s in jobs]
        outs = [
            list(client.stream_handle(h, timeout_s=240)) for h in handles
        ]
        # Zero lost, zero duplicated, bit-identical — migrated ones
        # included (the cursor deduplicated the delivered prefix).
        assert outs == expected
        assert any(h.replica == 0 for h in handles)
        # The drain story is in the driver's ring: notice -> drain with
        # migrations -> replacement swap.
        deadline = time.monotonic() + 60
        drained = None
        while time.monotonic() < deadline:
            tail = obs.get_event_log().tail(512)
            drains = [
                e for e in tail if e["name"] == "replica_preempt_drained"
            ]
            if drains and any(
                e["name"] == "replica_preempt_replaced" for e in tail
            ):
                drained = drains[-1]
                break
            time.sleep(0.1)
        assert drained is not None, "drain/replace events never appeared"
        assert drained["migrated"] >= 2
        assert drained["lost"] == 0
        assert drained["kv_blocks"] >= 1  # warm handoff really shipped
        names = [e["name"] for e in obs.get_event_log().tail(512)]
        assert "replica_preempting" in names
        # Warm handoff landed: the survivor served migrated prompt
        # tokens from the imported blocks (all prompts are unique, so
        # its only possible prefix hits are the handed-off ones).
        kv = obs.get_registry().counter(
            "rlt_serve_preempt_kv_blocks_total"
        ).value()
        assert kv >= 1
        stats = client.stats()
        hit_tokens = sum(
            s.get("prefix", {}).get("hit_tokens", 0)
            for s in stats if not s.get("unreachable")
        )
        assert hit_tokens >= 8  # >= one 8-token block served warm
        # The replacement swapped in and serves bit-exact.
        row = sup.rows()[0]
        assert row["state"] == "healthy" and row["restarts"] >= 1
        h = client.submit(jobs[0][0], replica=0, **jobs[0][1])
        assert list(
            client.stream_handle(h, timeout_s=240)
        ) == expected[0]
    finally:
        sup.stop()
        client.shutdown()


@pytest.mark.slow
def test_chaos_preempt_blackout_beats_crash(
    start_fabric, tmp_path, pt_params
):
    """The headline property, measured make-before-break: from the
    moment the doomed replica actually DIES, how long until each of ITS
    streams delivers again? A crash's streams are mid-flight at death
    (positive blackout: detect -> resubmit -> re-decode); a NOTICED
    kill's streams were live-migrated or finished inside the grace
    window, so the death itself interrupts nobody — strictly smaller.
    The same 0.25s/fold delay fault slows the doomed replica in BOTH
    rounds (the stand-in for a big model whose folds take real time)."""
    start_fabric(num_cpus=4)
    ckpt = _write_ckpt(tmp_path, pt_params)
    rng = np.random.default_rng(5)
    jobs = [
        (rng.integers(0, 97, size=12).tolist(),
         {"max_new_tokens": 40, "seed": i})
        for i in range(6)
    ]
    base_kw = dict(
        num_slots=2, max_seq=64, decode_fold=2, prefill_chunk=8,
        prefix_blocks=8, prefix_block=8,
    )
    slow_folds = [
        {"point": "fold_boundary", "action": "delay",
         "seconds": 0.25, "after": k}
        for k in range(3, 40)
    ]

    from ray_lightning_tpu.serve.client import start_replicas

    def measure(plan, death_marker):
        client = start_replicas(
            2, ckpt_path=ckpt, env={"JAX_PLATFORMS": "cpu"}, **base_kw
        )
        sup = FleetSupervisor(
            client, interval_s=0.1, restart_backoff_s=0.2,
            restart_limit=3, probe_timeout_s=60.0,
        ).start()
        try:
            client.inject_fault(0, plan)
            t0 = time.time()
            handles = [client.submit(p, **s) for p, s in jobs]
            affected = [
                i for i, h in enumerate(handles) if h.replica == 0
            ]
            stamps = {i: [] for i in range(len(jobs))}
            outs = {}

            def pull(i, h):
                toks = []
                for t in client.stream_handle(h, timeout_s=240):
                    toks.append(t)
                    stamps[i].append(time.time())
                outs[i] = toks

            threads = [
                threading.Thread(target=pull, args=(i, h))
                for i, h in enumerate(handles)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=240)
            assert len(outs) == len(jobs), "a stream was lost"
            # The death marker may land after the streams finished (the
            # drain's whole point): wait for it.
            t_death = None
            deadline = time.monotonic() + 90
            while t_death is None and time.monotonic() < deadline:
                for ev in obs.get_event_log().tail(2048):
                    if (
                        ev.get("name") == death_marker
                        and ev.get("ts", 0) >= t0
                    ):
                        t_death = ev["ts"]
                        break
                if t_death is None:
                    time.sleep(0.05)
            assert t_death is not None, f"no {death_marker} event"
            blackout = 0.0
            for i in affected:
                after = [t for t in stamps[i] if t > t_death]
                if after:
                    blackout = max(blackout, after[0] - t_death)
            return blackout
        finally:
            sup.stop()
            client.shutdown()

    drain_blackout = measure(
        [{"point": "fold_boundary", "action": "preempt", "after": 2,
          "seconds": 8.0}] + slow_folds,
        "replica_preempt_replaced",
    )
    crash_blackout = measure(
        [{"point": "fold_boundary", "action": "kill", "after": 8}]
        + slow_folds,
        "replica_lost",
    )
    assert crash_blackout > 0.0
    assert drain_blackout < crash_blackout, (
        drain_blackout, crash_blackout,
    )


@pytest.mark.slow
def test_chaos_gang_follower_preempt_respawns_gang_as_unit(
    start_fabric, tmp_path, pt_params
):
    """ROADMAP 4b's death-handling slice: a `preempt` fault on ONE gang
    FOLLOWER (surfaced only through its fabric heartbeat — followers
    have no RPC surface) drains and respawns the whole gang as a unit,
    and the fresh rendezvous serves bit-exact."""
    start_fabric(num_cpus=6)
    ckpt = _write_ckpt(tmp_path, pt_params)
    rng = np.random.default_rng(9)
    jobs = [
        (rng.integers(0, 97, size=8).tolist(),
         {"max_new_tokens": 8, "seed": i})
        for i in range(4)
    ]
    base_kw = dict(num_slots=2, max_seq=64, prefill_buckets=[16],
                   decode_fold=2)
    expected = _baseline(pt_params, base_kw, jobs)

    from ray_lightning_tpu.serve.client import start_replicas

    client = start_replicas(
        2,
        hosts_per_replica=2,
        ckpt_path=ckpt,
        env={"JAX_PLATFORMS": "cpu", "RLT_HEARTBEAT_S": "0.5"},
        **base_kw,
    )
    sup = FleetSupervisor(
        client, interval_s=0.2, restart_backoff_s=0.2,
        restart_limit=3, probe_timeout_s=120.0,
    ).start()
    t_start = time.time()
    try:
        # Arm gang 0's follower: the notice fires at its next replayed
        # op and reaches the supervisor via the heartbeat plane.
        client.inject_follower_fault(
            0, 0,
            [{"point": "follower_op", "action": "preempt",
              "seconds": 30.0}],
        )
        handles = [client.submit(p, **s) for p, s in jobs]
        outs = [
            list(client.stream_handle(h, timeout_s=240)) for h in handles
        ]
        assert outs == expected
        # The supervisor saw the follower's notice and respawned the
        # gang as a unit.
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            row = sup.rows()[0] if sup.rows() else {}
            if row.get("restarts", 0) >= 1 and row.get(
                "state"
            ) == "healthy":
                break
            time.sleep(0.1)
        else:
            pytest.fail(f"gang never respawned: {sup.rows()}")
        # The event ring is process-global (earlier tests' recovery
        # events persist): only THIS run's events count.
        preemptings = [
            e for e in obs.get_event_log().tail(512)
            if e["name"] == "replica_preempting"
            and e.get("ts", 0) >= t_start
        ]
        assert preemptings, "no replica_preempting event this run"
        assert preemptings[-1]["member"] == "follower"
        # The fresh rendezvous serves bit-exact.
        h = client.submit(jobs[0][0], replica=0, **jobs[0][1])
        assert list(
            client.stream_handle(h, timeout_s=240)
        ) == expected[0]
    finally:
        sup.stop()
        client.shutdown()
