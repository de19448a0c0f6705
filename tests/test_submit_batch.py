"""Batched front door (PR18): ``submit_many`` / the micro-batching
window / vectorized ``plan_many`` / amortized digest chaining / the
batch observability — driven against the in-memory fake replicas from
test_router (the exact RPC surface the client touches).

The standing contracts these tests pin: batched submits are
semantically IDENTICAL to N serial submits (same journal records, same
ids/seeds, same typed rejections — so greedy streams stay bit-exact),
while the wire traffic collapses to ONE plan_many call and ONE
submit_many RPC per target replica.
"""
import threading

import pytest

from test_router import _FakeReplica, _StatsClient, _client, _router, _stats

from ray_lightning_tpu.serve.client import RequestHandle
from ray_lightning_tpu.serve.router import RequestRejectedError


# ---------------------------------------------------------------------------
# submit_many: bit-exact semantics + the journal invariant
# ---------------------------------------------------------------------------
def test_submit_many_bit_exact_and_journals_each_request(start_fabric):
    """One batched call behaves like N serial submits: every slot gets
    its own handle, streams the same deterministic tokens, and leaves
    one journal ``submit`` record (written before any RPC departed)."""
    start_fabric(num_cpus=1)
    r0, r1 = _FakeReplica(stats=_stats()), _FakeReplica(stats=_stats())
    client, reg, _ = _client([r0, r1])
    router, _ = _router(client)
    client.router = router
    prompts = [[3 + i, 1, 4, i] for i in range(6)]
    handles = client.submit_many(
        prompts, sampling=[{"seed": i} for i in range(6)],
        max_new_tokens=4,
    )
    assert all(isinstance(h, RequestHandle) for h in handles)
    for i, h in enumerate(handles):
        assert list(client.stream_handle(h)) == _FakeReplica.tokens_for(
            prompts[i], i, 4
        )
    subs = [
        e for e in client.journal.dump()["entries"]
        if e["kind"] == "submit"
    ]
    assert len(subs) == 6
    assert {tuple(e["prompt"]) for e in subs} == {
        tuple(p) for p in prompts
    }
    # Everything rode the batched wire: zero serial submit RPCs.
    assert r0.submit_rpcs == r1.submit_rpcs == 0
    assert r0.batch_rpcs + r1.batch_rpcs >= 1


def test_submit_many_one_plan_call_one_rpc_per_target(start_fabric):
    """The wire-amortization tentpole: a batch of N submits issues ONE
    vectorized plan_many call (never N serial plans) and ONE
    submit_many RPC per target replica — with the batch counters and
    the plan batch-size bucket recording it."""
    start_fabric(num_cpus=1)
    r0, r1 = _FakeReplica(stats=_stats()), _FakeReplica(stats=_stats())
    client, reg, _ = _client([r0, r1])
    router, rreg = _router(client)
    client.router = router
    plan_many_calls = []
    real_plan_many = router.plan_many
    router.plan_many = lambda *a, **kw: (
        plan_many_calls.append(1) or real_plan_many(*a, **kw)
    )
    router.plan = lambda *a, **kw: pytest.fail(
        "serial plan() on the batched path"
    )
    prompts = [[10 + i, 20 + i, 30 + i] for i in range(8)]
    handles = client.submit_many(prompts, max_new_tokens=2)
    assert all(isinstance(h, RequestHandle) for h in handles)
    assert len(plan_many_calls) == 1
    targets = {h.replica for h in handles}
    assert r0.batch_rpcs + r1.batch_rpcs == len(targets)
    assert r0.submit_rpcs == r1.submit_rpcs == 0
    # The flush counter: one batch, however many requests it carried.
    assert reg.counter(
        "rlt_serve_submit_batches_total"
    ).value() == 1
    # The planning batch-size histogram-as-counter: one 8-wide batch.
    assert rreg.counter(
        "rlt_router_plan_batch_size"
    ).value(bucket="8-31") == 1
    plan_rows = router.rows()["plan"]
    assert plan_rows["batches"] == 1
    assert plan_rows["requests"] == 8
    assert plan_rows["mean_batch"] == 8.0


def test_submit_many_isolates_rejected_slots(start_fabric):
    """Admission control stays per-request inside a batch: on a
    saturated fleet the low-priority slots come back as their own
    RequestRejectedError instances (journaled ``rejected`` outcomes,
    never raised) while their priority-0 batchmates stream normally."""
    start_fabric(num_cpus=1)
    sat = _stats(queue=20, active=2, slots=2)
    r0, r1 = _FakeReplica(stats=sat), _FakeReplica(stats=dict(sat))
    client, reg, _ = _client([r0, r1])
    router, _ = _router(client, shed_queue_factor=4.0)
    client.router = router
    prompts = [[i + 1] for i in range(4)]
    out = client.submit_many(
        prompts, sampling=[{"priority": i % 2} for i in range(4)],
        max_new_tokens=4,
    )
    assert isinstance(out[0], RequestHandle)
    assert isinstance(out[2], RequestHandle)
    for rej in (out[1], out[3]):
        assert isinstance(rej, RequestRejectedError)
        assert rej.reason == "saturated"
        assert rej.retry_after_s > 0
    # The placed slots stream bit-exact; the shed ones never left the
    # driver (2 of 4 prompts admitted fleet-wide).
    assert list(client.stream_handle(out[0])) == _FakeReplica.tokens_for(
        prompts[0], 0, 4
    )
    assert len(r0.submits) + len(r1.submits) == 2
    ent = client.journal.dump()["entries"]
    assert sum(1 for e in ent if e["kind"] == "submit") == 4
    assert sum(
        1 for e in ent
        if e["kind"] == "outcome" and e["outcome"] == "rejected"
    ) == 2


def test_submit_many_target_death_fails_over_bit_exact(start_fabric):
    """A whole target dying under its batched RPC fails its slice over
    through the journal: every request lands on the survivor under the
    same id/seed (bit-exact streams), no slot is lost, and the
    batchmates on the healthy target never notice."""
    start_fabric(num_cpus=1)
    r0, r1 = _FakeReplica(), _FakeReplica()
    client, reg, _ = _client([r0, r1])  # no router: round-robin ints
    r0.dead = True
    prompts = [[40 + i, 2, 7] for i in range(4)]
    out = client.submit_many(
        prompts, sampling=[{"seed": i} for i in range(4)],
        max_new_tokens=4,
    )
    assert all(isinstance(h, RequestHandle) for h in out)
    for i, h in enumerate(out):
        assert list(client.stream_handle(h)) == _FakeReplica.tokens_for(
            prompts[i], i, 4
        )
    # Every request (the failed-over half included) executed on r1.
    assert len(r1.submits) == 4 and len(r0.submits) == 0


# ---------------------------------------------------------------------------
# The opt-in micro-batching window (--serve.submit_batch_ms)
# ---------------------------------------------------------------------------
def test_submit_batch_window_coalesces_concurrent_submits(start_fabric):
    """With the window armed, concurrent serial submit() calls coalesce
    into shared flushes (all traffic rides submit_many — zero serial
    RPCs) while each caller still gets its own handle and bit-exact
    stream; a pinned submit bypasses the window (the pin is the
    placement, there is nothing to plan)."""
    start_fabric(num_cpus=1)
    r0, r1 = _FakeReplica(stats=_stats()), _FakeReplica(stats=_stats())
    client, reg, _ = _client([r0, r1], submit_batch_ms=80.0)
    router, _ = _router(client)
    client.router = router
    results = {}

    def go(i):
        h = client.submit([9, i], max_new_tokens=4, seed=i)
        results[i] = list(client.stream_handle(h))

    threads = [
        threading.Thread(target=go, args=(i,)) for i in range(6)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for i in range(6):
        assert results[i] == _FakeReplica.tokens_for([9, i], i, 4)
    assert r0.submit_rpcs == r1.submit_rpcs == 0
    batches = reg.counter("rlt_serve_submit_batches_total").value()
    assert 1 <= batches <= 6
    assert r0.batch_rpcs + r1.batch_rpcs >= batches
    # Pinned bypass: straight out the serial path, no window wait.
    h = client.submit([5, 5], replica=1, max_new_tokens=2, seed=0)
    assert h.replica == 1 and r1.submit_rpcs == 1


def test_submit_batch_window_isolates_rejections(start_fabric):
    """A shed request inside a window flush raises ITS caller's typed
    RequestRejectedError — the coalesced batchmates keep their
    handles (single-submit semantics through the batched spine)."""
    start_fabric(num_cpus=1)
    sat = _stats(queue=20, active=2, slots=2)
    client, reg, _ = _client(
        [_FakeReplica(stats=sat)], submit_batch_ms=80.0
    )
    router, _ = _router(client, shed_queue_factor=4.0)
    client.router = router
    outs = {}

    def go(i, prio):
        try:
            outs[i] = client.submit(
                [i + 1], max_new_tokens=4, priority=prio
            )
        except RequestRejectedError as exc:
            outs[i] = exc

    threads = [
        threading.Thread(target=go, args=(i, i % 2)) for i in range(2)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert isinstance(outs[0], RequestHandle)
    assert isinstance(outs[1], RequestRejectedError)
    assert outs[1].reason == "saturated"


# ---------------------------------------------------------------------------
# Amortized digest chaining: ONE chain per request, reused end to end
# ---------------------------------------------------------------------------
def test_digest_chain_computed_once_per_request(start_fabric):
    """The digest satellite: a routed submit computes its block-digest
    chain exactly ONCE (plan computes, observe_route reuses the passed
    chain — ``chains`` counts one walk per request), repeated prefixes
    replay out of the incremental cache (``blocks_reused`` grows while
    ``blocks_hashed`` stands still), and the batched path keeps the
    same one-chain-per-request arithmetic."""
    start_fabric(num_cpus=1)
    r0 = _FakeReplica(stats=_stats())
    client, reg, _ = _client([r0])
    router, _ = _router(client, prefix_block=4)
    client.router = router
    prompt = list(range(16))  # four full blocks
    client.submit(prompt, max_new_tokens=2)
    st = router.digest_cache.stats()
    assert st["chains"] == 1  # plan computed it; observe_route reused
    assert st["blocks_hashed"] >= 4
    hashed = st["blocks_hashed"]
    # Same prompt again: the chain replays from the cache.
    client.submit(prompt, max_new_tokens=2)
    st2 = router.digest_cache.stats()
    assert st2["chains"] == 2
    assert st2["blocks_hashed"] == hashed
    assert st2["blocks_reused"] > st["blocks_reused"]
    # Batched: still exactly one chain walk per request.
    client.submit_many(
        [list(range(k, k + 8)) for k in range(3)], max_new_tokens=2
    )
    assert router.digest_cache.stats()["chains"] == 5


# ---------------------------------------------------------------------------
# plan_many: vectorized == serial, validated inputs, bucket accounting
# ---------------------------------------------------------------------------
def test_plan_many_matches_serial_plans():
    """One vectorized pass must pick what N serial plan() calls pick
    (same weights, same affinity, same round-robin advance) and carry
    the same digest chains — the batched door may not re-route."""
    rows = [_stats(rate=50.0), _stats(rate=200.0), _stats()]
    prompts = [[i, i + 1, i + 2, i + 3, 9] for i in range(6)]
    serial_router, _ = _router(_StatsClient(rows), prefix_block=4)
    serial = [
        serial_router.plan(p, alive=[0, 1, 2]) for p in prompts
    ]
    batch_router, _ = _router(_StatsClient(rows), prefix_block=4)
    batched = batch_router.plan_many(prompts, alive=[0, 1, 2])
    assert [p.replica for p in batched] == [p.replica for p in serial]
    assert [p.digests for p in batched] == [p.digests for p in serial]
    # Per-request sequences must be index-aligned with the prompts.
    with pytest.raises(ValueError, match="per-request knob"):
        batch_router.plan_many(
            [[1], [2]], max_new_tokens=[4], alive=[0]
        )


def test_plan_batch_size_buckets_count_batches_not_requests():
    """rlt_router_plan_batch_size increments ONCE per planning call in
    the bucket of its width — the serial/batched mix is readable
    straight off the counter, and rows()['plan'] carries the totals."""
    router, reg = _router(_StatsClient([_stats(), _stats()]))
    router.plan([1, 2], alive=[0, 1])
    c = reg.counter("rlt_router_plan_batch_size")
    assert c.value(bucket="1") == 1
    router.plan_many([[i, i] for i in range(4)], alive=[0, 1])
    assert c.value(bucket="2-7") == 1
    router.plan_many([[i, i] for i in range(32)], alive=[0, 1])
    assert c.value(bucket="32-127") == 1
    plan = router.rows()["plan"]
    assert plan["batches"] == 3
    assert plan["requests"] == 37
    assert plan["mean_batch"] == round(37 / 3, 2)


# ---------------------------------------------------------------------------
# The replica's half of the batched wire: a real engine behind it
# ---------------------------------------------------------------------------
def test_replica_submit_many_admits_what_serial_submits_admit():
    """``ServeReplica.submit_many`` (the RPC the fakes above stand in
    for) against N serial ``submit`` calls of the same requests on one
    real replica: every request of either kind is admitted under the id
    the caller minted, none is lost, each stream is bit-identical to
    solo ``gpt_generate``, and serving both compiled nothing."""
    import jax
    import numpy as np

    from ray_lightning_tpu.models.gpt import (
        GPTConfig,
        gpt_generate,
        init_gpt_params,
    )
    from ray_lightning_tpu.serve.server import ServeReplica

    cfg = GPTConfig(
        vocab_size=97, n_layer=2, n_head=4, n_kv_head=2, d_model=32,
        max_seq=48, attn_impl="reference", compute_dtype="float32",
    )
    params = init_gpt_params(jax.random.PRNGKey(0), cfg)
    prompts = np.random.default_rng(18).integers(
        0, 97, size=(6, 10)
    ).tolist()
    n = 5
    # The references first: the replica counts every compile of the
    # process after its own construction.
    refs = [
        np.asarray(
            gpt_generate(params, cfg, np.asarray(p, np.int32)[None], n)
        )[0, len(p):].tolist()
        for p in prompts
    ]
    rep = ServeReplica(
        params=params, model_config=cfg, num_slots=2, max_seq=48,
        prefill_buckets=[16], decode_fold=2,
    )

    def stream(rid):
        tokens = []
        for _ in range(240):
            out = rep.result(rid, cursor=len(tokens), wait_s=0.5)
            tokens += out["tokens"]
            if out["done"]:
                assert out["status"] == "finished", out
                return tokens
        pytest.fail(f"{rid} did not finish")

    finished = 'rlt_serve_requests_total{kind="finished"}'
    try:
        # The replica reports the process's registry: count from here.
        before = rep.stats()["metrics"].get(finished, 0)
        serial = [
            rep.submit(p, max_new_tokens=n, request_id=f"s{i}")
            for i, p in enumerate(prompts)
        ]
        batched = rep.submit_many([
            {"prompt": p, "max_new_tokens": n, "request_id": f"b{i}"}
            for i, p in enumerate(prompts)
        ])
        assert serial == [f"s{i}" for i in range(6)]
        assert batched == [f"b{i}" for i in range(6)]
        for ref, s, b in zip(refs, serial, batched):
            assert stream(s) == stream(b) == ref
        stats = rep.stats()
        assert stats["compiles_since_init"] == 0
        assert stats["metrics"][finished] - before == 12
    finally:
        rep.stop()
