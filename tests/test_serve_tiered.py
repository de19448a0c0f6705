"""Tiered KV prefix cache tests: spill the block pool to host RAM/disk.

The load-bearing property is the same oracle every serve PR rides:
spilled and promoted blocks carry bit-identical K/V (a pure function of
the token prefix), so greedy output through ANY tier path — device hit,
host hit, disk hit, miss — matches solo ``gpt_generate`` and an
untiered engine token for token, and the compile count stays frozen at
construction (both transfer executables are lowered up front;
``compiles_since_init == 0`` with tiers on, measured by the real
compile listener). Asserted across {device, host, disk, miss} x
{chunked prefill, mid-prefill cancel + recycle} x {mesh off, 2x4 mesh},
plus the byte-budget ("oldest drops, never over budget") and
all-blocks-referenced admission edges and journal/replay tier fidelity.
"""
import numpy as np
import pytest

from ray_lightning_tpu.models.gpt import (
    GPTConfig,
    gpt_generate,
    init_gpt_params,
)

#: fp32 + reference attention: the exactness-contract config (MHA so a
#: model axis of 2 divides both head counts on the 2x4 mesh).
CFG = GPTConfig(
    vocab_size=97,
    n_layer=2,
    n_head=4,
    d_model=32,
    max_seq=64,
    attn_impl="reference",
    compute_dtype="float32",
)

#: Logical bytes of one K+V pool block at prefix_block=4 under CFG.
BLK_BYTES = 2 * CFG.n_layer * 4 * CFG.kv_head * CFG.head_dim * 4

#: The mesh the tier contracts must hold under (model=2 shards heads
#: and the pool two ways; data=4 exercises the replicated extra axis).
MESH_SHAPE = (2, 4)


def _mb(n_blocks: int) -> float:
    """A MiB budget holding exactly ``n_blocks`` pool blocks."""
    return n_blocks * BLK_BYTES / (1 << 20)


@pytest.fixture(scope="module")
def params():
    import jax

    return init_gpt_params(jax.random.PRNGKey(0), CFG)


@pytest.fixture(scope="module")
def tp_mesh():
    """A ("model", "data") mesh over the forced host devices; skips
    cleanly when conftest's virtual-device flag could not take effect."""
    import jax

    needed = MESH_SHAPE[0] * MESH_SHAPE[1]
    if len(jax.devices()) != needed:
        pytest.skip(
            f"needs {needed} devices "
            f"(xla_force_host_platform_device_count), have "
            f"{len(jax.devices())}"
        )
    from ray_lightning_tpu.parallel.mesh import build_mesh

    return build_mesh(MESH_SHAPE, ("model", "data"))


def _engine(params, mesh=None, **kw):
    from ray_lightning_tpu.serve.engine import DecodeEngine

    base = dict(
        num_slots=2, max_seq=64, prefill_buckets=[16], prefill_chunk=4,
        prefix_blocks=2, prefix_block=4, decode_fold=2,
    )
    base.update(kw)
    return DecodeEngine(params, CFG, mesh=mesh, **base)


_REF_MEMO = {}


def _reference(params, prompt, n):
    """Solo gpt_generate, memoized per (prompt, n): the exactness and
    mesh tests reference identical pairs, and one-shot generate
    compiles a whole scan per shape — cache the session's answers."""
    key = (tuple(prompt), n)
    if key not in _REF_MEMO:
        out = gpt_generate(
            params, CFG, np.asarray(prompt, np.int32)[None], n
        )
        _REF_MEMO[key] = np.asarray(out)[0].tolist()
    return _REF_MEMO[key]


def _drive_one(eng, prompt, n, rid):
    """Admit one request and drive the engine to idle; returns its
    tokens (chunked prefill interleaved with folds, scheduler-style)."""
    eng.admit(prompt, request_id=rid, max_new_tokens=n)
    out = []
    for _ in range(300):
        if not eng.num_active:
            break
        for _, task, tok, _ in eng.prefill_step(1):
            if task.request_id == rid:
                out.append(tok)
        for _, got_rid, tok, _ in eng.step():
            if got_rid == rid:
                out.append(tok)
    assert eng.num_active == 0
    return out


def _tier_workload(rng):
    """One request sequence that drives every tier path through a
    2-block device pool + 4-block host tier + disk tier: device hits
    (r1), host hits (r3), disk hits (r6), and an everything-miss (r7).
    Every prompt is exactly 2 full blocks (plus a partial), so inserts
    never allocate a third block and the cascade stays choreographed:
    A spills to host at r2, B cascades host->disk at r5."""
    pA = rng.integers(0, 97, size=10).tolist()
    pB = rng.integers(0, 97, size=10).tolist()
    pC = rng.integers(0, 97, size=10).tolist()
    pD = rng.integers(0, 97, size=10).tolist()
    pE = rng.integers(0, 97, size=10).tolist()
    return [
        ("r0", pA, 5),           # cold insert
        ("r1", pA + pD[:1], 4),  # device hit (A resident; no 3rd block)
        ("r2", pB, 5),           # insert; A spills to host
        ("r3", pA, 5),           # host hit -> promote A (B to host)
        ("r4", pC, 5),           # insert; host at budget {B, A}
        ("r5", pD, 5),           # insert; host overflows B to disk
        ("r6", pB, 5),           # disk hit -> promote B
        ("r7", pE, 5),           # miss through every tier
    ]


def _tier_kw(tmp_path, tag):
    """The tier config the exactness matrix runs: host budget of 4
    blocks over a 1-GiB disk tier — the workload above touches every
    tier through it."""
    return dict(
        prefix_host_mb=_mb(4),
        prefix_disk_dir=str(tmp_path / f"{tag}-disk"),
        prefix_disk_mb=1.0,
    )


def _run_workload(eng):
    rng = np.random.default_rng(7)
    return {
        rid: _drive_one(eng, p, n, rid)
        for rid, p, n in _tier_workload(rng)
    }


def test_tiered_exactness_and_frozen_compiles(params, tmp_path):
    """The acceptance oracle, single-device: one workload whose
    admissions hit the device pool, the host tier, and the disk tier
    (and miss all three) produces greedy output bit-identical to solo
    gpt_generate — the same oracle the untiered engine holds, so every
    tier path is transitively bit-identical to an untiered engine —
    with ZERO backend compiles in steady state, tiers on (the transfer
    executables were lowered at construction; measured by the real
    compile listener)."""
    from ray_lightning_tpu.obs.jaxmon import install_compile_listener

    stats = install_compile_listener()
    rng = np.random.default_rng(7)
    workload = _tier_workload(rng)

    eng = _engine(params, **_tier_kw(tmp_path, "1x1"))
    compiled = eng.compiled_count
    base = stats.count("backend_compile")
    outs = _run_workload(eng)
    assert stats.count("backend_compile") == base
    assert eng.compiled_count == compiled

    # Every tier path really ran.
    tc = eng.tier_counters
    assert tc["device"]["hits"] > 0, tc
    assert tc["host"]["hits"] > 0, tc
    assert tc["disk"]["hits"] > 0, tc
    assert tc["device"]["misses"] > 0, tc
    assert tc["device"]["spills"] > 0, tc
    assert tc["host"]["spills"] > 0, tc  # the host->disk cascade
    assert tc["host"]["promotions"] > 0, tc
    assert tc["disk"]["promotions"] > 0, tc
    assert eng.refill_s > 0.0

    # Bit-identical to solo generate (the untiered engine's own oracle).
    for rid, p, n in workload:
        assert p + outs[rid] == _reference(params, p, n), rid


def test_tiered_mid_prefill_cancel_and_recycle(params):
    """A request cancelled MID-PREFILL after its admission promoted
    host-tier blocks: the blocks stay in the device pool (unpinned),
    the slot recycles, and the next tenant's output is exact — the
    cancel path never corrupts tiered state."""
    # chunk=2 so the post-match suffix needs TWO chunks: one
    # prefill_step leaves the victim genuinely mid-prefill.
    eng = _engine(
        params, num_slots=2, prefill_chunk=2, prefix_blocks=4,
        prefix_host_mb=_mb(6),
    )
    rng = np.random.default_rng(11)
    pA = rng.integers(0, 97, size=16).tolist()
    pB = rng.integers(0, 97, size=16).tolist()
    assert _drive_one(eng, pA, 4, "warm") == _reference(
        params, pA, 4
    )[len(pA):]
    # Evict A's blocks into the host tier.
    _drive_one(eng, pB, 4, "evictor")
    # Re-admit A: admission promotes its blocks back, then cancel while
    # the chunked prefill is still in flight.
    slot, tok, done = eng.admit(pA, request_id="victim", max_new_tokens=8)
    assert tok is None and not done
    assert eng.tier_counters["host"]["promotions"] >= 3
    eng.prefill_step(1)  # advance one chunk of two, then abandon
    assert eng.num_prefilling == 1  # genuinely mid-prefill
    eng.release(slot)
    assert eng.num_active == 0
    # Promoted blocks must be unpinned and reusable, not leaked.
    for meta in eng._pool_meta:
        assert meta is None or meta.refs == 0
    # The recycled slot serves the same prefix exactly (device hit now).
    hits0 = eng.tier_counters["device"]["hits"]
    out = _drive_one(eng, pA, 6, "recycled")
    assert eng.tier_counters["device"]["hits"] > hits0
    assert pA + out == _reference(params, pA, 6)


def test_tiered_mesh_2x4_bit_identical_and_frozen_compiles(
    params, tp_mesh, tmp_path
):
    """The tier contracts under the 8-device CPU mesh (model=2 shards
    the pool): spill captures per-device shards, refill rebuilds the
    sharded block via make_array_from_callback, and the {device, host,
    disk, miss} workload stays bit-identical to single-device solo
    gpt_generate (the oracle the single-device tiered and untiered
    engines hold too) with zero steady-state compiles."""
    from ray_lightning_tpu.obs.jaxmon import install_compile_listener

    stats = install_compile_listener()
    rng = np.random.default_rng(7)
    workload = _tier_workload(rng)

    eng = _engine(params, tp_mesh, **_tier_kw(tmp_path, "mesh"))
    base = stats.count("backend_compile")
    sharded = _run_workload(eng)
    assert stats.count("backend_compile") == base
    tc = eng.tier_counters
    assert tc["host"]["hits"] > 0 and tc["host"]["promotions"] > 0, tc
    assert tc["disk"]["hits"] > 0 and tc["disk"]["promotions"] > 0, tc

    for rid, p, n in workload:
        assert p + sharded[rid] == _reference(params, p, n), rid


def test_host_and_disk_budgets_never_exceeded(params, tmp_path):
    """Byte budgets are hard: the host tier holds at most its budget
    (oldest block drops first), the disk tier holds at most its budget
    in MEASURED file bytes, and a cascade (device -> host -> disk ->
    dropped) preserves LRU order end to end."""
    disk_dir = tmp_path / "budget"
    eng = _engine(
        params,
        prefix_host_mb=_mb(2),
        prefix_disk_dir=str(disk_dir),
        # Disk holds ~2 blocks incl. npy/keys header overhead.
        prefix_disk_mb=(2 * BLK_BYTES + 4096) / (1 << 20),
    )
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 97, size=10).tolist() for _ in range(6)]
    for i, p in enumerate(prompts):
        _drive_one(eng, p, 3, f"r{i}")
        tiers = eng.prefix_tier_stats()
        assert tiers["host"]["bytes"] <= tiers["host"]["budget_bytes"]
        assert tiers["disk"]["bytes"] <= tiers["disk"]["budget_bytes"]
    # 6 prompts x 2 blocks through a 2-block pool: device holds the
    # newest 2 blocks, host the next oldest 2, disk the next 2, and the
    # oldest fell off the end (disk evictions > 0).
    digests = [
        tuple(eng._block_digests(np.asarray(p, np.int32))) for p in prompts
    ]
    assert all(d in eng._pool_map for d in digests[-1])
    assert all(d in eng._host_map for d in digests[-2])
    assert all(d in eng._disk_map for d in digests[-3])
    assert all(d not in eng._disk_map for d in digests[0])
    assert eng.tier_counters["disk"]["evictions"] > 0
    # Disk files on disk match the map exactly (no leaks).
    import os

    names = {
        n.split(".")[0]
        for n in os.listdir(disk_dir)
        if n.endswith(".npy")
    }
    assert names == {d.hex() for d in eng._disk_map}


def test_all_blocks_referenced_admission_proceeds_uncached(params):
    """The eviction edge: every pool block ref-counted by in-flight
    chunked prefills — a concurrent admission that completes its
    prefill must proceed UNCACHED (its insert finds no allocatable
    block): no deadlock, no spurious eviction of a referenced block,
    and every output stays exact."""
    eng = _engine(params, num_slots=3, prefix_host_mb=_mb(4))
    rng = np.random.default_rng(5)
    shared = rng.integers(0, 97, size=8).tolist()  # exactly 2 blocks
    # Seed the pool: both blocks inserted, pool full.
    out0 = _drive_one(eng, shared + [1, 2], 3, "seed")
    assert eng.prefix_stats()["blocks_used"] == 2
    inserts0 = eng.prefix_inserts
    # The uncached prompt is admitted FIRST (lowest slot — prefill_step
    # budget 1 advances the lowest prefilling slot, so it completes
    # while both pins are still mid-prefill), then two admissions
    # matching the shared prefix pin (ref-count) every pool block.
    fresh = rng.integers(0, 97, size=6).tolist()
    eng.admit(fresh, request_id="fresh", max_new_tokens=3)
    long1 = shared + rng.integers(0, 97, size=3).tolist()
    long2 = shared + rng.integers(0, 97, size=2).tolist()
    eng.admit(long1, request_id="pin1", max_new_tokens=3)
    eng.admit(long2, request_id="pin2", max_new_tokens=3)
    assert all(
        m is not None and m.refs == 2 for m in eng._pool_meta
    )
    outs = {"pin1": [], "pin2": [], "fresh": []}
    # Two budget-1 prefill steps complete "fresh" (6 tokens, chunk=4)
    # with both pins parked mid-prefill, refs held.
    for _ in range(2):
        for _, task, tok, _ in eng.prefill_step(1):
            outs[task.request_id].append(tok)
    assert outs["fresh"], "fresh prefill did not complete"
    # Its full-block insert found every block pinned: it proceeded
    # uncached — no eviction, no spill, no new insert, refs intact.
    assert eng.prefix_evictions == 0
    assert eng.tier_counters["device"]["spills"] == 0
    assert eng.prefix_inserts == inserts0
    assert all(m is not None and m.refs == 2 for m in eng._pool_meta)
    for _ in range(300):
        if not eng.num_active:
            break
        for _, task, tok, _ in eng.prefill_step(1):
            outs[task.request_id].append(tok)
        for _, rid, tok, _ in eng.step():
            outs[rid].append(tok)
    assert eng.num_active == 0  # no deadlock
    # Pins released their refs; the referenced blocks were never evicted.
    assert eng.prefix_evictions == 0
    assert eng.prefix_stats()["blocks_used"] == 2
    for m in eng._pool_meta:
        assert m is not None and m.refs == 0
    for rid, p in (("pin1", long1), ("pin2", long2), ("fresh", fresh)):
        assert p + outs[rid] == _reference(params, p, 3), rid
    assert (shared + [1, 2]) + out0 == _reference(params, shared + [1, 2], 3)


def test_disk_tier_round_trips_bfloat16(tmp_path):
    """Extension dtypes must survive the disk tier: np.save cannot
    round-trip bfloat16 (it comes back as raw void), so blocks are
    stored as canonical bytes and viewed back — a bf16 engine's disk
    hits stay bit-identical to an untiered bf16 engine (regression:
    the first disk hit used to throw 'Dtype |V2 is not a valid JAX
    array type')."""
    import jax

    from ray_lightning_tpu.serve.engine import DecodeEngine

    bcfg = GPTConfig(
        vocab_size=97, n_layer=2, n_head=4, d_model=32, max_seq=64,
        attn_impl="reference", compute_dtype="bfloat16",
    )
    bparams = init_gpt_params(jax.random.PRNGKey(0), bcfg)
    kw = dict(
        num_slots=2, max_seq=64, prefill_buckets=[16], prefill_chunk=4,
        prefix_blocks=2, prefix_block=4, decode_fold=2,
    )
    rng = np.random.default_rng(19)
    pA = rng.integers(0, 97, size=10).tolist()
    pB = rng.integers(0, 97, size=10).tolist()
    pC = rng.integers(0, 97, size=10).tolist()
    reqs = [
        ("r0", pA, 4), ("r1", pB, 4), ("r2", pC, 4),
        ("r3", pA, 4), ("r4", pB, 4),
    ]

    def run(eng):
        return {rid: _drive_one(eng, p, n, rid) for rid, p, n in reqs}

    tiered_eng = DecodeEngine(
        bparams, bcfg,
        prefix_disk_dir=str(tmp_path / "bf16"), prefix_disk_mb=1.0, **kw
    )
    tiered = run(tiered_eng)
    assert tiered_eng.tier_counters["disk"]["hits"] > 0
    assert tiered == run(DecodeEngine(bparams, bcfg, **kw))


def test_tier_knob_validation(params):
    from ray_lightning_tpu.serve.engine import DecodeEngine

    with pytest.raises(ValueError, match="prefix_blocks"):
        DecodeEngine(
            params, CFG, num_slots=1, max_seq=32, prefill_buckets=[16],
            prefix_blocks=0, prefix_host_mb=1.0,
        )
    with pytest.raises(ValueError, match=">= 0"):
        DecodeEngine(
            params, CFG, num_slots=1, max_seq=32, prefill_buckets=[16],
            prefix_blocks=2, prefix_host_mb=-1.0,
        )


def test_scheduler_exports_tier_metrics(params):
    """Scheduler-diffed tier counters land in the tier-labelled
    Prometheus series and the snapshot's prefix_tiers block (hit-rate-
    by-tier included) — the prefix-pool observability gap closed — and
    the prefix_seed trace span names where each seeded block came from
    (a host count > 0 is the observable signature of a promotion paid
    at admission)."""
    from ray_lightning_tpu.obs.registry import MetricsRegistry
    from ray_lightning_tpu.obs.trace import SPAN_PREFIX_SEED, RequestTracer
    from ray_lightning_tpu.serve.metrics import ServeMetrics
    from ray_lightning_tpu.serve.scheduler import SamplingParams, Scheduler

    eng = _engine(params, prefix_host_mb=_mb(2))
    reg = MetricsRegistry()
    tracer = RequestTracer(capacity=256)
    sched = Scheduler(
        eng, metrics=ServeMetrics(2, registry=reg), tracer=tracer
    )
    rng = np.random.default_rng(13)
    pA = rng.integers(0, 97, size=10).tolist()
    pB = rng.integers(0, 97, size=10).tolist()
    rids = []
    for p in (pA, pB, pA):  # insert, evict->host, host hit
        rids.append(sched.submit(p, SamplingParams(max_new_tokens=3)))
        sched.run_until_idle()
    # The host-hit admission's prefix_seed span carries tier counts.
    seeds = [
        ev for ev in tracer.trace(rids[-1])
        if ev["span"] == SPAN_PREFIX_SEED
    ]
    assert seeds, tracer.trace(rids[-1])
    tiers = seeds[0]["tiers"]
    assert tiers["host"] >= 1 and tiers["host"] + tiers["device"] == 2
    snap = sched.metrics.snapshot()
    tiers = snap["prefix_tiers"]
    assert tiers["host"]["hits"] > 0
    assert 0.0 < tiers["host"]["hit_rate"] <= 1.0
    text = reg.render()
    assert 'rlt_serve_prefix_hits_total{tier="host"}' in text
    assert 'rlt_serve_prefix_spills_total{tier="device"}' in text
    assert 'rlt_serve_prefix_bytes{tier="host"}' in text
    # The fleet row derives hit-rate-by-tier for rlt top.
    from ray_lightning_tpu.obs.fleet import summarize_replica

    row = summarize_replica(
        dict(snap, active_slots=0, prefix=eng.prefix_stats())
    )
    assert row["prefix_tier_hit_rate"]["host"] > 0.0


def test_journal_replay_rebuilds_tiers_and_replays_host_hit(params):
    """Journal/replay fidelity: the engine header records the tier
    knobs, build_replay_scheduler rebuilds the same tier config, and a
    captured session containing a host-tier hit replays BIT-EXACTLY —
    reproducing a host-tier hit on the replay side too."""
    from ray_lightning_tpu.obs.journal import (
        WorkloadJournal,
        build_replay_scheduler,
        engine_header,
        replay_journal,
    )
    from ray_lightning_tpu.serve.scheduler import SamplingParams, Scheduler

    eng = _engine(params, prefix_host_mb=_mb(2))
    journal = WorkloadJournal(capacity=256)
    journal.set_header(engine_header(eng))
    sched = Scheduler(eng, journal=journal)
    rng = np.random.default_rng(17)
    pA = rng.integers(0, 97, size=10).tolist()
    pB = rng.integers(0, 97, size=10).tolist()
    for p in (pA, pB, pA):  # insert, evict->host, host hit
        sched.submit(p, SamplingParams(max_new_tokens=4))
        sched.run_until_idle()
    assert eng.tier_counters["host"]["hits"] > 0
    dump = journal.dump()
    hdr = dump["header"]["engine"]
    assert hdr["prefix_host_mb"] == eng.prefix_host_mb
    assert hdr["prefix_disk_dir"] is None
    assert hdr["prefix_blocks"] == 2

    replay_sched = build_replay_scheduler(dump["header"], params=params)
    assert replay_sched.engine.prefix_host_mb == eng.prefix_host_mb
    assert replay_sched.engine.prefix_blocks == eng.prefix_blocks
    result = replay_journal(dump, scheduler=replay_sched)
    assert result["exact"], result["divergence"]
    assert result["compared"] == 3
    # The replay rebuilt and exercised the same tier machinery (virtual
    # replay interleaves admissions the capture ran sequentially, so
    # WHICH tier serves a block can differ — exactness cannot).
    assert replay_sched.engine.tier_counters["device"]["spills"] > 0


def test_tiers_serve_more_prompt_tokens_from_cache_than_the_pool_alone(
    params, tmp_path
):
    """A working set larger than the device pool (five 2-block prompts
    through a 2-block pool), tiers on against tiers off: the same
    requests, the same tokens, and more of the prompts' tokens served
    from cache — a spilled block survives the eviction that drops it
    from an untiered engine. Counts, not times."""
    tiered = _engine(params, **_tier_kw(tmp_path, "cmp"))
    plain = _engine(params)
    assert _run_workload(tiered) == _run_workload(plain)
    assert tiered.prefix_prompt_tokens == plain.prefix_prompt_tokens
    assert tiered.prefix_hit_tokens > plain.prefix_hit_tokens > 0
    cold = plain.tier_counters
    assert cold["host"]["hits"] == cold["disk"]["hits"] == 0, cold
    assert cold["host"]["spills"] == cold["disk"]["spills"] == 0, cold
