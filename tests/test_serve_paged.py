"""Paged KV tests: block-table attention over one refcounted page pool.

The load-bearing property is the strongest form of the serve oracle:
the paged attention paths gather pages back into the dense layout
in-graph and run the UNCHANGED dense math, so greedy output is
bit-identical to the dense engine (and solo ``gpt_generate``) by
construction — asserted across {chunked prefill, prefix hit/alias,
mid-prefill cancel + page recycle, spec=ngram, 2x4 mesh, tiered
spill/promote} with ``compiles_since_init == 0`` in steady state (page
tables mutate through one pre-lowered table-write executable). On top
ride the allocator edges: alias refcounts under cancel, every-page-
referenced backpressure that parks rather than deadlocks, the
export/import handoff carrying aliased pages, journal/replay config
fidelity, and the residency claim (>= 1.5x residents at a fixed HBM
token budget).
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

#: Logical bytes of one K+V page at kv_page=4 under CFG (tier budgets).
PAGE_BYTES = 2 * CFG.n_layer * 4 * CFG.kv_head * CFG.head_dim * 4

MESH_SHAPE = (2, 4)


def _mb(n_pages: int) -> float:
    return n_pages * PAGE_BYTES / (1 << 20)


@pytest.fixture(scope="module")
def params():
    import jax

    return init_gpt_params(jax.random.PRNGKey(0), CFG)


@pytest.fixture(scope="module")
def tp_mesh():
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


def _paged(params, mesh=None, **kw):
    from ray_lightning_tpu.serve.engine import DecodeEngine

    base = dict(
        num_slots=3, max_seq=64, prefill_buckets=[16], prefill_chunk=4,
        kv_page=4, kv_pages=32, decode_fold=2,
    )
    base.update(kw)
    return DecodeEngine(params, CFG, mesh=mesh, **base)


def _dense(params, **kw):
    from ray_lightning_tpu.serve.engine import DecodeEngine

    base = dict(
        num_slots=3, max_seq=64, prefill_buckets=[16], prefill_chunk=4,
        decode_fold=2,
    )
    base.update(kw)
    return DecodeEngine(params, CFG, **base)


_REF_MEMO = {}


def _reference(params, prompt, n):
    key = (tuple(prompt), n)
    if key not in _REF_MEMO:
        out = gpt_generate(
            params, CFG, np.asarray(prompt, np.int32)[None], n
        )
        _REF_MEMO[key] = np.asarray(out)[0].tolist()
    return _REF_MEMO[key]


def _drive_one(eng, prompt, n, rid):
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


def _workload(rng):
    """Cold inserts, alias hits (shared full pages), a long prompt, and
    a fresh miss — the alias/allocation paths a paged engine must hold
    exactness through."""
    pA = rng.integers(0, 97, size=10).tolist()  # 2 full pages + tail
    pB = rng.integers(0, 97, size=14).tolist()
    pC = rng.integers(0, 97, size=22).tolist()  # long: 5 pages + tail
    return [
        ("r0", pA, 5),            # cold insert
        ("r1", pA, 4),            # full-prefix alias (2 pages)
        ("r2", pA + pB[:3], 6),   # shared 2 pages, fresh suffix
        ("r3", pB, 5),            # cold insert
        ("r4", pC, 6),            # long prompt
        ("r5", pB + pC[:2], 4),   # alias pB's pages
    ]


def test_paged_exactness_and_frozen_compiles(params):
    """The acceptance oracle: a workload of cold inserts, copy-free
    alias hits, and long prompts produces greedy output bit-identical
    to solo gpt_generate (transitively: to the dense engine, which
    holds the same oracle) with ZERO backend compiles in steady state
    under paging, alias hits actually taken, and every page refcount
    released at idle."""
    from ray_lightning_tpu.obs.jaxmon import install_compile_listener

    stats = install_compile_listener()
    rng = np.random.default_rng(7)
    workload = _workload(rng)

    eng = _paged(params)
    compiled = eng.compiled_count
    base = stats.count("backend_compile")
    outs = {rid: _drive_one(eng, p, n, rid) for rid, p, n in workload}
    assert stats.count("backend_compile") == base
    assert eng.compiled_count == compiled

    assert eng.page_alias_hits > 0  # the copy-free path really ran
    assert eng.prefix_inserts > 0
    for rid, p, n in workload:
        assert p + outs[rid] == _reference(params, p, n), rid
    # Idle pool: no page still referenced, ledger balances.
    for m in eng._pool_meta:
        assert m is None or m.refs == 0
    st = eng.kv_page_stats()
    assert st["aliased"] == 0
    assert st["allocs"] - st["frees"] == st["resident"], st


@pytest.mark.parametrize("fold", [1, 2, 4])
def test_paged_fold_budget_freeze_matches_generate(params, fold):
    """The paged twin of tests/test_serve.py's ``budget_freeze`` traffic:
    slots at three depths share the paged fold, the deepest runs out of
    its token budget inside a fold and stays frozen under the others
    (its stale writes go to the scratch page once its pages are freed),
    a shorter prompt then takes its slot, and the last request decodes
    up to the view's last row — every output bit-identical to solo
    gpt_generate, with no compile after construction."""
    from tests.utils import budget_freeze_requests

    reqs, late = budget_freeze_requests(np.random.default_rng(0))
    # pages for three residents at once, the 64-row one among them
    eng = _paged(params, decode_fold=fold, kv_pages=64)
    compiled = eng.compiled_count
    outs, slot_of = {}, {}

    def admit(p, n):
        rid = f"r{len(outs)}"
        slot_of[rid], _, _ = eng.admit(p, request_id=rid, max_new_tokens=n)
        outs[rid] = []

    for p, n in reqs:
        admit(p, n)
    frozen_folds = 0
    for _ in range(400):
        if not eng.num_active:
            break
        for _, task, tok, _ in eng.prefill_step(4):
            outs[task.request_id].append(tok)
        for _, rid, tok, _ in eng.step():
            outs[rid].append(tok)
        if len(outs["r1"]) < reqs[1][1]:
            continue
        frozen_folds += 1
        if late and frozen_folds > 2 and eng.free_slots():
            reqs.append(late.pop(0))
            admit(*reqs[-1])
    assert not late and eng.num_active == 0
    assert slot_of["r3"] == slot_of["r1"]  # the frozen tenant's slot
    for i, (p, n) in enumerate(reqs):
        assert p + outs[f"r{i}"] == _reference(params, p, n), f"r{i}"
    assert eng.compiled_count == compiled


def test_paged_vs_dense_same_tokens(params):
    """Paged and dense engines, same workload, token-for-token equal —
    the direct A/B the bit-exact contract promises."""
    rng = np.random.default_rng(11)
    # Cold insert, full alias, partial alias — the three cache shapes;
    # the longer tail rides the generate-oracle test above.
    workload = _workload(rng)[:3]
    paged = _paged(params)
    dense = _dense(params)
    for rid, p, n in workload:
        assert _drive_one(paged, p, n, rid) == _drive_one(
            dense, p, n, rid
        ), rid


def test_paged_spec_ngram_exact_and_frozen(params):
    """spec=ngram inside the paged fold: the drafter + paged verify
    compile into the one step executable (zero steady-state compiles)
    and greedy output stays bit-identical to solo generate, with real
    accepts happening on a repetitive suffix."""
    from ray_lightning_tpu.obs.jaxmon import install_compile_listener

    stats = install_compile_listener()
    eng = _paged(params, spec="ngram", spec_depth=3)
    base = stats.count("backend_compile")
    p = (list(range(6)) * 4)[:14]
    out = _drive_one(eng, p, 12, "s0")
    assert stats.count("backend_compile") == base
    assert p + out == _reference(params, p, 12)
    assert eng.spec_accepted_tokens > 0


def test_paged_mid_prefill_cancel_page_recycle(params):
    """A request cancelled MID-PREFILL while a second request ALIASES
    the same prefix pages: the cancel unrefs without freeing the shared
    pages (the survivor still reads them), the victim's private pages
    recycle through the quarantine, and every stream stays exact."""
    eng = _paged(params, num_slots=3, prefill_chunk=2)
    rng = np.random.default_rng(13)
    shared = rng.integers(0, 97, size=8).tolist()  # exactly 2 pages
    pA = shared + rng.integers(0, 97, size=6).tolist()
    pB = shared + rng.integers(0, 97, size=4).tolist()
    # Warm the shared pages into the cache.
    warm_out = _drive_one(eng, shared + [3], 3, "warm")
    # Admit BOTH: each aliases the 2 shared pages (refs -> 2).
    slotA, _, _ = eng.admit(pA, request_id="victim", max_new_tokens=6)
    eng.admit(pB, request_id="survivor", max_new_tokens=6)
    shared_pages = [
        i for i, m in enumerate(eng._pool_meta)
        if m is not None and m.refs == 2
    ]
    assert len(shared_pages) == 2, shared_pages
    eng.prefill_step(1)  # victim genuinely mid-prefill
    eng.release(slotA)
    # The survivor's alias still pins the shared pages.
    for pg in shared_pages:
        assert eng._pool_meta[pg] is not None
        assert eng._pool_meta[pg].refs == 1, pg
    out = []
    for _ in range(300):
        if not eng.num_active:
            break
        for _, task, tok, _ in eng.prefill_step(2):
            if task.request_id == "survivor":
                out.append(tok)
        for _, rid, tok, _ in eng.step():
            if rid == "survivor":
                out.append(tok)
    assert pB + out == _reference(params, pB, 6)
    assert shared + [3] + warm_out == _reference(params, shared + [3], 3)
    # Victim's private pages recycled; nothing leaked.
    for m in eng._pool_meta:
        assert m is None or m.refs == 0
    # And the recycled capacity is reusable: a fresh request fits.
    pC = rng.integers(0, 97, size=10).tolist()
    assert pC + _drive_one(eng, pC, 4, "re") == _reference(params, pC, 4)


def test_paged_every_page_referenced_parks_not_deadlocks(params):
    """Eviction pressure with EVERY page referenced: the scheduler's
    page-aware admission parks the queue head (backpressure event, no
    deadlock, no engine allocation failure) until residents finish and
    free pages; everything completes exactly. Admissions that find the
    cache pages pinned proceed uncached."""
    from ray_lightning_tpu.obs.events import EventLog
    from ray_lightning_tpu.serve.scheduler import SamplingParams, Scheduler

    ev = EventLog(256)
    # 9 usable pages; each request needs 3 -> 3 residents saturate.
    eng = _paged(
        params, num_slots=8, kv_page=8, kv_pages=10, prefill_chunk=8,
        decode_fold=1,
    )
    sched = Scheduler(eng, max_prefills_per_step=8, events=ev)
    rng = np.random.default_rng(5)
    outs = {}
    for _ in range(5):
        p = rng.integers(0, 97, size=10).tolist()
        # 10 + 6 -> 3 pages each: three residents fill all 9 usable
        # pages exactly, so the pressure check sees 0 available.
        rid = sched.submit(p, SamplingParams(max_new_tokens=6))
        outs[rid] = (p, [])
    saw_saturated = False
    for _ in range(400):
        if not sched.has_work():
            break
        for e in sched.step():
            if e.token is not None:
                outs[e.request_id][1].append(e.token)
        if eng.pages_available() == 0 and sched.queue_depth() > 0:
            saw_saturated = True
    assert not sched.has_work(), "deadlocked under page pressure"
    assert saw_saturated  # the pressure was real
    assert "kv_pages_backpressure" in ev.to_jsonl()
    for rid, (p, out) in outs.items():
        assert p + out == _reference(params, p, 6), rid


def test_paged_tiered_spill_promote_exact(params, tmp_path):
    """PR 10's tiers operate on the unified pages: pool pressure spills
    evicted cache pages D2H into the host tier (then disk), a revisit
    PROMOTES them back through the compiled H2D write and ALIASES them
    — and every tier path stays bit-identical to solo generate with
    zero steady-state compiles."""
    from ray_lightning_tpu.obs.jaxmon import install_compile_listener

    stats = install_compile_listener()
    # 17 usable pages (the minimum for max_seq 64 / page 4): ten
    # 2-cache-page prompts want 20 cache pages, so round 1 already
    # evicts — the victims spill into the tiers instead of dying.
    eng = _paged(
        params, num_slots=2, kv_pages=18,
        prefix_host_mb=_mb(4),
        prefix_disk_dir=str(tmp_path / "paged-disk"), prefix_disk_mb=1.0,
    )
    assert eng.paged and eng._tiered  # tiers need no prefix_blocks knob
    base = stats.count("backend_compile")
    rng = np.random.default_rng(17)
    prompts = [rng.integers(0, 97, size=10).tolist() for _ in range(10)]
    outs = {}
    # Round 1: insert everything (evictions cascade into the tiers).
    for i, p in enumerate(prompts):
        outs[f"a{i}"] = (p, _drive_one(eng, p, 4, f"a{i}"))
    # Round 2: revisit the OLDEST half — their pages were the eviction
    # victims, so the hits are genuinely cold (promote + alias).
    for i, p in enumerate(prompts[:5]):
        outs[f"b{i}"] = (p, _drive_one(eng, p, 4, f"b{i}"))
    assert stats.count("backend_compile") == base
    tc = eng.tier_counters
    assert tc["device"]["spills"] > 0, tc
    cold_hits = tc["host"]["hits"] + tc["disk"]["hits"]
    cold_promos = tc["host"]["promotions"] + tc["disk"]["promotions"]
    assert cold_hits > 0 and cold_promos > 0, tc
    assert eng.page_alias_hits > 0
    for rid, (p, out) in outs.items():
        assert p + out == _reference(params, p, 4), rid


def test_paged_mesh_2x4_bit_identical_and_frozen_compiles(
    params, tp_mesh
):
    """The paged contracts under the 8-device CPU mesh (model=2 shards
    the page pool's head axis; tables and slot state replicate): the
    alias/insert workload stays bit-identical to single-device solo
    gpt_generate with zero steady-state compiles."""
    from ray_lightning_tpu.obs.jaxmon import install_compile_listener

    stats = install_compile_listener()
    rng = np.random.default_rng(7)
    workload = _workload(rng)
    eng = _paged(params, tp_mesh)
    base = stats.count("backend_compile")
    outs = {rid: _drive_one(eng, p, n, rid) for rid, p, n in workload}
    assert stats.count("backend_compile") == base
    assert eng.page_alias_hits > 0
    for rid, p, n in workload:
        assert p + outs[rid] == _reference(params, p, n), rid


def test_paged_export_import_handoff_carries_aliased_pages(params):
    """PR 12's cross-replica KV handoff on the unified allocator: a
    paged engine exports a request's cached prefix pages WHILE they are
    aliased by a live request, a same-config peer imports them, and the
    migrated request's admission on the peer lands a warm copy-free
    alias — outputs exact on both sides."""
    rng = np.random.default_rng(23)
    shared = rng.integers(0, 97, size=12).tolist()  # 3 full pages
    prompt = shared + rng.integers(0, 97, size=3).tolist()

    src = _paged(params)
    _drive_one(src, prompt, 4, "orig")
    # A live request aliasing the pages keeps them referenced while the
    # export reads them (refs > 0 must not block a read-only export).
    src.admit(prompt, request_id="rider", max_new_tokens=4)
    blocks = src.export_prefix_blocks(prompt)
    assert len(blocks) == 3
    assert any(
        m is not None and m.refs > 0 for m in src._pool_meta
    )

    dst = _paged(params)
    assert dst.import_prefix_blocks(blocks) == 3
    hits0 = dst.page_alias_hits
    out = _drive_one(dst, prompt, 4, "migrated")
    assert dst.page_alias_hits == hits0 + 3  # warm, copy-free
    assert prompt + out == _reference(params, prompt, 4)
    # Source finishes its rider exactly too (export was read-only).
    out_src = []
    for _ in range(300):
        if not src.num_active:
            break
        for _, task, tok, _ in src.prefill_step(2):
            out_src.append(tok)
        for _, rid, tok, _ in src.step():
            out_src.append(tok)
    assert prompt + out_src == _reference(params, prompt, 4)


def test_paged_journal_replay_rebuilds_config(params):
    """Replay fidelity: the journal header records kv_page/kv_pages
    (and zeroes the folded-away prefix knobs so rebuild cannot trip the
    combo rejection), build_replay_scheduler rebuilds the same paged
    config, and a captured alias-hitting session replays bit-exactly —
    reproducing the alias path on the replay side."""
    from ray_lightning_tpu.obs.journal import (
        WorkloadJournal,
        build_replay_scheduler,
        engine_header,
        replay_journal,
    )
    from ray_lightning_tpu.serve.scheduler import SamplingParams, Scheduler

    eng = _paged(params)
    journal = WorkloadJournal(capacity=256)
    journal.set_header(engine_header(eng))
    sched = Scheduler(eng, journal=journal)
    rng = np.random.default_rng(29)
    pA = rng.integers(0, 97, size=10).tolist()
    for p in (pA, rng.integers(0, 97, size=12).tolist(), pA):
        sched.submit(p, SamplingParams(max_new_tokens=4))
        sched.run_until_idle()
    assert eng.page_alias_hits > 0
    dump = journal.dump()
    hdr = dump["header"]["engine"]
    assert hdr["kv_page"] == 4 and hdr["kv_pages"] == 32
    assert hdr["prefix_blocks"] == 0

    replay_sched = build_replay_scheduler(dump["header"], params=params)
    assert replay_sched.engine.paged
    assert replay_sched.engine.kv_page == 4
    assert replay_sched.engine.kv_pages == 32
    result = replay_journal(dump, scheduler=replay_sched)
    assert result["exact"], result["divergence"]
    assert result["compared"] == 3
    # The replay rebuilt and exercised the same paged machinery
    # (virtual replay interleaves admissions the capture ran
    # sequentially, so WHETHER a block is served by alias or fresh
    # prefill can differ — exactness cannot).
    assert replay_sched.engine.page_allocs > 0


def test_paged_knob_validation(params):
    from ray_lightning_tpu.serve.engine import DecodeEngine

    kw = dict(num_slots=1, max_seq=32, prefill_buckets=[16])
    with pytest.raises(ValueError, match="kv_pages > 0"):
        DecodeEngine(params, CFG, kv_page=4, **kw)
    with pytest.raises(ValueError, match="divide"):
        DecodeEngine(params, CFG, kv_page=5, kv_pages=16, **kw)
    with pytest.raises(ValueError, match="max-length request"):
        DecodeEngine(params, CFG, kv_page=4, kv_pages=4, **kw)
    with pytest.raises(ValueError, match="unifies the prefix pool"):
        DecodeEngine(
            params, CFG, kv_page=4, kv_pages=16, prefix_blocks=2, **kw
        )
    # (Tiers riding the unified pool without a prefix_blocks knob is
    # exercised — with traffic — by the spill/promote test above.)


def test_paged_cli_rejects_prefix_cache_combo():
    """The loud up-front rejection: --serve.kv_pages combined with the
    dense prefix cache must fail before any checkpoint loads, naming
    the remedy; kv_page alone (no budget) fails too."""
    from ray_lightning_tpu.cli import cli_entry

    with pytest.raises(ValueError, match="unifies the prefix pool"):
        cli_entry([
            "serve", "--serve.ckpt_path", "/nonexistent.ckpt",
            "--serve.prompts", "/nonexistent.txt",
            "--serve.kv_pages", "64", "--serve.prefix_cache", "on",
        ])
    with pytest.raises(ValueError, match="needs --serve.kv_pages"):
        cli_entry([
            "serve", "--serve.ckpt_path", "/nonexistent.ckpt",
            "--serve.prompts", "/nonexistent.txt",
            "--serve.kv_page", "16",
        ])


def test_paged_metrics_fleet_row_and_top_column(params):
    """Page-pool observability end to end: the scheduler-diffed
    counters land in the rlt_serve_kv_page_* series and the
    state-labelled rlt_serve_kv_pages gauge, the snapshot carries the
    kv_pages block (occupancy/fragmentation), the fleet row derives the
    page cells, and the rlt top frame renders the pages column."""
    from ray_lightning_tpu.cli import render_fleet
    from ray_lightning_tpu.obs.fleet import summarize_replica
    from ray_lightning_tpu.obs.registry import MetricsRegistry
    from ray_lightning_tpu.serve.metrics import ServeMetrics
    from ray_lightning_tpu.serve.scheduler import SamplingParams, Scheduler

    eng = _paged(params)
    reg = MetricsRegistry()
    sched = Scheduler(eng, metrics=ServeMetrics(3, registry=reg))
    rng = np.random.default_rng(31)
    pA = rng.integers(0, 97, size=10).tolist()
    for p in (pA, pA):  # insert then alias
        sched.submit(p, SamplingParams(max_new_tokens=4))
        sched.run_until_idle()
    snap = sched.metrics.snapshot()
    kv = snap["kv_pages"]
    assert kv["page_size"] == 4 and kv["pages_total"] == 31
    assert kv["alias_hits"] > 0
    assert kv["fragmentation_tokens"] >= 0
    assert 0.0 <= kv["occupancy"] <= 1.0
    text = reg.render()
    assert 'rlt_serve_kv_pages{state="free"}' in text
    assert 'rlt_serve_kv_pages{state="resident"}' in text
    assert 'rlt_serve_kv_pages{state="aliased"}' in text
    assert "rlt_serve_kv_page_allocs_total" in text
    assert "rlt_serve_kv_page_frees_total" in text
    assert "rlt_serve_kv_page_alias_hits_total" in text

    row = summarize_replica(dict(snap, active_slots=0))
    assert row["kv_pages"]["resident"] >= 0
    assert set(row["kv_pages"]) == {
        "free", "resident", "aliased", "occupancy",
        "fragmentation_tokens",
    }
    frame = render_fleet(
        {"latest": {"replicas": [row], "fleet": {}}}
    )
    assert "pages f/r/a" in frame
    assert "{}/{}/{}".format(
        row["kv_pages"]["free"], row["kv_pages"]["resident"],
        row["kv_pages"]["aliased"],
    ) in frame
    # Dense rows render a "-" cell, not a crash.
    dense_row = dict(row, kv_pages=None)
    assert "pages f/r/a" in render_fleet(
        {"latest": {"replicas": [dense_row], "fleet": {}}}
    )
    # Memory/footprint shapes ride the same engine: no dense slot
    # strips, the unified pool + page table reported instead.
    mem = eng.memory_stats()
    assert mem["kv_cache"]["bytes"] == 0
    assert mem["prefix_pool"]["bytes"] > 0
    assert mem["page_table"]["bytes"] > 0
    assert eng.pages_for(10, 6) == (10 + 6) // 4 + 1
    # pages_for clamps at the cache edge exactly like the dense write.
    assert eng.pages_for(50, 14) == (64 - 1) // 4 + 1


def test_paged_residency_beats_dense_at_fixed_budget(params):
    """The capacity claim, miniature: at the SAME KV token budget (256
    tokens), the paged engine holds >= 1.5x the dense engine's maximum
    concurrent residents on short requests — and both produce identical
    tokens."""
    from ray_lightning_tpu.serve.scheduler import SamplingParams, Scheduler

    rng = np.random.default_rng(37)
    prompts = [rng.integers(0, 97, size=10).tolist() for _ in range(10)]

    def run(paged):
        kw = (
            dict(num_slots=12, kv_page=8, kv_pages=33)
            if paged
            else dict(num_slots=4)  # 4 slots x 64 = the same 256 tokens
        )
        eng = _paged(params, prefill_chunk=8, **kw) if paged else _dense(
            params, num_slots=4, prefill_chunk=8
        )
        sched = Scheduler(eng, max_prefills_per_step=12)
        outs = {}
        for p in prompts:
            rid = sched.submit(p, SamplingParams(max_new_tokens=6))
            outs[rid] = []
        max_res = 0
        while sched.has_work():
            for e in sched.step():
                if e.token is not None:
                    outs[e.request_id].append(e.token)
            max_res = max(max_res, eng.num_active)
        return max_res, list(outs.values())

    dense_res, dense_out = run(False)
    paged_res, paged_out = run(True)
    assert paged_out == dense_out
    assert paged_res >= 1.5 * dense_res, (paged_res, dense_res)




def test_paged_piggyback_fused_dispatch_bit_exact(params):
    """Piggybacked prefill rows over the PAGED pool (chunk writes land
    through page tables while decode rows read them): scheduler-driven
    mixed workload bit-identical to solo gpt_generate with frozen
    compiles and the fused counters moving."""
    from ray_lightning_tpu.serve.scheduler import SamplingParams, Scheduler

    eng = _paged(params, piggyback_chunks=2, fold_ladder=[1, 2])
    compiles_before = eng.compiled_count
    sched = Scheduler(eng, max_prefills_per_step=2)
    rng = np.random.default_rng(41)
    reqs = {}
    for i in range(6):
        p = rng.integers(0, 97, size=int(rng.integers(5, 15))).tolist()
        n = int(rng.integers(3, 8))
        rid = sched.submit(p, SamplingParams(max_new_tokens=n))
        reqs[rid] = (p, n, [])
    for ev in sched.run_until_idle():
        if ev.token is not None:
            reqs[ev.request_id][2].append(ev.token)
    assert not sched.has_work() and eng.num_active == 0
    for rid, (p, n, toks) in reqs.items():
        assert p + toks == _reference(params, p, n), rid
    assert eng.piggyback_dispatches > 0
    assert eng.piggyback_chunk_rows > 0
    assert eng.compiled_count == compiles_before
    # No page leaked through the fused chunk path: everything left in
    # the pool is an unreferenced (aliasable) cache entry.
    for m in eng._pool_meta:
        assert m is None or m.refs == 0
