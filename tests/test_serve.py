"""Serving subsystem tests: slot engine exactness, continuous batching,
scheduler policy, replica actors, stats.

The load-bearing property is EXACTNESS UNDER BATCHING: whatever mix of
requests shares the engine's compiled step, each request's greedy tokens
must equal a solo ``gpt_generate`` run — admissions and evictions
mid-flight included — with a compile count that never moves after
construction.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from ray_lightning_tpu.models.gpt import (
    GPTConfig,
    gpt_generate,
    init_gpt_params,
)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: GQA config on purpose: the slot cache carries Hkv < H heads, the shape
#: most likely to break slot indexing.
SERVE_CFG = GPTConfig(
    vocab_size=97,
    n_layer=2,
    n_head=4,
    n_kv_head=2,
    d_model=32,
    max_seq=64,
    attn_impl="reference",
    compute_dtype="float32",
)


@pytest.fixture(scope="module")
def serve_params():
    import jax

    return init_gpt_params(jax.random.PRNGKey(0), SERVE_CFG)


@pytest.fixture(scope="module")
def engine(serve_params):
    from ray_lightning_tpu.serve.engine import DecodeEngine

    return DecodeEngine(
        serve_params,
        SERVE_CFG,
        num_slots=3,
        max_seq=64,
        prefill_buckets=[8, 16],
    )


def _reference(params, prompt, n, cfg=SERVE_CFG):
    out = gpt_generate(
        params, cfg, np.asarray(prompt, np.int32)[None], n
    )
    return np.asarray(out)[0].tolist()


def _prompt_whose_greedy(params, want, n, tries=64):
    """``(prompt, solo)``: the first 6-token prompt of a seeded batch
    whose greedy continuation of ``n`` tokens satisfies ``want``. A
    fixture that names one prompt breaks whenever the toy model's
    numerics move under a new jax; a search keeps the precondition true.
    The batch finds candidates in one call, the solo run decides."""
    prompts = np.random.default_rng(0).integers(0, 97, size=(tries, 6))
    outs = np.asarray(
        gpt_generate(params, SERVE_CFG, prompts.astype(np.int32), n)
    )[:, 6:]
    for prompt, out in zip(prompts.tolist(), outs.tolist()):
        if want(out):
            solo = _reference(params, prompt, n)[6:]
            if want(solo):
                return prompt, solo
    pytest.fail(f"none of {tries} prompts has the continuation wanted")


#: Llama-style: three layers of RMSNorm, rotary GQA and SwiGLU — every leaf
#: the engine re-forms when it is built (``engine_weights``).
LLAMA_CFG = GPTConfig.llama(
    vocab_size=97, n_layer=3, n_head=4, n_kv_head=2, d_model=32, d_ff=48,
    max_seq=64, attn_impl="reference", compute_dtype="float32",
)


@pytest.mark.parametrize("family", ["gqa-gelu", "llama"])
def test_engine_concurrent_matches_sequential_generate(family, request):
    """Different prompt/output lengths admitted together, a request joining
    mid-flight as another leaves: every output token-identical to solo
    gpt_generate, with ZERO compiles after construction.

    ``llama``: the engine holds the re-formed tree (gate and up apart, the
    GQA projections flat) and solo ``gpt_generate`` runs on the STORED tree
    the engine was handed, which the engine left as it was."""
    if family == "llama":
        import jax

        from ray_lightning_tpu.serve.engine import DecodeEngine

        cfg = LLAMA_CFG
        serve_params = init_gpt_params(jax.random.PRNGKey(0), cfg)
        engine = DecodeEngine(
            serve_params, cfg, num_slots=3, max_seq=64, prefill_buckets=[8, 16]
        )
        held, given = engine.params["blocks"], serve_params["blocks"]
        assert "wi" not in held and held["wi_gate"].shape == (3, 32, 48)
        assert held["wq"].shape == (3, 32, 32) and given["wq"].shape == (3, 32, 4, 8)
        assert given["wi"].shape == (3, 32, 2, 48) and not given["wi"].is_deleted()
    else:
        cfg = SERVE_CFG
        engine = request.getfixturevalue("engine")
        serve_params = request.getfixturevalue("serve_params")
    compiles_before = engine.compiled_count
    rng = np.random.default_rng(0)
    reqs = [
        (rng.integers(0, 97, size=5).tolist(), 7),
        (rng.integers(0, 97, size=8).tolist(), 4),
        (rng.integers(0, 97, size=11).tolist(), 9),
    ]
    outs = {}
    for i, (p, n) in enumerate(reqs):
        _, tok, done = engine.admit(
            p, request_id=f"r{i}", max_new_tokens=n
        )
        outs[f"r{i}"] = [tok]
        assert not done
    joined = False
    for _ in range(100):
        if not engine.num_active:
            break
        for _, rid, tok, _ in engine.step():
            outs[rid].append(tok)
        if not joined and engine.free_slots():
            # The shortest request finished: a new one joins mid-flight
            # while the others keep decoding (continuous batching).
            p4 = rng.integers(0, 97, size=6).tolist()
            _, tok, _ = engine.admit(p4, request_id="r3", max_new_tokens=5)
            outs["r3"] = [tok]
            reqs.append((p4, 5))
            joined = True
    assert joined and engine.num_active == 0
    for i, (p, n) in enumerate(reqs):
        assert p + outs[f"r{i}"] == _reference(serve_params, p, n, cfg), f"r{i}"
    # No per-request recompilation: the count is frozen at construction.
    assert engine.compiled_count == compiles_before


def test_engine_int8_matches_sequential_generate(serve_params):
    """The engine consumes a weight-only int8 tree directly and stays
    token-identical to gpt_generate over the SAME quantized tree."""
    from ray_lightning_tpu.serve.engine import DecodeEngine
    from ray_lightning_tpu.utils.quantize import quantize_params_int8

    qparams = quantize_params_int8(serve_params)
    eng = DecodeEngine(
        qparams, SERVE_CFG, num_slots=2, max_seq=48, prefill_buckets=[8]
    )
    compiles = eng.compiled_count
    rng = np.random.default_rng(1)
    reqs = [
        (rng.integers(0, 97, size=6).tolist(), 6),
        (rng.integers(0, 97, size=8).tolist(), 8),
    ]
    outs = {}
    for i, (p, n) in enumerate(reqs):
        _, tok, _ = eng.admit(p, request_id=f"q{i}", max_new_tokens=n)
        outs[f"q{i}"] = [tok]
    while eng.num_active:
        for _, rid, tok, _ in eng.step():
            outs[rid].append(tok)
    for i, (p, n) in enumerate(reqs):
        assert p + outs[f"q{i}"] == _reference(qparams, p, n), f"q{i}"
    assert eng.compiled_count == compiles


def test_engine_sampling_independent_of_batchmates(serve_params):
    """A sampled (temperature > 0) request draws the same tokens alone as
    it does sharing steps with batchmates: per-slot rng chains."""
    from ray_lightning_tpu.serve.engine import DecodeEngine

    def run(with_companion):
        eng = DecodeEngine(
            serve_params, SERVE_CFG, num_slots=2, max_seq=48,
            prefill_buckets=[8],
        )
        prompt = list(range(1, 7))
        _, tok, _ = eng.admit(
            prompt, request_id="s", max_new_tokens=8,
            temperature=0.8, top_k=20, top_p=0.9, seed=123,
        )
        toks = [tok]
        if with_companion:
            _, c0, _ = eng.admit(
                [9, 8, 7], request_id="c", max_new_tokens=8,
                temperature=1.3, seed=7,
            )
        while eng.num_active:
            for _, rid, tok, _ in eng.step():
                if rid == "s":
                    toks.append(tok)
        return toks

    assert run(False) == run(True)
    # And the EOS knob actually terminates: eos on a tiny vocab hits fast.
    eng = DecodeEngine(
        serve_params, SERVE_CFG, num_slots=1, max_seq=48, prefill_buckets=[8]
    )
    solo = run(False)
    eos = solo[3]
    _, tok, done = eng.admit(
        list(range(1, 7)), request_id="e", max_new_tokens=8,
        temperature=0.8, top_k=20, top_p=0.9, seed=123, eos_token=eos,
    )
    toks = [tok]
    while eng.num_active and not done:
        for _, _, tok, done in eng.step():
            toks.append(tok)
    assert toks == solo[:4]  # stopped AT the eos token


def test_engine_rejects_oversize_and_full(engine):
    with pytest.raises(ValueError):
        engine.admit(
            list(range(40)), request_id="big", max_new_tokens=4
        )  # over every bucket
    with pytest.raises(ValueError):
        engine.admit(
            list(range(8)), request_id="long", max_new_tokens=60
        )  # prompt + new > max_seq


def test_scheduler_priority_deadline_cancel(serve_params):
    """One-slot engine: priorities order admission, deadlines expire
    queued work, cancellation evicts in-flight work at a step boundary."""
    from ray_lightning_tpu.serve.engine import DecodeEngine
    from ray_lightning_tpu.serve.scheduler import SamplingParams, Scheduler

    eng = DecodeEngine(
        serve_params, SERVE_CFG, num_slots=1, max_seq=48, prefill_buckets=[8]
    )
    sched = Scheduler(eng, max_prefills_per_step=1)
    sp = SamplingParams(max_new_tokens=4)
    rid_low = sched.submit([1, 2, 3], sp, priority=5)
    rid_hi = sched.submit([4, 5, 6], sp, priority=0)
    rid_dead = sched.submit([7, 8, 9], sp, priority=9, deadline_s=0.0)
    order = []
    events = []
    for _ in range(50):
        if not sched.has_work():
            break
        for ev in sched.step():
            events.append(ev)
            if ev.reason == "token" and ev.request_id not in order:
                order.append(ev.request_id)
    # Priority 0 ran before priority 5; the 0-deadline request never ran.
    assert order.index(rid_hi) < order.index(rid_low)
    assert [e.reason for e in events if e.request_id == rid_dead] == [
        "expired"
    ]
    # Cancellation mid-flight: submit, let it start, cancel, slot frees.
    rid = sched.submit([1, 2, 3, 4], SamplingParams(max_new_tokens=20))
    sched.step()  # admits
    assert eng.num_active == 1
    assert sched.cancel(rid)
    evs = sched.step()
    assert ("cancelled" in [e.reason for e in evs if e.request_id == rid])
    assert eng.num_active == 0
    # Unknown ids are reported as such.
    assert not sched.cancel("nope")


def test_scheduler_outputs_match_reference_under_load(serve_params):
    """8 overlapping requests through a 3-slot scheduler: continuous
    batching with queueing, every output exact."""
    from ray_lightning_tpu.serve.engine import DecodeEngine
    from ray_lightning_tpu.serve.scheduler import SamplingParams, Scheduler

    eng = DecodeEngine(
        serve_params, SERVE_CFG, num_slots=3, max_seq=48,
        prefill_buckets=[8, 16],
    )
    sched = Scheduler(eng, max_prefills_per_step=2)
    rng = np.random.default_rng(2)
    reqs = {}
    for i in range(8):
        p = rng.integers(0, 97, size=int(rng.integers(3, 12))).tolist()
        n = int(rng.integers(2, 9))
        rid = sched.submit(p, SamplingParams(max_new_tokens=n))
        reqs[rid] = (p, n, [])
    events = sched.run_until_idle()
    for ev in events:
        if ev.token is not None:
            reqs[ev.request_id][2].append(ev.token)
    assert not sched.has_work()
    for rid, (p, n, toks) in reqs.items():
        assert p + toks == _reference(serve_params, p, n)
    snap = sched.metrics.snapshot()
    assert snap["admitted"] == 8 and snap["finished"] == 8
    assert snap["occupancy"] > 0
    assert snap["tokens_per_sec"] > 0


def _drive_mixed_join(eng, rng):
    """Mixed lengths and a mid-flight join at a fold boundary."""
    reqs = [
        (rng.integers(0, 97, size=5).tolist(), 7),
        (rng.integers(0, 97, size=8).tolist(), 4),
        (rng.integers(0, 97, size=11).tolist(), 9),
    ]
    outs = {}
    for i, (p, n) in enumerate(reqs):
        _, tok, done = eng.admit(p, request_id=f"r{i}", max_new_tokens=n)
        outs[f"r{i}"] = [tok]
        assert not done
    joined = False
    for _ in range(100):
        if not eng.num_active:
            break
        for _, rid, tok, _ in eng.step():
            outs[rid].append(tok)
        if not joined and eng.free_slots():
            p4 = rng.integers(0, 97, size=6).tolist()
            _, tok, _ = eng.admit(p4, request_id="r3", max_new_tokens=5)
            outs["r3"] = [tok]
            reqs.append((p4, 5))
            joined = True
    assert joined
    return reqs, outs


def _drive_budget_freeze(eng, rng, max_seq=64):
    """Slots at three depths (``budget_freeze_requests``). The deepest
    runs out of its token budget inside a fold and stays frozen under its
    batchmates for the folds that follow, rewriting the stale row at its
    frozen position on every iteration; its slot then goes to a shorter
    prompt, whose rows past the prompt still hold the old tenant's K/V;
    and the last request decodes up to the cache's last row."""
    from tests.utils import budget_freeze_requests

    reqs, late = budget_freeze_requests(rng, max_seq)
    outs, slot_of = {}, {}
    for i, (p, n) in enumerate(reqs):
        slot_of[f"r{i}"], tok, done = eng.admit(
            p, request_id=f"r{i}", max_new_tokens=n
        )
        outs[f"r{i}"] = [tok]
        assert not done
    frozen_folds = 0
    for _ in range(200 + max_seq):
        if not eng.num_active:
            break
        for _, rid, tok, _ in eng.step():
            outs[rid].append(tok)
        if len(outs["r1"]) < reqs[1][1]:
            continue
        frozen_folds += 1
        # two folds with the freed slot idle under the others, then
        # the short prompt takes it; the long one takes the next free
        if late and frozen_folds > 2 and eng.free_slots():
            p, n = late.pop(0)
            rid = f"r{len(reqs)}"
            slot_of[rid], tok, _ = eng.admit(
                p, request_id=rid, max_new_tokens=n
            )
            outs[rid] = [tok]
            reqs.append((p, n))
    assert not late
    assert slot_of["r3"] == slot_of["r1"]  # the stale rows' slot
    return reqs, outs


_FOLD_TRAFFIC = {
    "mixed_join": _drive_mixed_join,
    "budget_freeze": _drive_budget_freeze,
}


@pytest.mark.parametrize("traffic", sorted(_FOLD_TRAFFIC))
@pytest.mark.parametrize("fold", [1, 2, 4])
def test_engine_folded_matches_sequential_generate(
    serve_params, fold, traffic
):
    """decode_fold=K: K tokens per dispatch, mixed lengths, a mid-flight
    join at a fold boundary — every output token-identical to solo
    gpt_generate (K=1 included: the fold generalizes, never forks, the
    unfolded behavior), with ZERO compiles after construction even
    across admissions and folded steps. ``traffic`` picks who shares the
    fold: see ``_drive_mixed_join`` and ``_drive_budget_freeze``."""
    from ray_lightning_tpu.serve.engine import DecodeEngine

    eng = DecodeEngine(
        serve_params, SERVE_CFG, num_slots=3, max_seq=64,
        prefill_buckets=[8, 16], decode_fold=fold,
    )
    compiles = eng.compiled_count
    reqs, outs = _FOLD_TRAFFIC[traffic](eng, np.random.default_rng(0))
    assert eng.num_active == 0
    for i, (p, n) in enumerate(reqs):
        assert p + outs[f"r{i}"] == _reference(serve_params, p, n), f"r{i}"
    assert eng.compiled_count == compiles


@pytest.mark.parametrize("fold", [1, 4])
def test_engine_with_the_decode_kernel_serves_the_xla_reads_tokens(fold, monkeypatch):
    """The decode kernel (ops/decode_attention.py) under the engine, in
    interpret mode: the one selection function is told "tpu", nothing else.
    ``budget_freeze`` traffic on a cache of three blocks a slot — a frozen
    slot under live ones, a slot re-let over a longer tenant's stale rows, a
    request that decodes to the cache's last row — gives the tokens of the
    same engine on the XLA read, with no compile after construction. (Not
    gpt_generate's to the bit: the kernel sums the softmax blockwise.)"""
    import jax

    from ray_lightning_tpu.serve.engine import DecodeEngine
    from tests.utils import force_decode_kernel

    cfg = GPTConfig.llama(
        vocab_size=97, n_layer=2, n_head=4, n_kv_head=2, d_model=256, max_seq=384,
        compute_dtype="float32",
    )
    params = init_gpt_params(jax.random.PRNGKey(0), cfg)
    kw = dict(num_slots=3, max_seq=384, prefill_buckets=[8, 16], decode_fold=fold)

    def serve():
        eng = DecodeEngine(params, cfg, **kw)
        compiles = eng.compiled_count
        reqs, outs = _drive_budget_freeze(eng, np.random.default_rng(0), max_seq=384)
        assert eng.num_active == 0 and eng.compiled_count == compiles
        assert len(outs["r4"]) == 384 - 16  # to the last row
        return eng, outs

    xla, want = serve()
    assert xla._attn_reads == {None: (cfg.n_layer, 0)}
    force_decode_kernel(monkeypatch)
    kernel, got = serve()
    assert kernel._attn_reads == {None: (cfg.n_layer, 128)}
    assert got == want
    a, b = kernel.attn_stats(), xla.attn_stats()
    assert a["rows_live"] == b["rows_live"] and a["rows_allocated"] == b["rows_allocated"]
    assert b["rows_visited"] == b["rows_allocated"]
    assert a["rows_live"] <= a["rows_visited"] < a["rows_allocated"]


def test_engine_fold_eos_truncates_mid_fold(serve_params):
    """EOS landing strictly INSIDE a fold: the slot self-freezes in-graph
    — emission stops exactly at the eos token (never past it), the
    device-side active mask drops, and a batchmate decodes through the
    same folds unperturbed."""
    from ray_lightning_tpu.serve.engine import DecodeEngine

    # eos = the 6th generated token, a value with no earlier occurrence,
    # landing on the FIRST iteration of the second fold — the slot must
    # freeze with three fold iterations still to run under it.
    prompt, solo = _prompt_whose_greedy(
        serve_params, lambda s: s[5] not in s[:5], 8
    )
    eos = solo[5]
    assert eos not in solo[:5]
    eng = DecodeEngine(
        serve_params, SERVE_CFG, num_slots=2, max_seq=64,
        prefill_buckets=[8, 16], decode_fold=4,
    )
    _, tok, done = eng.admit(
        prompt, request_id="e", max_new_tokens=8, eos_token=eos
    )
    toks = [tok]
    assert not done
    mate_prompt = list(range(20, 31))
    _, mtok, _ = eng.admit(mate_prompt, request_id="m", max_new_tokens=9)
    mtoks = [mtok]
    while eng.num_active:
        for _, rid, tok, _ in eng.step():
            (toks if rid == "e" else mtoks).append(tok)
    assert toks == solo[: solo.index(eos) + 1]  # stopped AT eos, mid-fold
    assert mate_prompt + mtoks == _reference(serve_params, mate_prompt, 9)
    state = eng.device_state()  # sync point: device agrees nothing runs
    assert not state["active"].any()


def test_engine_fold_cancel_at_boundary_and_recycle(serve_params):
    """Cancellation between folds (with a speculative fold already in
    flight): the zombie fold's tokens are dropped, the slot recycles,
    and the NEXT tenant of the same slot decodes exactly — the stale
    state/cache leak nothing."""
    from ray_lightning_tpu.serve.engine import DecodeEngine

    eng = DecodeEngine(
        serve_params, SERVE_CFG, num_slots=1, max_seq=64,
        prefill_buckets=[8, 16], decode_fold=4,
    )
    compiles = eng.compiled_count
    slot, tok, _ = eng.admit(
        list(range(1, 9)), request_id="victim", max_new_tokens=20
    )
    n_before = 1 + len(eng.step())  # one fold harvested, next in flight
    eng.release(slot)  # fold-boundary cancel while fold N+1 executes
    assert eng.num_active == 0 and eng.free_slots() == [0]
    prompt = list(range(40, 46))
    slot2, tok2, _ = eng.admit(prompt, request_id="next", max_new_tokens=7)
    assert slot2 == slot  # same slot, recycled
    toks = [tok2]
    while eng.num_active:
        for _, rid, tok, _ in eng.step():
            assert rid == "next"  # no zombie "victim" tokens surface
            toks.append(tok)
    assert prompt + toks == _reference(serve_params, prompt, 7)
    assert n_before < 20  # the victim really was cut short
    assert eng.compiled_count == compiles


def _drive_engine(eng, outs):
    """Drive a chunked engine to idle: interleave prefill chunks with
    decode folds, collecting tokens per request id."""
    while eng.num_active:
        for _, task, tok, _ in eng.prefill_step(1):
            outs[task.request_id].append(tok)
        for _, rid, tok, _ in eng.step():
            outs[rid].append(tok)


@pytest.mark.parametrize("chunk", [4, 8, 64])
def test_engine_chunked_prefill_matches_generate(serve_params, chunk):
    """Chunked prefill (chunk smaller than, comparable to, and covering
    the whole prompt bucket): admission is a per-slot state machine whose
    chunks interleave with decode folds of resident batchmates, prompts
    may exceed the largest prefill bucket (chunking lifts the cap), and
    every greedy output stays bit-identical to solo gpt_generate with
    ZERO compiles after construction."""
    from ray_lightning_tpu.serve.engine import DecodeEngine

    eng = DecodeEngine(
        serve_params, SERVE_CFG, num_slots=3, max_seq=64,
        prefill_buckets=[8, 16], prefill_chunk=chunk, decode_fold=2,
    )
    compiles = eng.compiled_count
    rng = np.random.default_rng(0)
    reqs = [
        (rng.integers(0, 97, size=5).tolist(), 7),
        (rng.integers(0, 97, size=11).tolist(), 4),
        # Over the largest (16) prompt bucket: only chunking admits this.
        (rng.integers(0, 97, size=20).tolist(), 6),
    ]
    outs = {}
    for i, (p, n) in enumerate(reqs):
        slot, tok, done = eng.admit(p, request_id=f"r{i}", max_new_tokens=n)
        assert tok is None and not done  # first token rides prefill_step
        outs[f"r{i}"] = []
    # Join mid-flight: r0's prefill completes first; admit r3 while the
    # 20-token prompt is still chunking and others decode.
    joined = False
    for _ in range(200):
        if not eng.num_active:
            break
        for _, task, tok, _ in eng.prefill_step(1):
            outs[task.request_id].append(tok)
        for _, rid, tok, _ in eng.step():
            outs[rid].append(tok)
        if not joined and eng.free_slots():
            p4 = rng.integers(0, 97, size=6).tolist()
            eng.admit(p4, request_id="r3", max_new_tokens=5)
            outs["r3"] = []
            reqs.append((p4, 5))
            joined = True
    assert joined and eng.num_active == 0
    for i, (p, n) in enumerate(reqs):
        assert p + outs[f"r{i}"] == _reference(serve_params, p, n), f"r{i}"
    assert eng.compiled_count == compiles


def test_engine_prefix_cache_hit_and_miss_exact(serve_params):
    """Prefix caching: a second request sharing a prompt prefix seeds its
    KV from the pool (compiled cache-to-cache copy) and prefills only the
    suffix — outputs bit-identical to solo gpt_generate on hit AND miss,
    hit counters move, compile count frozen."""
    from ray_lightning_tpu.serve.engine import DecodeEngine

    eng = DecodeEngine(
        serve_params, SERVE_CFG, num_slots=2, max_seq=64,
        prefill_buckets=[8, 16], prefill_chunk=4, prefix_blocks=8,
        prefix_block=4, decode_fold=2,
    )
    compiles = eng.compiled_count
    rng = np.random.default_rng(3)
    prefix = rng.integers(0, 97, size=8).tolist()
    a = prefix + rng.integers(0, 97, size=3).tolist()
    b = prefix + rng.integers(0, 97, size=5).tolist()
    c = rng.integers(0, 97, size=9).tolist()  # unrelated: a miss
    for rid, (p, n) in zip("abc", [(a, 6), (b, 7), (c, 5)]):
        outs = {rid: []}
        eng.admit(p, request_id=rid, max_new_tokens=n)
        _drive_engine(eng, outs)
        assert p + outs[rid] == _reference(serve_params, p, n), rid
    stats = eng.prefix_stats()
    assert stats["hit_tokens"] >= len(prefix)  # b reused a's prefix
    assert stats["inserts"] > 0
    assert eng.compiled_count == compiles


def test_engine_prefix_cache_lru_eviction_and_refcounts(serve_params):
    """Pool pressure: distinct prefixes overflow a tiny pool -> LRU
    eviction of unreferenced blocks; an evicted prefix re-misses and
    still decodes exactly; refcounts drop to zero after completion."""
    from ray_lightning_tpu.serve.engine import DecodeEngine

    eng = DecodeEngine(
        serve_params, SERVE_CFG, num_slots=1, max_seq=64,
        prefill_buckets=[8, 16], prefill_chunk=4, prefix_blocks=3,
        prefix_block=4, decode_fold=1,
    )
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, 97, size=12).tolist() for _ in range(4)]
    for i, p in enumerate(prompts):
        outs = {f"p{i}": []}
        eng.admit(p, request_id=f"p{i}", max_new_tokens=4)
        _drive_engine(eng, outs)
        assert p + outs[f"p{i}"] == _reference(serve_params, p, 4)
    stats = eng.prefix_stats()
    assert stats["evictions"] > 0  # 4 prompts x 2+ blocks into 3 slots
    assert stats["blocks_used"] == stats["blocks_total"] == 3
    assert all(m is None or m.refs == 0 for m in eng._pool_meta)
    # The first prompt's blocks were evicted; it must re-run exactly.
    outs = {"again": []}
    eng.admit(prompts[0], request_id="again", max_new_tokens=6)
    _drive_engine(eng, outs)
    assert prompts[0] + outs["again"] == _reference(
        serve_params, prompts[0], 6
    )


def test_engine_mid_prefill_cancel_and_recycle(serve_params):
    """Cancel landing strictly INSIDE a chunked prefill: the state
    machine drops, pinned prefix blocks unref, the slot recycles, and the
    next tenant — admitted into the half-prefilled slot — decodes
    bit-identically (partial rows leak nothing)."""
    from ray_lightning_tpu.serve.engine import DecodeEngine

    eng = DecodeEngine(
        serve_params, SERVE_CFG, num_slots=1, max_seq=64,
        prefill_buckets=[8, 16], prefill_chunk=4, prefix_blocks=4,
        prefix_block=4, decode_fold=2,
    )
    compiles = eng.compiled_count
    rng = np.random.default_rng(5)
    victim = rng.integers(0, 97, size=12).tolist()
    slot, tok, done = eng.admit(
        victim, request_id="victim", max_new_tokens=8
    )
    assert tok is None and not done
    assert eng.prefill_step(1) == []  # one chunk in, prefill unfinished
    eng.release(slot)  # mid-prefill cancel
    assert eng.num_active == 0 and eng.free_slots() == [0]
    assert all(m is None or m.refs == 0 for m in eng._pool_meta)
    nxt = rng.integers(0, 97, size=7).tolist()
    slot2, _, _ = eng.admit(nxt, request_id="next", max_new_tokens=7)
    assert slot2 == slot  # same slot, recycled mid-prefill
    outs = {"next": []}
    _drive_engine(eng, outs)
    assert nxt + outs["next"] == _reference(serve_params, nxt, 7)
    assert eng.compiled_count == compiles


def test_scheduler_chunked_under_load_and_prefill_metrics(serve_params):
    """8 overlapping requests (half sharing a prefix) through a chunked +
    prefix-cached engine driven by the scheduler's chunk-vs-fold
    interleave budget: outputs exact, and the stats payload carries the
    TTFT queue/prefill breakdown, prefix hit rate, and chunks-per-admit."""
    from ray_lightning_tpu.serve.engine import DecodeEngine
    from ray_lightning_tpu.serve.scheduler import SamplingParams, Scheduler

    eng = DecodeEngine(
        serve_params, SERVE_CFG, num_slots=3, max_seq=48,
        prefill_buckets=[8, 16], prefill_chunk=4, prefix_blocks=8,
        prefix_block=4, decode_fold=4,
    )
    sched = Scheduler(
        eng, max_prefills_per_step=2, max_prefill_chunks_per_step=2
    )
    rng = np.random.default_rng(6)
    shared = rng.integers(0, 97, size=8).tolist()
    reqs = {}
    for i in range(8):
        if i % 2:
            p = shared + rng.integers(
                0, 97, size=int(rng.integers(2, 6))
            ).tolist()
        else:
            p = rng.integers(0, 97, size=int(rng.integers(3, 12))).tolist()
        n = int(rng.integers(2, 9))
        rid = sched.submit(p, SamplingParams(max_new_tokens=n))
        reqs[rid] = (p, n, [])
    for ev in sched.run_until_idle():
        if ev.token is not None:
            reqs[ev.request_id][2].append(ev.token)
    assert not sched.has_work()
    for rid, (p, n, toks) in reqs.items():
        assert p + toks == _reference(serve_params, p, n)
    snap = sched.metrics.snapshot()
    assert snap["admitted"] == 8 and snap["finished"] == 8
    assert snap["ttft_p50_s"] >= snap["ttft_prefill_p50_s"] >= 0
    assert snap["ttft_queue_p50_s"] >= 0
    assert snap["prefix_hit_rate"] > 0  # the shared-prefix half hit
    assert snap["prefill_chunks_per_admit"] >= 1
    assert snap["ttft_p95_s"] >= snap["ttft_p50_s"]


def test_scheduler_cancel_racing_same_fold_finish_is_purged(
    serve_params, monkeypatch
):
    """Satellite regression: a cancel landing while step() is in its
    lock-free engine section, for a request finishing in that same fold,
    must not pin the id in _cancelled forever — a later request REUSING
    the id would be spuriously evicted."""
    from ray_lightning_tpu.serve.engine import DecodeEngine
    from ray_lightning_tpu.serve.scheduler import SamplingParams, Scheduler

    eng = DecodeEngine(
        serve_params, SERVE_CFG, num_slots=1, max_seq=48,
        prefill_buckets=[8],
    )
    sched = Scheduler(eng)
    sched.submit([1, 2, 3], SamplingParams(max_new_tokens=2),
                 request_id="dup")
    orig_step = eng.step
    fired = {"n": 0}

    def racy_step():
        # The cancel lands INSIDE the scheduler's engine section — after
        # this step's eviction scan, during the fold that finishes "dup"
        # (admission emitted token 1; this fold emits token 2 = done).
        if fired["n"] == 0:
            assert sched.cancel("dup")
        fired["n"] += 1
        return orig_step()

    monkeypatch.setattr(eng, "step", racy_step)
    evs = sched.step()  # admit + finishing fold, cancel racing inside
    assert any(
        ev.request_id == "dup" and ev.done and ev.token is not None
        for ev in evs
    )
    # The leak: without the end-of-step purge this id stays forever.
    assert "dup" not in sched._cancelled
    # And an id reuse is NOT spuriously evicted.
    sched.submit([4, 5, 6], SamplingParams(max_new_tokens=2),
                 request_id="dup")
    evs = sched.run_until_idle()
    assert all(ev.reason != "cancelled" for ev in evs)
    assert any(ev.request_id == "dup" and ev.done for ev in evs)


def test_scheduler_priority_aging_prevents_starvation(serve_params):
    """Satellite: under a sustained priority-0 stream a priority-5
    request starves forever with the pure (priority, seq) heap; with
    priority_age_s it ages to 0 and admits ahead of younger arrivals."""
    from ray_lightning_tpu.serve.engine import DecodeEngine
    from ray_lightning_tpu.serve.scheduler import SamplingParams, Scheduler

    sp = SamplingParams(max_new_tokens=1)  # done at admission: slot churns

    def drive(age):
        eng = DecodeEngine(
            serve_params, SERVE_CFG, num_slots=1, max_seq=48,
            prefill_buckets=[8],
        )
        sched = Scheduler(eng, priority_age_s=age)
        starved = sched.submit([1, 2, 3], sp, priority=5)
        first_tokens = []
        for i in range(6):
            sched.submit([4 + i, 5, 6], sp, priority=0)  # sustained p0s
            for ev in sched.step():
                if ev.token is not None:
                    first_tokens.append(ev.request_id)
        return starved, first_tokens

    starved, order = drive(None)
    assert starved not in order  # control: pure priority starves it
    starved, order = drive(1e-6)
    assert starved in order  # aged to priority 0 -> admitted
    # FIFO within the aged priority: it outranks the younger p0s.
    assert order.index(starved) == 0


def test_scheduler_folded_under_load_and_latency_metrics(serve_params):
    """8 overlapping requests through a folded (K=4) pipelined engine:
    outputs exact under queueing + continuous batching, and the stats
    payload carries the decode-latency observability fields."""
    from ray_lightning_tpu.serve.engine import DecodeEngine
    from ray_lightning_tpu.serve.scheduler import SamplingParams, Scheduler

    eng = DecodeEngine(
        serve_params, SERVE_CFG, num_slots=3, max_seq=48,
        prefill_buckets=[8, 16], decode_fold=4,
    )
    sched = Scheduler(eng, max_prefills_per_step=2)
    rng = np.random.default_rng(2)
    reqs = {}
    for i in range(8):
        p = rng.integers(0, 97, size=int(rng.integers(3, 12))).tolist()
        n = int(rng.integers(2, 9))
        rid = sched.submit(p, SamplingParams(max_new_tokens=n))
        reqs[rid] = (p, n, [])
    events = sched.run_until_idle()
    for ev in events:
        if ev.token is not None:
            reqs[ev.request_id][2].append(ev.token)
    assert not sched.has_work()
    for rid, (p, n, toks) in reqs.items():
        assert p + toks == _reference(serve_params, p, n)
    snap = sched.metrics.snapshot()
    assert snap["admitted"] == 8 and snap["finished"] == 8
    assert snap["decode_tokens_per_sec"] > 0
    assert snap["step_time_p50_s"] > 0
    assert snap["step_time_p95_s"] >= snap["step_time_p50_s"]
    assert snap["inter_token_p50_s"] > 0


# -- speculative decoding ----------------------------------------------
#: Tiny draft model for spec='model': different seed, different shape —
#: its proposals owe the main model nothing, so these tests prove the
#: drafter-agnostic contract (a bad drafter changes speed, never tokens).
DRAFT_CFG = GPTConfig(
    vocab_size=97,
    n_layer=1,
    n_head=2,
    d_model=16,
    max_seq=48,
    attn_impl="reference",
    compute_dtype="float32",
)


@pytest.fixture(scope="module")
def draft_params():
    import jax

    return init_gpt_params(jax.random.PRNGKey(7), DRAFT_CFG)


def _spec_kwargs(spec, depth, draft_params):
    kw = dict(spec=spec, spec_depth=depth)
    if spec == "model":
        kw.update(
            spec_params=draft_params, spec_config=DRAFT_CFG, spec_window=16
        )
    return kw


@pytest.mark.parametrize("spec", ["ngram", "model"])
@pytest.mark.parametrize("depth", [2, 4])
@pytest.mark.parametrize("fold", [1, 4])
def test_engine_spec_matches_sequential_generate(
    serve_params, draft_params, spec, depth, fold
):
    """The speculative acceptance matrix (spec x depth x decode_fold):
    propose-then-verify emits 1..depth+1 tokens per verify, yet every
    greedy output stays bit-identical to solo gpt_generate — a
    mid-flight join included — and a SAMPLED batchmate draws the
    identical rng chain (each emission consumes exactly one key split,
    sampled from verify logits of already-verified inputs). Compile
    count frozen across admissions and speculative folds."""
    from ray_lightning_tpu.serve.engine import DecodeEngine

    eng = DecodeEngine(
        serve_params, SERVE_CFG, num_slots=3, max_seq=64,
        prefill_buckets=[8, 16], decode_fold=fold,
        **_spec_kwargs(spec, depth, draft_params),
    )
    compiles = eng.compiled_count
    rng = np.random.default_rng(0)
    reqs = [
        (rng.integers(0, 97, size=5).tolist(), 7),
        (rng.integers(0, 97, size=8).tolist(), 4),
        (rng.integers(0, 97, size=11).tolist(), 9),
    ]
    outs = {}
    for i, (p, n) in enumerate(reqs):
        _, tok, done = eng.admit(p, request_id=f"r{i}", max_new_tokens=n)
        outs[f"r{i}"] = [tok]
        assert not done
    joined = False
    for _ in range(100):
        if not eng.num_active:
            break
        for _, rid, tok, _ in eng.step():
            outs[rid].append(tok)
        if not joined and eng.free_slots():
            p4 = rng.integers(0, 97, size=6).tolist()
            _, tok, _ = eng.admit(p4, request_id="r3", max_new_tokens=5)
            outs["r3"] = [tok]
            reqs.append((p4, 5))
            joined = True
    assert joined and eng.num_active == 0
    for i, (p, n) in enumerate(reqs):
        assert p + outs[f"r{i}"] == _reference(serve_params, p, n), f"r{i}"
    assert eng.compiled_count == compiles
    # The speculative path really ran (every decode emission rode a
    # verify) and its accounting is sane.
    st = eng.spec_stats()
    assert st["verifies"] > 0
    assert 0.0 <= st["accept_rate"] <= 1.0
    assert 1.0 <= st["tokens_per_verify"] <= depth + 1
    # Sampled chain identity: the same sampled request alone vs sharing
    # speculative folds with a greedy batchmate.
    def sampled_run(with_companion):
        e2 = DecodeEngine(
            serve_params, SERVE_CFG, num_slots=2, max_seq=48,
            prefill_buckets=[8], decode_fold=fold,
            **_spec_kwargs(spec, depth, draft_params),
        )
        _, tok, _ = e2.admit(
            list(range(1, 7)), request_id="s", max_new_tokens=8,
            temperature=0.8, top_k=20, top_p=0.9, seed=123,
        )
        toks = [tok]
        if with_companion:
            e2.admit([9, 8, 7], request_id="c", max_new_tokens=8)
        while e2.num_active:
            for _, rid, tok, _ in e2.step():
                if rid == "s":
                    toks.append(tok)
        return toks

    assert sampled_run(False) == sampled_run(True)


def test_engine_spec_eos_inside_accepted_block(serve_params):
    """EOS landing mid-accept-scan: the prompt's greedy continuation is
    a long constant run with one transition, so the n-gram drafter
    accepts 4-token blocks (every verify of the run emits depth + 1
    tokens, from index 1 on) until a verify meets the transition value —
    the eos — with accepted drafts before it in the SAME verify and
    proposals after it discarded. The slot must freeze exactly there (no
    post-EOS emission from the remaining scan indices or fold
    iterations), and a batchmate decodes through the same speculative
    folds unperturbed."""
    from ray_lightning_tpu.serve.engine import DecodeEngine

    def run_then_new_value_mid_verify(s):
        t = next((j for j in range(1, len(s)) if s[j] != s[0]), None)
        # scan index of the transition inside its verify: 1..3 of 0..4
        return t is not None and t >= 6 and (t - 1) % 5 in (1, 2, 3)

    prompt, solo = _prompt_whose_greedy(
        serve_params, run_then_new_value_mid_verify, 20
    )
    t = next(j for j in range(1, 20) if solo[j] != solo[0])
    # Precondition (locks the construction): a constant run, then a
    # value not seen before at index t.
    assert solo[:t] == [solo[0]] * t and solo[t] not in solo[:t]
    eos = solo[t]
    eng = DecodeEngine(
        serve_params, SERVE_CFG, num_slots=2, max_seq=64,
        prefill_buckets=[8, 16], decode_fold=2, spec="ngram",
        spec_depth=4,
    )
    _, tok, done = eng.admit(
        prompt, request_id="e", max_new_tokens=20, eos_token=eos
    )
    toks = [tok]
    assert not done
    mate_prompt = list(range(20, 31))
    _, mtok, _ = eng.admit(mate_prompt, request_id="m", max_new_tokens=9)
    mtoks = [mtok]
    while eng.num_active:
        for _, rid, tok, _ in eng.step():
            (toks if rid == "e" else mtoks).append(tok)
    assert toks == solo[: t + 1]  # stopped AT eos, mid-scan, mid-fold
    assert mate_prompt + mtoks == _reference(serve_params, mate_prompt, 9)
    st = eng.spec_stats()
    # The run really was speculative: whole draft blocks were accepted
    # (the eos verify alone carries 4 accepted tokens before the eos).
    assert st["accepted_tokens"] >= 4
    assert st["tokens_per_verify"] > 1.0
    state = eng.device_state()  # sync point: device agrees nothing runs
    assert not state["active"].any()


def test_engine_spec_cancel_verify_in_flight_and_recycle(serve_params):
    """Fold-boundary cancel with a speculative verify already in flight
    (pipeline on): the zombie verify's tokens are dropped at harvest
    (none surface, none count toward accept stats), the slot recycles,
    the next tenant of the same slot — admitted over the stale token
    history — decodes bit-identically, and a SAMPLED surviving batchmate
    's rng chain is untouched by its neighbour's cancel + recycle."""
    from ray_lightning_tpu.serve.engine import DecodeEngine

    def survivor_solo():
        eng = DecodeEngine(
            serve_params, SERVE_CFG, num_slots=1, max_seq=64,
            prefill_buckets=[8, 16], decode_fold=4, spec="ngram",
            spec_depth=3,
        )
        _, tok, _ = eng.admit(
            list(range(1, 7)), request_id="s", max_new_tokens=12,
            temperature=0.8, top_k=20, top_p=0.9, seed=123,
        )
        toks = [tok]
        while eng.num_active:
            for _, _, tok, _ in eng.step():
                toks.append(tok)
        return toks

    solo = survivor_solo()
    eng = DecodeEngine(
        serve_params, SERVE_CFG, num_slots=2, max_seq=64,
        prefill_buckets=[8, 16], decode_fold=4, spec="ngram",
        spec_depth=3,
    )
    compiles = eng.compiled_count
    slot_s, tok_s, _ = eng.admit(
        list(range(1, 7)), request_id="s", max_new_tokens=12,
        temperature=0.8, top_k=20, top_p=0.9, seed=123,
    )
    stoks = [tok_s]
    slot_v, _, _ = eng.admit(
        list(range(40, 48)), request_id="victim", max_new_tokens=30
    )
    for _, rid, tok, _ in eng.step():  # fold harvested, next in flight
        if rid == "s":
            stoks.append(tok)
    eng.release(slot_v)  # cancel while the speculative verify executes
    assert eng.free_slots() == [slot_v]
    nxt = list(range(60, 66))
    slot2, ntok, _ = eng.admit(nxt, request_id="next", max_new_tokens=7)
    assert slot2 == slot_v  # same slot, recycled under spec
    ntoks = [ntok]
    seen_rids = set()
    while eng.num_active:
        for _, rid, tok, _ in eng.step():
            seen_rids.add(rid)
            if rid == "s":
                stoks.append(tok)
            elif rid == "next":
                ntoks.append(tok)
    assert "victim" not in seen_rids  # no zombie tokens surface
    assert nxt + ntoks == _reference(serve_params, nxt, 7)
    assert stoks == solo  # survivor's sampled rng chain unchanged
    assert eng.compiled_count == compiles


def test_scheduler_spec_metrics_and_replica_stats(
    start_fabric, tmp_path, serve_params
):
    """Spec accounting end to end: the scheduler diffs the engine's
    accept counters into ServeMetrics (snapshot carries spec_accept_rate
    in [0, 1] and draft_tokens_per_verify = depth), and a ServeReplica
    built with spec='ngram' serves exact outputs while its stats RPC
    ships spec_stats."""
    from ray_lightning_tpu.serve import start_replicas
    from ray_lightning_tpu.serve.engine import DecodeEngine
    from ray_lightning_tpu.serve.scheduler import SamplingParams, Scheduler

    eng = DecodeEngine(
        serve_params, SERVE_CFG, num_slots=2, max_seq=48,
        prefill_buckets=[8, 16], decode_fold=2, spec="ngram", spec_depth=3,
    )
    sched = Scheduler(eng, max_prefills_per_step=2)
    rng = np.random.default_rng(2)
    reqs = {}
    for i in range(4):
        p = rng.integers(0, 97, size=int(rng.integers(3, 12))).tolist()
        n = int(rng.integers(4, 9))
        rid = sched.submit(p, SamplingParams(max_new_tokens=n))
        reqs[rid] = (p, n, [])
    for ev in sched.run_until_idle():
        if ev.token is not None:
            reqs[ev.request_id][2].append(ev.token)
    for rid, (p, n, toks) in reqs.items():
        assert p + toks == _reference(serve_params, p, n)
    snap = sched.metrics.snapshot()
    assert 0.0 <= snap["spec_accept_rate"] <= 1.0
    assert snap["draft_tokens_per_verify"] == 3.0
    # Replica wiring: spec knobs ride the RPC surface end to end.
    start_fabric(num_cpus=4)
    ckpt = _write_ckpt(tmp_path, serve_params)
    client = start_replicas(
        1,
        ckpt_path=ckpt,
        num_slots=2,
        prefill_buckets=[8, 16],
        spec="ngram",
        spec_depth=4,
        env={"JAX_PLATFORMS": "cpu"},
    )
    try:
        p = list(range(1, 8))
        out = client.generate(p, max_new_tokens=8, timeout_s=120)
        assert p + out == _reference(serve_params, p, 8)
        (snap,) = client.stats()
        assert snap["spec"] == "ngram"
        assert snap["spec_stats"]["verifies"] > 0
        assert 0.0 <= snap["spec_stats"]["accept_rate"] <= 1.0
        assert snap["compiles_since_init"] == 0
    finally:
        client.shutdown()


def _write_ckpt(tmp_path, params):
    import dataclasses

    from ray_lightning_tpu.utils.state_stream import (
        state_stream_to_file,
        to_state_stream,
    )

    path = os.path.join(tmp_path, "serve.ckpt")
    state_stream_to_file(
        to_state_stream(
            {"params": params, "gpt_config": dataclasses.asdict(SERVE_CFG)}
        ),
        path,
    )
    return path


def test_replica_e2e_streaming_and_stats(
    start_fabric, tmp_path, serve_params
):
    """The acceptance smoke: a replica actor on the local fabric, >= 8
    overlapping requests through the client, streamed tokens, non-zero
    occupancy and tokens/s from the stats endpoint — outputs exact."""
    from ray_lightning_tpu.serve import start_replicas

    start_fabric(num_cpus=4)
    ckpt = _write_ckpt(tmp_path, serve_params)
    client = start_replicas(
        1,
        ckpt_path=ckpt,
        num_slots=4,
        prefill_buckets=[8, 16],
        max_prefills_per_step=2,
        env={"JAX_PLATFORMS": "cpu"},
    )
    try:
        rng = np.random.default_rng(3)
        jobs = []
        for i in range(8):  # all submitted BEFORE any stream is drained
            p = rng.integers(0, 97, size=int(rng.integers(3, 12))).tolist()
            n = int(rng.integers(2, 8))
            jobs.append((p, n, client.submit(p, max_new_tokens=n)))
        for p, n, handle in jobs:
            streamed = list(client.stream_handle(handle, timeout_s=120))
            assert p + streamed == _reference(serve_params, p, n)
        (snap,) = client.stats()
        assert snap["admitted"] == 8 and snap["finished"] == 8
        assert snap["occupancy"] > 0
        assert snap["tokens_per_sec"] > 0
        assert snap["queue_depth"] == 0
        assert "ttft_p50_s" in snap
    finally:
        client.shutdown()


def test_replica_int8_and_cancel(start_fabric, tmp_path, serve_params):
    from ray_lightning_tpu.serve import start_replicas
    from ray_lightning_tpu.utils.quantize import quantize_params_int8

    start_fabric(num_cpus=4)
    ckpt = _write_ckpt(tmp_path, serve_params)
    client = start_replicas(
        1,
        ckpt_path=ckpt,
        int8=True,
        num_slots=2,
        prefill_buckets=[8],
        env={"JAX_PLATFORMS": "cpu"},
    )
    try:
        qparams = quantize_params_int8(serve_params)
        p = list(range(1, 8))
        out = client.generate(p, max_new_tokens=6, timeout_s=120)
        assert p + out == _reference(qparams, p, 6)
        (snap,) = client.stats()
        assert snap["int8"] is True
        # Cancel a long request mid-stream.
        h = client.submit([2, 3, 4], max_new_tokens=30)
        assert client.cancel(h)
        with pytest.raises((RuntimeError, KeyError)):
            list(client.stream_handle(h, timeout_s=30))
    finally:
        client.shutdown()


@pytest.mark.slow
def test_cli_serve_smoke(tmp_path, serve_params):
    """``rlt serve`` end to end: load a checkpoint, serve >= 8 overlapping
    prompt lines from a file, print per-request outputs and a stats JSON
    with non-zero occupancy + tokens/s."""
    ckpt = _write_ckpt(tmp_path, serve_params)
    prompts = os.path.join(tmp_path, "prompts.txt")
    rng = np.random.default_rng(4)
    lines = [
        ",".join(
            str(t)
            for t in rng.integers(0, 97, size=int(rng.integers(3, 8)))
        )
        for _ in range(8)
    ]
    with open(prompts, "w") as f:
        f.write("\n".join(lines) + "\n")
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "RLT_NUM_TPU_CHIPS": "0",
    }
    env["PYTHONPATH"] = os.pathsep.join(
        [REPO_ROOT, env.get("PYTHONPATH", "")]
    ).rstrip(os.pathsep)
    proc = subprocess.run(
        [
            sys.executable, "-m", "ray_lightning_tpu.cli", "serve",
            "--serve.ckpt_path", ckpt,
            "--serve.prompts", prompts,
            "--serve.max_new_tokens", "5",
            "--serve.num_slots", "4",
            "--serve.prefill_buckets", "[8]",
        ],
        capture_output=True,
        text=True,
        timeout=420,
        env=env,
        cwd=REPO_ROOT,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    out_lines = [ln for ln in proc.stdout.splitlines() if "\t" in ln]
    assert len(out_lines) == 8
    for line, prompt_csv in zip(out_lines, lines):
        _, csv = line.split("\t")
        toks = [int(t) for t in csv.split(",")]
        prompt = [int(t) for t in prompt_csv.split(",")]
        assert toks[: len(prompt)] == prompt
        assert len(toks) == len(prompt) + 5
    stats_line = [
        ln
        for ln in proc.stdout.splitlines()
        if ln.startswith('{"serve_stats"')
    ]
    assert stats_line, proc.stdout
    stats = json.loads(stats_line[-1])["serve_stats"]
    assert stats[0]["occupancy"] > 0
    assert stats[0]["tokens_per_sec"] > 0


# ---------------------------------------------------------------------------
# Fused piggyback dispatch + the pre-lowered fold-depth ladder
# ---------------------------------------------------------------------------
#: Chunked-prefill engine with fused prefill rows riding the decode
#: fold: the exactness matrix below must be indistinguishable from the
#: separate-dispatch engine, token for token.
PB_KW = dict(
    num_slots=3, max_seq=64, prefill_buckets=[16], prefill_chunk=4,
    decode_fold=2, piggyback_chunks=2,
)


def _run_sched_workload(params, engine_kw, seed=11, n_reqs=6):
    """Scheduler-driven mixed workload; asserts the compile count is
    frozen at construction and returns (engine, {rid: (p, n, toks)})."""
    from ray_lightning_tpu.serve.engine import DecodeEngine
    from ray_lightning_tpu.serve.scheduler import SamplingParams, Scheduler

    eng = DecodeEngine(params, SERVE_CFG, **engine_kw)
    compiles_before = eng.compiled_count
    sched = Scheduler(eng, max_prefills_per_step=2)
    rng = np.random.default_rng(seed)
    reqs = {}
    for i in range(n_reqs):
        p = rng.integers(0, 97, size=int(rng.integers(5, 14))).tolist()
        n = int(rng.integers(3, 8))
        rid = sched.submit(p, SamplingParams(max_new_tokens=n))
        reqs[rid] = (p, n, [])
    for ev in sched.run_until_idle():
        if ev.token is not None:
            reqs[ev.request_id][2].append(ev.token)
    assert not sched.has_work()
    assert eng.compiled_count == compiles_before
    return eng, reqs


def test_piggyback_fused_dispatch_bit_exact(serve_params):
    """Piggyback ON vs OFF over the same workload: both bit-identical
    to solo gpt_generate (so to each other), with the fused engine
    actually folding chunk rows into decode dispatches (counters move)
    and the separate-dispatch engine never doing so."""
    off_kw = {k: v for k, v in PB_KW.items() if k != "piggyback_chunks"}
    eng_off, reqs_off = _run_sched_workload(serve_params, off_kw)
    eng_on, reqs_on = _run_sched_workload(serve_params, PB_KW)
    for eng, reqs in ((eng_off, reqs_off), (eng_on, reqs_on)):
        for rid, (p, n, toks) in reqs.items():
            assert p + toks == _reference(serve_params, p, n), rid
    assert eng_off.piggyback_dispatches == 0
    assert eng_on.piggyback_dispatches > 0
    assert eng_on.piggyback_chunk_rows >= eng_on.piggyback_dispatches


def test_piggyback_spec_ngram_bit_exact(serve_params):
    """Speculative decoding under fused dispatch: drafter + verify +
    piggybacked chunk rows in one executable, still bit-exact."""
    eng, reqs = _run_sched_workload(
        serve_params, dict(PB_KW, spec="ngram", spec_depth=2), seed=13
    )
    for rid, (p, n, toks) in reqs.items():
        assert p + toks == _reference(serve_params, p, n), rid
    assert eng.piggyback_dispatches > 0


def test_fold_ladder_switches_mid_stream_zero_compiles(serve_params):
    """The pre-lowered fold-depth ladder: a second admission wave lands
    mid-stream, forcing the rung back down for piggyback rows, then back
    up as the queue drains — at least two rungs dispatched, greedy
    output exact, and ZERO backend compiles inside the serving window
    (the real compile listener, not the engine's own counter)."""
    from ray_lightning_tpu.obs.jaxmon import install_compile_listener
    from ray_lightning_tpu.serve.engine import DecodeEngine
    from ray_lightning_tpu.serve.scheduler import SamplingParams, Scheduler

    rng = np.random.default_rng(29)
    wave1 = [
        (rng.integers(0, 97, size=9).tolist(), 8),
        (rng.integers(0, 97, size=6).tolist(), 7),
    ]
    wave2 = [
        (rng.integers(0, 97, size=12).tolist(), 6),
        (rng.integers(0, 97, size=7).tolist(), 5),
    ]
    # References compile OUTSIDE the listener window.
    expected = {
        f"w{i}": _reference(serve_params, p, n)
        for i, (p, n) in enumerate(wave1 + wave2)
    }
    stats = install_compile_listener()
    eng = DecodeEngine(
        serve_params, SERVE_CFG,
        **dict(PB_KW, piggyback_chunks=3, fold_ladder=[1, 2, 4]),
    )
    sched = Scheduler(eng, max_prefills_per_step=2)
    baseline = stats.count("backend_compile")
    outs = {}
    for i, (p, n) in enumerate(wave1):
        rid = sched.submit(p, SamplingParams(max_new_tokens=n),
                           request_id=f"w{i}")
        outs[rid] = []
    for _ in range(4):  # wave 1 prefills drain; deep rungs take over
        for ev in sched.step():
            if ev.token is not None:
                outs[ev.request_id].append(ev.token)
    for j, (p, n) in enumerate(wave2):  # mid-stream: rung forced shallow
        rid = sched.submit(p, SamplingParams(max_new_tokens=n),
                           request_id=f"w{len(wave1) + j}")
        outs[rid] = []
    for ev in sched.run_until_idle():
        if ev.token is not None:
            outs[ev.request_id].append(ev.token)
    # The compile window closes BEFORE any reference re-run (the
    # precomputed `expected` keeps gpt_generate's own compiles out).
    assert stats.count("backend_compile") == baseline
    rungs_used = [k for k, v in eng.fold_dispatches.items() if v > 0]
    assert len(rungs_used) >= 2, eng.fold_dispatches
    for i, (p, n) in enumerate(wave1 + wave2):
        assert p + outs[f"w{i}"] == expected[f"w{i}"], f"w{i}"


def test_piggyback_cancel_mid_fold(serve_params):
    """A piggybacked prefill cancelled BETWEEN fused dispatches: the
    boundary eviction drops its chunk state machine, its terminal reads
    `cancelled`, the survivors stay bit-exact, and no compile moves."""
    from ray_lightning_tpu.serve.engine import DecodeEngine
    from ray_lightning_tpu.serve.scheduler import SamplingParams, Scheduler

    eng = DecodeEngine(serve_params, SERVE_CFG, **PB_KW)
    compiles_before = eng.compiled_count
    sched = Scheduler(eng, max_prefills_per_step=2)
    rng = np.random.default_rng(31)
    p_keep = rng.integers(0, 97, size=5).tolist()
    p_dead = rng.integers(0, 97, size=13).tolist()  # 4 chunks of 4
    keep = sched.submit(p_keep, SamplingParams(max_new_tokens=8),
                        request_id="keep")
    outs = {keep: []}
    for _ in range(3):  # `keep` admits and starts decoding
        for ev in sched.step():
            if ev.token is not None:
                outs[ev.request_id].append(ev.token)
    dead = sched.submit(p_dead, SamplingParams(max_new_tokens=6),
                        request_id="dead")
    evs = sched.step()  # one fused dispatch carries a `dead` chunk row
    assert not any(e.done for e in evs if e.request_id == dead)
    assert eng.piggyback_dispatches > 0
    assert sched.cancel(dead)
    tail = sched.run_until_idle()
    for ev in evs + tail:
        if ev.token is not None:
            outs.setdefault(ev.request_id, []).append(ev.token)
    assert "cancelled" in [
        e.reason for e in tail if e.request_id == dead and e.done
    ]
    assert p_keep + outs[keep] == _reference(serve_params, p_keep, 8)
    assert eng.num_active == 0 and not sched.has_work()
    assert eng.compiled_count == compiles_before
