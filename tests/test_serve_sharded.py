"""Mesh-sharded serving engine tests: tensor-parallel decode across chips.

The load-bearing property is the same oracle that made PRs 2-6 safe to
verify, carried onto the mesh: with attention heads and the KV cache
sharded over a "model" axis, greedy output stays BIT-IDENTICAL to the
single-device engine for the same model/config (the sharded contractions
reassociate partial sums at the ~1e-7 level, orders of magnitude under
fp32 greedy argmax margins), and the compile count stays frozen at
construction (``compiles_since_init == 0`` in steady state with sharding
on). Asserted across {plain, chunked prefill + prefix hit, spec=ngram}.

The multi-device CPU mesh comes from conftest.py's session-scoped env
guard (``XLA_FLAGS=--xla_force_host_platform_device_count=8`` before any
jax import); the fixture below verifies the flag actually took effect
and skips cleanly when it could not (e.g. jax initialized earlier with
different flags in an embedding process).
"""
import numpy as np
import pytest

from ray_lightning_tpu.models.gpt import (
    GPTConfig,
    gpt_generate,
    init_gpt_params,
)

#: MHA on purpose (n_kv_head == n_head == 4): a model axis of 4 must
#: divide BOTH head counts; the GQA-divisibility rejection has its own
#: test below. fp32 + reference attention: the exactness-contract config.
SHARD_CFG = GPTConfig(
    vocab_size=97,
    n_layer=2,
    n_head=4,
    d_model=32,
    max_seq=64,
    attn_impl="reference",
    compute_dtype="float32",
)

#: The serving mesh under test: model=4 shards heads/KV four ways, the
#: data axis exercises the "extra axis stays replicated" path.
MESH_SHAPE = (4, 2)


@pytest.fixture(scope="module")
def tp_mesh():
    """A ("model", "data") mesh over the forced host devices; skips
    cleanly when the virtual-device flag could not take effect."""
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


@pytest.fixture(scope="module")
def shard_params():
    import jax

    return init_gpt_params(jax.random.PRNGKey(0), SHARD_CFG)


def _reference(params, prompt, n):
    out = gpt_generate(
        params, SHARD_CFG, np.asarray(prompt, np.int32)[None], n
    )
    return np.asarray(out)[0].tolist()


def _drive(eng, outs):
    """Run an engine to idle, collecting tokens per request id (chunked
    prefills interleaved with decode folds, like the scheduler does)."""
    while eng.num_active:
        for _, task, tok, _ in eng.prefill_step(1):
            outs[task.request_id].append(tok)
        for _, rid, tok, _ in eng.step():
            outs[rid].append(tok)


def _run_workload(eng, reqs, join=None):
    """Admit ``reqs`` [(prompt, n), ...], drive to idle with an optional
    mid-flight join; returns {request_id: [tokens]}."""
    outs = {}
    for i, (p, n) in enumerate(reqs):
        _, tok, done = eng.admit(p, request_id=f"r{i}", max_new_tokens=n)
        outs[f"r{i}"] = [] if tok is None else [tok]
        assert not done
    joined = join is None
    for _ in range(300):
        if not eng.num_active:
            break
        for _, task, tok, _ in eng.prefill_step(1):
            outs[task.request_id].append(tok)
        for _, rid, tok, _ in eng.step():
            outs[rid].append(tok)
        if not joined and eng.free_slots():
            p4, n4 = join
            _, tok, _ = eng.admit(
                p4, request_id=f"r{len(reqs)}", max_new_tokens=n4
            )
            outs[f"r{len(reqs)}"] = [] if tok is None else [tok]
            reqs.append((p4, n4))
            joined = True
    assert joined and eng.num_active == 0
    return outs


def _engine(params, mesh, **kw):
    from ray_lightning_tpu.serve.engine import DecodeEngine

    return DecodeEngine(params, SHARD_CFG, mesh=mesh, **kw)


def test_sharded_engine_plain_bit_identical_and_frozen_compiles(
    tp_mesh, shard_params
):
    """The acceptance oracle, plain config: mixed lengths + a mid-flight
    join through the tp-sharded engine — greedy output bit-identical to
    the single-device engine AND to solo gpt_generate, with ZERO backend
    compiles in steady state (sharding on, measured by the real compile
    listener, not just the engine's own counter)."""
    from ray_lightning_tpu.obs.jaxmon import install_compile_listener

    rng = np.random.default_rng(0)
    reqs = [
        (rng.integers(0, 97, size=5).tolist(), 7),
        (rng.integers(0, 97, size=8).tolist(), 4),
        (rng.integers(0, 97, size=11).tolist(), 9),
    ]
    join = (rng.integers(0, 97, size=6).tolist(), 5)
    kw = dict(num_slots=3, max_seq=64, prefill_buckets=[8, 16],
              decode_fold=2)

    stats = install_compile_listener()
    eng = _engine(shard_params, tp_mesh, **kw)
    compiled = eng.compiled_count
    base = stats.count("backend_compile")
    sharded = _run_workload(eng, list(reqs), join=join)
    # The whole workload — admissions, folds, evictions, the join — ran
    # on executables frozen at construction: zero NEW backend compiles.
    assert stats.count("backend_compile") == base
    assert eng.compiled_count == compiled

    single = _run_workload(
        _engine(shard_params, None, **kw), list(reqs), join=join
    )
    assert sharded == single  # bit-identical, token for token
    for i, (p, n) in enumerate(list(reqs) + [join]):
        assert p + sharded[f"r{i}"] == _reference(shard_params, p, n), f"r{i}"


def test_sharded_fold_writes_the_cache_shard_locally(tp_mesh, shard_params):
    """The decode step's cache write indexes layer, slot and position;
    the sharded axis is the KV heads, so each shard writes its own heads'
    rows. Counted in the fold's compiled HLO: the two all-reduces a layer
    the sharded matmuls need (attention output, MLP output) and no other
    collective — nothing gathers or moves the cache."""
    import re
    from collections import Counter

    eng = _engine(
        shard_params, tp_mesh, num_slots=3, max_seq=64,
        prefill_buckets=[8, 16], decode_fold=2,
    )
    (text,) = [ex.as_text() for ex in eng._step_exec.values()]
    found = Counter(
        re.findall(
            r"= \S+ (all-reduce|all-gather|collective-permute|all-to-all"
            r"|reduce-scatter)(?:-start)?\(",
            text,
        )
    )
    assert dict(found) == {"all-reduce": 2 * SHARD_CFG.n_layer}


def test_sharded_engine_chunked_prefix_bit_identical(tp_mesh, shard_params):
    """Chunked prefill + a prefix-cache hit under the mesh: the suffix
    prefill seeds from pool blocks through the sharded cache-to-cache
    copy executable, and every output stays bit-identical to the
    single-device engine."""
    rng = np.random.default_rng(3)
    prefix = rng.integers(0, 97, size=8).tolist()
    reqs = [
        (prefix + rng.integers(0, 97, size=3).tolist(), 6),
        (prefix + rng.integers(0, 97, size=5).tolist(), 7),  # pool hit
        (rng.integers(0, 97, size=20).tolist(), 5),  # over-bucket miss
    ]
    kw = dict(num_slots=2, max_seq=64, prefill_buckets=[8, 16],
              prefill_chunk=4, prefix_blocks=8, prefix_block=4,
              decode_fold=2)

    results = {}
    for label, mesh in (("sharded", tp_mesh), ("single", None)):
        eng = _engine(shard_params, mesh, **kw)
        compiled = eng.compiled_count
        outs = {}
        for rid, (p, n) in enumerate(reqs):
            outs[f"r{rid}"] = []
            eng.admit(p, request_id=f"r{rid}", max_new_tokens=n)
            _drive(eng, outs)
        assert eng.compiled_count == compiled
        assert eng.prefix_stats()["hit_tokens"] >= len(prefix), label
        results[label] = outs
    assert results["sharded"] == results["single"]
    for i, (p, n) in enumerate(reqs):
        assert p + results["sharded"][f"r{i}"] == _reference(
            shard_params, p, n
        ), f"r{i}"


def test_sharded_engine_spec_ngram_bit_identical(tp_mesh, shard_params):
    """Speculative decoding under the mesh: drafter + verify + accept
    compile into the one sharded fold executable; outputs bit-identical
    to the single-device spec engine (and to gpt_generate), verifies
    really ran, compile count frozen."""
    rng = np.random.default_rng(5)
    reqs = [
        (rng.integers(0, 97, size=5).tolist(), 7),
        (rng.integers(0, 97, size=8).tolist(), 6),
    ]
    kw = dict(num_slots=2, max_seq=64, prefill_buckets=[8, 16],
              decode_fold=2, spec="ngram", spec_depth=3)

    results = {}
    for label, mesh in (("sharded", tp_mesh), ("single", None)):
        eng = _engine(shard_params, mesh, **kw)
        compiled = eng.compiled_count
        results[label] = _run_workload(eng, list(reqs))
        assert eng.compiled_count == compiled
        assert eng.spec_stats()["verifies"] > 0, label
    assert results["sharded"] == results["single"]
    for i, (p, n) in enumerate(reqs):
        assert p + results["sharded"][f"r{i}"] == _reference(
            shard_params, p, n
        ), f"r{i}"


def test_sharded_memory_stats_divide_by_model_axis(tp_mesh, shard_params):
    """memory_stats: KV cache and prefix pool per-device bytes are
    total / model-axis (measured from the live shards); slot token
    history stays replicated; ServeMetrics exports the per-device rows
    as rlt_serve_hbm_bytes{component=}."""
    from ray_lightning_tpu.obs.registry import MetricsRegistry
    from ray_lightning_tpu.serve.metrics import ServeMetrics

    model = MESH_SHAPE[0]
    eng = _engine(
        shard_params, tp_mesh, num_slots=2, max_seq=64,
        prefill_buckets=[8], prefill_chunk=4, prefix_blocks=4,
        prefix_block=4, spec="ngram", spec_depth=2,
    )
    mem = eng.memory_stats()
    assert mem["kv_cache"]["bytes"] > 0
    assert (
        mem["kv_cache"]["per_device_bytes"]
        == mem["kv_cache"]["bytes"] // model
    )
    assert (
        mem["prefix_pool"]["per_device_bytes"]
        == mem["prefix_pool"]["bytes"] // model
    )
    # Replicated components: every device holds the full array.
    assert (
        mem["token_history"]["per_device_bytes"]
        == mem["token_history"]["bytes"]
        > 0
    )
    assert mem["total"]["bytes"] == sum(
        mem[c]["bytes"]
        for c in ("kv_cache", "prefix_pool", "token_history")
    )
    # Single-device control: per-device == total for everything.
    eng1 = _engine(
        shard_params, None, num_slots=2, max_seq=64, prefill_buckets=[8]
    )
    mem1 = eng1.memory_stats()
    assert (
        mem1["kv_cache"]["per_device_bytes"] == mem1["kv_cache"]["bytes"]
    )
    # Metrics export: the per-device series, labelled by component.
    reg = MetricsRegistry()
    ServeMetrics(2, registry=reg).record_memory(mem)
    text = reg.render()
    assert "rlt_serve_hbm_bytes" in text
    assert 'component="kv_cache"' in text
    got = {
        k: v
        for k, v in reg.to_dict().items()
        if k.startswith("rlt_serve_hbm_bytes")
    }
    assert (
        got['rlt_serve_hbm_bytes{component="kv_cache"}']
        == mem["kv_cache"]["per_device_bytes"]
    )


def test_sharded_engine_rejects_indivisible_heads(tp_mesh, shard_params):
    """A mesh whose model axis cannot split the head counts rejects at
    construction, naming both numbers — before anything compiles."""
    import jax

    from ray_lightning_tpu.serve.engine import DecodeEngine

    gqa_cfg = GPTConfig(
        vocab_size=97, n_layer=2, n_head=4, n_kv_head=2, d_model=32,
        max_seq=64, attn_impl="reference", compute_dtype="float32",
    )
    gqa_params = init_gpt_params(jax.random.PRNGKey(1), gqa_cfg)
    with pytest.raises(ValueError, match="model axis.*n_kv_head"):
        DecodeEngine(
            gqa_params, gqa_cfg, num_slots=2, max_seq=64,
            prefill_buckets=[8], mesh=tp_mesh,
        )


def test_build_mesh_nonfactoring_shape_names_the_fix():
    """build_mesh's error for a shape that doesn't factor the device
    count carries the axis names, both counts, and the XLA_FLAGS hint —
    serve users now hit this from a CLI string."""
    import jax

    from ray_lightning_tpu.parallel.mesh import build_mesh

    n = len(jax.devices())
    bad = (n + 1, 1)
    with pytest.raises(ValueError) as exc:
        build_mesh(bad, ("model", "data"))
    msg = str(exc.value)
    assert f"model={n + 1}" in msg
    assert str(n) in msg and "multiply" in msg
    assert "xla_force_host_platform_device_count" in msg


def test_parse_mesh_spec_vocabulary():
    """--serve.mesh parsing: the accepted forms normalize, everything
    else rejects up front with the valid vocabulary."""
    from ray_lightning_tpu.parallel.mesh import (
        mesh_from_spec,
        parse_mesh_spec,
    )

    assert parse_mesh_spec("4x2") == (4, 2)
    assert parse_mesh_spec("4X2") == (4, 2)
    assert parse_mesh_spec("8") == (8, 1)
    assert parse_mesh_spec(8) == (8, 1)  # YAML coerces bare ints
    assert parse_mesh_spec(None) == (1, 1)
    assert mesh_from_spec("1x1") is None  # single-device fast path
    assert mesh_from_spec(None) is None
    for bad in ("potato", "4x", "x4", "0x2", "-1x1", "4x2x1", "", True):
        with pytest.raises(ValueError, match="MODELxDATA"):
            parse_mesh_spec(bad)


def test_cli_serve_rejects_malformed_mesh_before_loading():
    """run_serve validates --serve.mesh right after the key vocabulary —
    a malformed spec fails with the format named, BEFORE the (absent)
    checkpoint would have been complained about, so no checkpoint load
    or replica spawn is ever attempted."""
    from ray_lightning_tpu.cli import run_serve

    with pytest.raises(ValueError, match="MODELxDATA"):
        run_serve({"serve": {"mesh": "8y2", "ckpt_path": "/nope"}})
    # And the canonical form is accepted at parse time (failure must be
    # the missing prompts/ckpt, not the mesh).
    with pytest.raises(ValueError, match="ckpt_path"):
        run_serve({"serve": {"mesh": "4x2"}})


def test_cli_serve_mesh_forces_virtual_devices_on_cpu(
    tmp_path, monkeypatch
):
    """On a chipless fabric, run_serve must give mesh replicas the
    virtual host devices the spec needs (XLA_FLAGS in the actor env) —
    without it a --serve.mesh 4x2 replica would see one CPU device and
    reject the mesh at spawn. The mesh spec itself rides replica_kwargs
    normalized."""
    import ray_lightning_tpu.serve as serve_pkg
    from ray_lightning_tpu import fabric
    from ray_lightning_tpu.cli import run_serve

    captured = {}

    def fake_start_replicas(n, **kwargs):
        captured.update(kwargs, replicas=n)
        raise RuntimeError("stop-here")  # skip the actual serve loop

    monkeypatch.setattr(serve_pkg, "start_replicas", fake_start_replicas)
    monkeypatch.setattr(fabric, "is_initialized", lambda: True)
    monkeypatch.setattr(fabric, "cluster_resources", lambda: {"TPU": 0})
    prompts = tmp_path / "p.txt"
    prompts.write_text("1,2,3\n")
    with pytest.raises(RuntimeError, match="stop-here"):
        run_serve(
            {
                "serve": {
                    "ckpt_path": "/nope.ckpt",
                    "prompts": str(prompts),
                    "mesh": "4x2",
                }
            }
        )
    assert captured["mesh"] == "4x2"
    assert (
        captured["env"]["XLA_FLAGS"]
        == "--xla_force_host_platform_device_count=8"
    )
    assert captured["env"]["JAX_PLATFORMS"] == "cpu"


def test_gang_leader_engine_mirrors_op_stream(shard_params):
    """Multi-host lockstep contract, in-process: every device-mutating
    scheduler call the leader executes is shipped to the follower
    queues first; replaying the stream on a second identical engine
    reproduces its device state bit-for-bit (slot choice, prefix-pool
    walk, and rng advancement are deterministic functions of the op
    sequence), and close() delivers the drain sentinel."""
    import queue as _q

    from ray_lightning_tpu.serve.server import _GangLeaderEngine

    local = _q.Queue()

    class Chan:  # fabric.Queue stand-in
        def put(self, item):
            local.put(item)

    leader = _engine(
        shard_params, None, num_slots=2, max_seq=48,
        prefill_buckets=[8], decode_fold=2,
    )
    mirror = _engine(
        shard_params, None, num_slots=2, max_seq=48,
        prefill_buckets=[8], decode_fold=2,
    )
    gang = _GangLeaderEngine(leader, [Chan()])
    rng = np.random.default_rng(7)
    p1 = rng.integers(0, 97, size=6).tolist()
    p2 = rng.integers(0, 97, size=5).tolist()
    slot, _, _ = gang.admit(p1, request_id="a", max_new_tokens=6)
    gang.admit_many(
        [dict(prompt=p2, request_id="b", max_new_tokens=8)]
    )
    gang.step()
    gang.release(slot)  # mid-flight cancel rides the same stream
    while gang.num_active:
        gang.step()
    assert gang.free_slots() == leader.free_slots()  # reads delegate
    gang.close()
    ops = []
    while not local.empty():
        ops.append(local.get())
    assert ops[-1] is None  # drain sentinel
    for op in ops[:-1]:
        name, args, kwargs = op
        getattr(mirror, name)(*args, **kwargs)
    s_lead = leader.device_state()
    s_mirror = mirror.device_state()
    assert set(s_lead) == set(s_mirror)
    for k in s_lead:
        assert np.array_equal(s_lead[k], s_mirror[k]), k


def test_gang_follower_trace_propagation_stitches(shard_params):
    """Trace context crosses the gang op stream: the leader broadcasts
    engine ops carrying request ids, the follower's engine records its
    own spans under the SAME ids, and the merged export shows follower
    spans on a DISTINCT process track with every remote request id
    resolving to a client-side submit span (the PR 8 stitching contract
    on the in-process leader/follower mirror)."""
    import queue as _q

    from ray_lightning_tpu.obs.trace import (
        SPAN_CLIENT_SUBMIT,
        SPAN_PREFILL_CHUNK,
        RequestTracer,
        merge_chrome_trace,
    )
    from ray_lightning_tpu.serve.server import _GangLeaderEngine

    local = _q.Queue()

    class Chan:  # fabric.Queue stand-in
        def put(self, item):
            local.put(item)

    kw = dict(num_slots=2, max_seq=48, prefill_buckets=[16],
              prefill_chunk=4, decode_fold=2)
    leader = _engine(shard_params, None, **kw)
    mirror = _engine(shard_params, None, **kw)
    client_tracer = RequestTracer()
    leader.tracer = RequestTracer()
    mirror.tracer = RequestTracer()  # what ServeShardFollower wires up
    gang = _GangLeaderEngine(leader, [Chan()])
    rng = np.random.default_rng(11)
    for rid, size, n in (("a", 9, 5), ("b", 6, 4)):
        client_tracer.event(
            rid, SPAN_CLIENT_SUBMIT, attrs={"replica": 0}
        )
        gang.admit(
            rng.integers(0, 97, size=size).tolist(),
            request_id=rid, max_new_tokens=n,
        )
    while gang.num_active or leader._prefills:
        gang.prefill_step(2)
        gang.step()
    gang.close()
    # Replay the op stream on the mirror, exactly like the follower's
    # daemon loop does.
    while True:
        op = local.get_nowait()
        if op is None:
            break
        name, args, kwargs = op
        getattr(mirror, name)(*args, **kwargs)
    assert mirror.tracer.request_ids(), "follower recorded no spans"

    merged = merge_chrome_trace([
        {"name": "client", **client_tracer.dump()},
        {"name": "replica0", **leader.tracer.dump()},
        {"name": "follower0", **mirror.tracer.dump()},
    ])
    evs = merged["traceEvents"]
    procs = {
        e["args"]["name"]: e["pid"]
        for e in evs
        if e.get("name") == "process_name"
    }
    assert set(procs) == {"client", "replica0", "follower0"}
    assert len(set(procs.values())) == 3  # distinct process tracks
    follower_markers = [
        e for e in evs
        if e["ph"] == "i" and e["pid"] == procs["follower0"]
    ]
    assert any(
        e["name"] == SPAN_PREFILL_CHUNK for e in follower_markers
    )
    # Every span's request id — leader AND follower — resolves to a
    # client-side submit span.
    client_rids = set(client_tracer.request_ids())
    for e in evs:
        if e["ph"] == "i" and e["pid"] != procs["client"]:
            assert e["args"]["request_id"] in client_rids, e
    # And the follower recorded the SAME per-request chunk ladder as
    # the leader (the op stream is the single source of truth).
    for rid in ("a", "b"):
        lead_chunks = [
            ev for ev in leader.tracer.trace(rid)
            if ev["span"] == SPAN_PREFILL_CHUNK
        ]
        mirror_chunks = [
            ev for ev in mirror.tracer.trace(rid)
            if ev["span"] == SPAN_PREFILL_CHUNK
        ]
        assert len(lead_chunks) == len(mirror_chunks) >= 1
        assert [c["index"] for c in lead_chunks] == [
            c["index"] for c in mirror_chunks
        ]


def test_replica_stats_carry_mesh_and_memory(tp_mesh, shard_params):
    """ServeReplica with a mesh spec end to end (in-process): exact
    output, stats() ships mesh + per-component memory, and the
    frozen-compile contract holds as the compiles_since_init metric."""
    import time

    from ray_lightning_tpu.serve.server import ServeReplica

    # Reference BEFORE the replica exists: gpt_generate compiles its own
    # programs, which must not pollute the replica's compiles_since_init
    # baseline-vs-now window.
    p = list(range(1, 8))
    want = _reference(shard_params, p, 6)
    rep = ServeReplica(
        params=shard_params, model_config=SHARD_CFG, num_slots=2,
        prefill_buckets=[8, 16],
        mesh="{}x{}".format(*MESH_SHAPE),
        watchdog=False, tracing=False,
    )
    try:
        rid = rep.submit(p, max_new_tokens=6)
        deadline = time.monotonic() + 120
        cursor, toks, done = 0, [], False
        while not done and time.monotonic() < deadline:
            res = rep.result(rid, cursor, wait_s=0.2)
            toks += res["tokens"]
            cursor += len(res["tokens"])
            done = res["done"]
        assert done
        assert p + toks == want
        snap = rep.stats()
        assert snap["mesh"] == "{}x{}".format(*MESH_SHAPE)
        assert snap["compiles_since_init"] == 0
        kv = snap["memory"]["kv_cache"]
        assert kv["per_device_bytes"] == kv["bytes"] // MESH_SHAPE[0]
    finally:
        rep.stop()


def test_sharded_piggyback_fold_ladder_bit_identical_zero_compiles(
    tp_mesh, shard_params
):
    """The fused dispatch under the mesh: piggybacked chunk rows + the
    fold ladder with heads/KV sharded over "model". The rung choice and
    the piggyback plan are pure functions of the op stream, so the one
    in-process gang member here exercises the same code path every
    gang follower replays. Bit-identical to the single-device engine's
    oracle (solo gpt_generate), zero backend compiles while serving."""
    from ray_lightning_tpu.obs.jaxmon import install_compile_listener
    from ray_lightning_tpu.serve.scheduler import SamplingParams, Scheduler

    rng = np.random.default_rng(47)
    reqs = [
        (rng.integers(0, 97, size=int(rng.integers(5, 14))).tolist(),
         int(rng.integers(3, 8)))
        for _ in range(5)
    ]
    expected = {
        f"m{i}": _reference(shard_params, p, n)
        for i, (p, n) in enumerate(reqs)
    }
    stats = install_compile_listener()
    eng = _engine(
        shard_params, tp_mesh, num_slots=3, max_seq=64,
        prefill_buckets=[16], prefill_chunk=4, decode_fold=2,
        piggyback_chunks=2, fold_ladder=[1, 2],
    )
    sched = Scheduler(eng, max_prefills_per_step=2)
    baseline = stats.count("backend_compile")
    outs = {}
    for i, (p, n) in enumerate(reqs):
        rid = sched.submit(p, SamplingParams(max_new_tokens=n),
                           request_id=f"m{i}")
        outs[rid] = []
    for ev in sched.run_until_idle():
        if ev.token is not None:
            outs[ev.request_id].append(ev.token)
    assert not sched.has_work() and eng.num_active == 0
    assert stats.count("backend_compile") == baseline
    assert eng.piggyback_dispatches > 0
    for i, (p, n) in enumerate(reqs):
        assert p + outs[f"m{i}"] == expected[f"m{i}"], f"m{i}"
