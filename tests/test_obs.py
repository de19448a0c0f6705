"""Observability subsystem tests: registry/Prometheus round trips, the
metrics HTTP endpoint, request-trace lifecycle completeness (chunked
prefill x prefix hit x mid-fold cancel), trainer step-breakdown
accounting, compile-event telemetry, fabric heartbeats, and the
on-demand profiler.

The load-bearing properties: (1) every admitted request's span sequence
is WELL-FORMED — submit/queued/admitted ordering, contiguous chunk
indices, exactly one terminal event, monotonic timestamps — no matter
which admission path it took; (2) metric values survive the Prometheus
text round trip; (3) the trainer's data-wait/step/drain segments account
for the fit loop's wall time.
"""
import json
import time
import urllib.request

import numpy as np
import pytest

from ray_lightning_tpu import obs
from ray_lightning_tpu.models.gpt import GPTConfig, init_gpt_params
from ray_lightning_tpu.obs import trace as obs_trace
from ray_lightning_tpu.serve.metrics import ServeMetrics

OBS_CFG = GPTConfig(
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
def obs_params():
    import jax

    return init_gpt_params(jax.random.PRNGKey(0), OBS_CFG)


# ---------------------------------------------------------------------------
# Registry + Prometheus text format
# ---------------------------------------------------------------------------
def test_registry_render_parse_roundtrip():
    reg = obs.MetricsRegistry()
    c = reg.counter("rlt_test_events_total", "events")
    c.inc(3)
    c.inc(2, kind="a")
    g = reg.gauge("rlt_test_depth", "depth")
    g.set(7.5)
    h = reg.histogram("rlt_test_latency_seconds", "lat", buckets=[0.1, 1.0])
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    text = reg.render()
    parsed = obs.parse_prometheus_text(text)
    assert parsed["rlt_test_events_total"][""] == 3.0
    assert parsed["rlt_test_events_total"]['{kind="a"}'] == 2.0
    assert parsed["rlt_test_depth"][""] == 7.5
    # Histogram: cumulative buckets, sum, count all survive the wire.
    assert parsed["rlt_test_latency_seconds_bucket"]['{le="0.1"}'] == 1.0
    assert parsed["rlt_test_latency_seconds_bucket"]['{le="1"}'] == 2.0
    assert parsed["rlt_test_latency_seconds_bucket"]['{le="+Inf"}'] == 3.0
    assert parsed["rlt_test_latency_seconds_count"][""] == 3.0
    assert abs(parsed["rlt_test_latency_seconds_sum"][""] - 5.55) < 1e-9
    # Registration is idempotent; kind mismatch is an error.
    assert reg.counter("rlt_test_events_total") is c
    with pytest.raises(ValueError):
        reg.gauge("rlt_test_events_total")
    # to_dict mirrors the same values for JSON surfaces.
    d = reg.to_dict()
    assert d["rlt_test_events_total"] == 3.0
    assert d["rlt_test_latency_seconds_count"] == 3


def test_relabel_text_adds_labels_everywhere():
    from ray_lightning_tpu.obs.registry import relabel_text

    reg = obs.MetricsRegistry()
    reg.counter("rlt_x_total").inc(1)
    reg.counter("rlt_y_total").inc(2, kind="k")
    relabelled = relabel_text(reg.render(), replica=1)
    parsed = obs.parse_prometheus_text(relabelled)
    assert parsed["rlt_x_total"]['{replica="1"}'] == 1.0
    assert parsed["rlt_y_total"]['{kind="k",replica="1"}'] == 2.0


def test_http_endpoint_scrapes_current_values():
    reg = obs.MetricsRegistry()
    c = reg.counter("rlt_scrape_total")
    c.inc(4)
    srv = obs.MetricsHTTPServer(
        collect_text=reg.render, collect_json=lambda: {"ok": True}
    ).start()
    try:
        body = urllib.request.urlopen(srv.url, timeout=10).read().decode()
        parsed = obs.parse_prometheus_text(body)
        assert parsed["rlt_scrape_total"][""] == 4.0
        c.inc(1)  # per-request collection: the next scrape sees it
        body = urllib.request.urlopen(srv.url, timeout=10).read().decode()
        assert obs.parse_prometheus_text(body)["rlt_scrape_total"][""] == 5.0
        stats = json.loads(
            urllib.request.urlopen(
                f"http://{srv.host}:{srv.port}/stats", timeout=10
            ).read()
        )
        assert stats == {"ok": True}
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# ServeMetrics regressions (satellites)
# ---------------------------------------------------------------------------
def test_ttft_p50_uses_nearest_rank():
    m = ServeMetrics(num_slots=2)
    # Six samples: the old `ttft[len // 2]` indexing read 4.0 here; the
    # nearest-rank _pct(..., 0.50) every other percentile uses reads 3.0.
    for v in (1.0, 2.0, 3.0, 4.0, 5.0, 6.0):
        m.record_first_token(v, v / 2, 1, 0, 8)
    snap = m.snapshot()
    assert snap["ttft_p50_s"] == 3.0


def test_queue_depth_updates_on_terminal_events():
    m = ServeMetrics(num_slots=2)
    m.record_submit(queue_depth=2)
    assert m.snapshot()["queue_depth"] == 2
    # finish/cancel/expire carry the depth they observed — the stat must
    # not stay stale until the next submit/admit refreshes it.
    m.record_finish(queue_depth=1)
    assert m.snapshot()["queue_depth"] == 1
    m.record_cancel(queue_depth=0)
    assert m.snapshot()["queue_depth"] == 0
    m.record_expire()  # no depth observed -> unchanged, not zeroed
    assert m.snapshot()["queue_depth"] == 0
    assert m.snapshot()["cancelled"] == 1
    assert m.snapshot()["expired"] == 1


def test_scheduler_cancel_of_queued_request_updates_queue_depth(obs_params):
    from ray_lightning_tpu.serve.engine import DecodeEngine
    from ray_lightning_tpu.serve.scheduler import SamplingParams, Scheduler

    eng = DecodeEngine(
        obs_params, OBS_CFG, num_slots=2, max_seq=32, prefill_buckets=[8]
    )
    sched = Scheduler(eng, max_prefills_per_step=1)
    rng = np.random.default_rng(0)
    r1 = sched.submit(
        rng.integers(0, 97, size=4).tolist(),
        SamplingParams(max_new_tokens=20),
    )
    r2 = sched.submit(
        rng.integers(0, 97, size=4).tolist(),
        SamplingParams(max_new_tokens=4),
    )
    sched.step()  # r1 admitted (1-per-step budget); r2 still queued
    assert sched.metrics.snapshot()["queue_depth"] == 1
    assert sched.cancel(r2)
    # The cancel is honored at the next pop — record_cancel must carry
    # the depth so the stat drops WITHOUT any submit/admit refreshing it.
    sched.step()
    snap = sched.metrics.snapshot()
    assert snap["queue_depth"] == 0
    assert snap["cancelled"] == 1
    assert sched.cancel(r1)
    sched.run_until_idle()


# ---------------------------------------------------------------------------
# Trace lifecycle completeness
# ---------------------------------------------------------------------------
def _spans(evs):
    return [e["span"] for e in evs]


def _assert_well_formed(evs, terminal):
    spans = _spans(evs)
    assert spans[0] == obs_trace.SPAN_SUBMIT, spans
    assert spans[1] == obs_trace.SPAN_QUEUED, spans
    terminals = [s for s in spans if s in obs_trace.TERMINAL_SPANS]
    assert terminals == [terminal], spans
    assert spans[-1] == terminal, spans
    ts = [e["t"] for e in evs]
    assert ts == sorted(ts), "trace timestamps must be monotonic"
    if obs_trace.SPAN_ADMITTED in spans:
        i_adm = spans.index(obs_trace.SPAN_ADMITTED)
        assert i_adm >= 2
        chunk_idxs = [
            e["index"] for e in evs if e["span"] == obs_trace.SPAN_PREFILL_CHUNK
        ]
        assert chunk_idxs == list(range(len(chunk_idxs))), spans
        if chunk_idxs:
            assert spans.index(obs_trace.SPAN_PREFILL_CHUNK) > i_adm
    if obs_trace.SPAN_FIRST_TOKEN in spans:
        i_ft = spans.index(obs_trace.SPAN_FIRST_TOKEN)
        # Decode folds live strictly between first token and terminal.
        for i, s in enumerate(spans):
            if s == obs_trace.SPAN_DECODE_FOLD:
                assert i_ft < i < len(spans) - 1 or spans[i + 1 :] == [
                    terminal
                ], spans


def test_trace_lifecycle_chunked_prefix_and_cancel(obs_params):
    """The admission matrix: cold chunked prefill, prefix-cache hit, and
    a mid-decode cancel — every trace well-formed, exported Chrome JSON
    valid."""
    from ray_lightning_tpu.serve.engine import DecodeEngine
    from ray_lightning_tpu.serve.scheduler import SamplingParams, Scheduler

    eng = DecodeEngine(
        obs_params,
        OBS_CFG,
        num_slots=2,
        max_seq=64,
        prefill_buckets=[32],
        prefill_chunk=8,
        prefix_blocks=8,
        prefix_block=8,
        decode_fold=2,
    )
    tracer = obs.RequestTracer(capacity=2048)
    sched = Scheduler(sched_engine := eng, tracer=tracer)
    assert sched_engine.tracer is tracer  # engine shares the tracer
    rng = np.random.default_rng(0)
    prefix = rng.integers(0, 97, size=24).tolist()

    # 1) Cold chunked prefill (24 + 4 = 28 tokens -> 4 chunks of 8).
    r_cold = sched.submit(
        prefix + rng.integers(0, 97, size=4).tolist(),
        SamplingParams(max_new_tokens=6),
    )
    sched.run_until_idle()
    # 2) Same prefix again: seeded from the pool, suffix-only prefill.
    r_hit = sched.submit(
        prefix + rng.integers(0, 97, size=4).tolist(),
        SamplingParams(max_new_tokens=6),
    )
    sched.run_until_idle()
    # 3) Mid-decode cancel: long budget, cancel after the first token.
    r_cancel = sched.submit(
        rng.integers(0, 97, size=12).tolist(),
        SamplingParams(max_new_tokens=40),
    )
    for _ in range(50):
        sched.step()
        if any(
            e["span"] == obs_trace.SPAN_FIRST_TOKEN
            for e in tracer.trace(r_cancel)
        ):
            break
    assert sched.cancel(r_cancel)
    sched.run_until_idle()

    t_cold = tracer.trace(r_cold)
    t_hit = tracer.trace(r_hit)
    t_cancel = tracer.trace(r_cancel)
    _assert_well_formed(t_cold, obs_trace.SPAN_FINISH)
    _assert_well_formed(t_hit, obs_trace.SPAN_FINISH)
    _assert_well_formed(t_cancel, obs_trace.SPAN_CANCEL)
    # Cold request: full chunk ladder, no seed.
    assert _spans(t_cold).count(obs_trace.SPAN_PREFILL_CHUNK) == 4
    assert obs_trace.SPAN_PREFIX_SEED not in _spans(t_cold)
    # Hit request: seeded 24 tokens (3 blocks), one suffix chunk.
    seeds = [e for e in t_hit if e["span"] == obs_trace.SPAN_PREFIX_SEED]
    assert len(seeds) == 1 and seeds[0]["tokens"] == 24
    assert _spans(t_hit).count(obs_trace.SPAN_PREFILL_CHUNK) == 1
    # Cancelled request decoded some folds, then terminated.
    assert obs_trace.SPAN_DECODE_FOLD in _spans(t_cancel)

    # Chrome export: JSON-serializable, phases derived, markers present.
    chrome = obs.to_chrome_trace(tracer.recent_traces(8))
    blob = json.dumps(chrome)
    events = json.loads(blob)["traceEvents"]
    x_names = {e["name"] for e in events if e["ph"] == "X"}
    assert {"queued", "prefill", "decode"} <= x_names
    assert all("ts" in e and "dur" in e for e in events if e["ph"] == "X")
    i_names = {e["name"] for e in events if e["ph"] == "i"}
    assert obs_trace.SPAN_PREFILL_CHUNK in i_names
    assert obs_trace.SPAN_PREFIX_SEED in i_names


def test_tracer_ring_buffer_bounded():
    tracer = obs.RequestTracer(capacity=4)
    for i in range(10):
        tracer.event(f"r{i}", obs_trace.SPAN_SUBMIT)
    assert len(tracer) == 4
    assert tracer.trace("r0") == []  # rotated out
    assert tracer.trace("r9") != []
    tracer.enabled = False
    tracer.event("r10", obs_trace.SPAN_SUBMIT)
    assert tracer.trace("r10") == []  # disabled tracer records nothing


def test_tracer_truncation_honesty():
    """Ring wrap that eats PART of a request's history is reported, not
    hidden: the retained trace's first event carries ``truncated`` and
    the dump lists the id — so duration math downstream (anatomy) can
    refuse to treat the first retained timestamp as the start."""
    tracer = obs.RequestTracer(capacity=4)
    tracer.event("old", obs_trace.SPAN_SUBMIT)
    for i in range(4):  # wraps "old"'s submit out while keeping later
        tracer.event("old", obs_trace.SPAN_DECODE_FOLD, attrs={"i": i})
    assert tracer.is_truncated("old")
    tr = tracer.trace("old")
    assert tr and tr[0].get("truncated") is True
    assert all("truncated" not in ev for ev in tr[1:])
    dump = tracer.dump(4)
    assert "old" in dump["truncated"]
    # A fully retained request is NOT flagged.
    tracer2 = obs.RequestTracer(capacity=8)
    tracer2.event("fresh", obs_trace.SPAN_SUBMIT)
    tracer2.event("fresh", obs_trace.SPAN_FINISH)
    assert not tracer2.is_truncated("fresh")
    # Healthy rings keep the legacy wire form: no "truncated" key at all.
    assert "truncated" not in tracer2.dump(4)
    assert all("truncated" not in ev for ev in tracer2.trace("fresh"))


# ---------------------------------------------------------------------------
# ServeReplica observability RPC surface (in-process)
# ---------------------------------------------------------------------------
def test_replica_obs_rpcs(obs_params):
    from ray_lightning_tpu.serve.server import ServeReplica

    rep = ServeReplica(
        params=obs_params,
        model_config=OBS_CFG,
        num_slots=2,
        max_seq=48,
        prefill_buckets=[16],
        prefill_chunk=8,
        decode_fold=2,
    )
    try:
        rng = np.random.default_rng(1)
        rid = rep.submit(
            rng.integers(0, 97, size=10).tolist(), max_new_tokens=6
        )
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            if rep.result(rid, wait_s=0.5)["done"]:
                break
        else:
            pytest.fail("request did not finish")
        evs = rep.trace(rid)
        assert _spans(evs)[0] == obs_trace.SPAN_SUBMIT
        assert _spans(evs)[-1] == obs_trace.SPAN_FINISH
        assert rid in rep.recent_traces(4)
        chrome = rep.export_trace(rid)
        assert chrome["traceEvents"]
        parsed = obs.parse_prometheus_text(rep.metrics_text())
        assert parsed["rlt_serve_requests_total"]['{kind="finished"}'] >= 1
        assert "rlt_serve_ttft_seconds_count" in parsed
        stats = rep.stats()
        # The frozen-compile contract as a metric: serving this request
        # compiled nothing.
        assert stats["compiles_since_init"] == 0
        assert stats["tracing"] is True
        assert stats["metrics"]["rlt_serve_engine_steps_total"] >= 1
        # profile() returns at once; the capture runs in a thread of the
        # replica and a second call collects it.
        assert rep.profile(0.05)["started"]
        prof = rep.profile_result(wait_s=60.0)
        assert prof["ok"], prof
        assert prof["files"]
    finally:
        rep.stop()


# ---------------------------------------------------------------------------
# Trainer telemetry
# ---------------------------------------------------------------------------
def test_trainer_step_breakdown_sums_to_wall(tmp_path):
    from ray_lightning_tpu.models import BoringModule
    from ray_lightning_tpu.trainer import Trainer

    t = Trainer(
        max_epochs=2,
        enable_checkpointing=False,
        seed=0,
        num_sanity_val_steps=0,
        default_root_dir=str(tmp_path),
    )
    t.fit(BoringModule())
    tel = t.state["telemetry"]
    assert tel["steps"] == t.global_step > 0
    total = tel["data_wait_s"] + tel["step_s"] + tel["drain_s"]
    # The segments are consecutive monotonic intervals; only float
    # rounding separates their sum from the recorded wall time.
    assert abs(total - tel["wall_s"]) <= 1e-3 + 0.02 * tel["wall_s"]
    assert 0.99 <= (
        tel["data_wait_frac"] + tel["step_frac"] + tel["drain_frac"]
    ) <= 1.01
    # Compile events were recorded for the fit's executables.
    assert tel["compile_events"]["backend_compile"]["count"] >= 1
    # Acceptance: the Prometheus endpoint serves TRAINER-path registry
    # metrics (the serve path's are covered in test_replica_obs_rpcs).
    srv = obs.MetricsHTTPServer(
        collect_text=obs.get_registry().render
    ).start()
    try:
        body = urllib.request.urlopen(srv.url, timeout=10).read().decode()
    finally:
        srv.close()
    parsed = obs.parse_prometheus_text(body)
    assert parsed["rlt_train_steps_total"][""] >= tel["steps"]
    assert '{segment="data_wait"}' in parsed["rlt_train_seconds_total"]


def test_trainer_tokens_per_sec_for_lm_modules(tmp_path):
    from ray_lightning_tpu.models.gpt import GPTLM
    from ray_lightning_tpu.trainer import Trainer

    cfg = GPTConfig(
        vocab_size=97,
        n_layer=1,
        n_head=2,
        d_model=32,
        max_seq=16,
        attn_impl="reference",
    )
    t = Trainer(
        max_epochs=1,
        enable_checkpointing=False,
        seed=0,
        num_sanity_val_steps=0,
        default_root_dir=str(tmp_path),
    )
    t.fit(GPTLM(config=cfg, batch_size=2, n_train=32))
    tel = t.state["telemetry"]
    assert tel["tokens_per_sec"] > 0
    # tokens = steps x module batch x batch_multiplier x max_seq; the
    # multiplier depends on the virtual-device topology, so assert the
    # per-step quantum rather than hardcoding it.
    assert tel["tokens_total"] % (tel["steps"] * 2 * 16) == 0
    assert tel["tokens_total"] >= tel["steps"] * 2 * 16
    assert "mfu" not in tel  # CPU: no fabricated MFU


def test_compile_listener_counts_new_compiles():
    import jax

    stats = obs.install_compile_listener()
    before = stats.count("backend_compile")
    # A shape this process has not compiled before.
    jax.jit(lambda x: x * 3 + 1)(np.ones((3, 5), np.float32))
    assert stats.count("backend_compile") >= before + 1
    snap = stats.snapshot()
    assert snap["backend_compile"]["total_s"] > 0


# ---------------------------------------------------------------------------
# Fabric heartbeats
# ---------------------------------------------------------------------------
class _HBActor:
    def ping(self):
        return "ok"


def test_fabric_heartbeats_aggregate(start_fabric):
    fabric = start_fabric(num_cpus=2)
    actor = (
        fabric.remote(_HBActor)
        .options(num_cpus=1, env={"RLT_HEARTBEAT_S": "0.2"})
        .remote()
    )
    assert fabric.get(actor.ping.remote()) == "ok"
    # Wait for a heartbeat that POSTDATES the call (the first push can
    # race the ping and still report calls_handled=0).
    deadline = time.monotonic() + 15
    hbs = {}
    while time.monotonic() < deadline:
        hbs = fabric.heartbeats()
        if hbs and all(h["calls_handled"] >= 1 for h in hbs.values()):
            break
        time.sleep(0.1)
    assert hbs, "no heartbeat arrived within 15s"
    (hb,) = hbs.values()
    assert hb["rss_bytes"] > 0
    assert hb["calls_handled"] >= 1
    assert hb["age_s"] >= 0
    reg = obs.MetricsRegistry()
    obs.heartbeats_to_registry(hbs, reg)
    parsed = obs.parse_prometheus_text(reg.render())
    assert any(
        v > 0 for v in parsed["rlt_fabric_worker_rss_bytes"].values()
    )
    fabric.kill(actor)


# ---------------------------------------------------------------------------
# Profiling
# ---------------------------------------------------------------------------
def test_capture_profile_produces_artifacts(tmp_path):
    out = obs.capture_profile(0.05, outdir=str(tmp_path / "prof"))
    assert out["ok"], out
    assert out["files"], out
    # A second capture reuses the machinery cleanly.
    again = obs.capture_profile(0.05)
    assert again["ok"], again
