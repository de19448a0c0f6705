"""What the host does, by name: ``obs.trace.span`` into ``SpanTotals``
(monotone totals, exposed host time), the collector hook beside the
compile listener, the spans of the replica's loop, its RPC surface and
the fit loop, their annotations in a profiler trace, and a ``profile``
RPC that leaves the replica serving.

The load-bearing properties: (1) the names of one thread add up to its
wall time, however they nest; (2) exposed time is charged only while the
loop has work and no device program is in flight; (3) with no profiler
session nothing is annotated, with one the trace holds the spans with
their attributes; (4) everything ``stats()["spans"]`` ships only grows.
"""
import gc
import glob
import time

import numpy as np
import pytest

from ray_lightning_tpu.models.gpt import GPTConfig, init_gpt_params
from ray_lightning_tpu.obs import jaxmon
from ray_lightning_tpu.obs import trace as obs_trace
from ray_lightning_tpu.obs.trace import SpanTotals, span

CFG = GPTConfig(
    vocab_size=97,
    n_layer=2,
    n_head=4,
    n_kv_head=2,
    d_model=32,
    max_seq=64,
    attn_impl="reference",
    compute_dtype="float32",
)

#: every name the replica's loop thread may time (docs/observability.md)
LOOP_SPANS = (
    "serve.loop.idle", "serve.loop.publish", "serve.loop.tick",
    "serve.sched.boundary", "serve.sched.admit",
    "serve.sched.prefill_chunks", "serve.sched.account",
    "serve.engine.dispatch", "serve.engine.harvest_wait",
    "serve.engine.harvest", "serve.engine.key_wait",
    "serve.engine.admit_wait",
)


@pytest.fixture(scope="module")
def params():
    import jax

    return init_gpt_params(jax.random.PRNGKey(0), CFG)


def _replica(params, **kw):
    from ray_lightning_tpu.serve.server import ServeReplica

    return ServeReplica(
        params=params, model_config=CFG, num_slots=2, max_seq=48,
        prefill_buckets=[16], decode_fold=2, watchdog=False, **kw,
    )


def _serve(rep, n_requests=3, max_new_tokens=8):
    rng = np.random.default_rng(1)
    rids = [
        rep.submit(rng.integers(0, 97, size=10).tolist(),
                   max_new_tokens=max_new_tokens)
        for _ in range(n_requests)
    ]
    deadline = time.monotonic() + 120
    for rid in rids:
        while not rep.result(rid, wait_s=0.2)["done"]:
            assert time.monotonic() < deadline, "request did not finish"
    return rids


def _idle_waits(rep):
    """Idle waits of the loop thread that have ended (``stats()`` is the
    ``serve.rpc.stats`` span too)."""
    return rep.stats()["spans"]["segments"].get("serve.loop.idle", {"n": 0})["n"]


def _events(trace_dir, prefix):
    """``{name: [stats dict, ...]}`` of the host plane's annotations
    whose name starts with ``prefix``."""
    from jax.profiler import ProfileData

    files = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    assert files, "no .xplane.pb written"
    found = {}
    for plane in ProfileData.from_file(files[-1]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(prefix):
                    found.setdefault(ev.name, []).append(dict(ev.stats))
    return found


# ---------------------------------------------------------------------------
# SpanTotals
# ---------------------------------------------------------------------------
def test_spans_nest_and_sum_to_wall():
    totals = SpanTotals()
    t0 = time.perf_counter()
    with span(totals, "outer") as outer:
        time.sleep(0.01)
        with span(totals, "inner"):
            time.sleep(0.02)
        with span(totals, "inner"):
            time.sleep(0.005)
    wall = time.perf_counter() - t0
    seg = totals.snapshot()["segments"]
    assert seg["outer"]["n"] == 1 and seg["inner"]["n"] == 2
    # Self time: the outer span does not count its children again...
    assert 0.009 <= seg["outer"]["s"] < 0.02
    assert seg["inner"]["s"] >= 0.025
    assert seg["inner"]["max_s"] >= 0.02
    # ...so the names add up to the wall time, and the span object still
    # knows the whole block.
    assert abs(seg["outer"]["s"] + seg["inner"]["s"] - wall) < 2e-3
    assert abs(outer.ns * 1e-9 - wall) < 2e-3


def test_span_totals_are_monotone():
    totals = SpanTotals()
    before = totals.snapshot()
    assert before == {
        "segments": {}, "exposed_s": {}, "work_s": 0.0,
        "riders_s": {"waiting": {}, "decoding": {}},
    }
    snaps = []
    for _ in range(3):
        with totals.work(), span(totals, "a"):
            time.sleep(0.001)
        snaps.append(totals.snapshot())
    for s0, s1 in zip(snaps, snaps[1:]):
        assert s1["segments"]["a"]["n"] == s0["segments"]["a"]["n"] + 1
        assert s1["segments"]["a"]["s"] > s0["segments"]["a"]["s"]
        assert s1["segments"]["a"]["max_s"] >= s0["segments"]["a"]["max_s"]
        assert s1["exposed_s"]["a"] > s0["exposed_s"]["a"]
        assert s1["work_s"] > s0["work_s"]


def test_span_records_through_an_exception():
    totals = SpanTotals()
    with pytest.raises(KeyError):
        with span(totals, "outer"):
            with span(totals, "inner"):
                raise KeyError("x")
    seg = totals.snapshot()["segments"]
    assert seg["outer"]["n"] == seg["inner"]["n"] == 1
    with span(totals, "after"):  # the stack of open spans is clean again
        pass
    assert totals.snapshot()["segments"]["after"]["n"] == 1


class _FakeEngine:
    """The engine's side of the exposed-time contract: says when a
    program goes in flight and when a sync shows the queue empty."""

    def __init__(self):
        self.spans = SpanTotals()
        self._inflight = None

    def dispatch(self):
        with span(self.spans, "dispatch"):
            time.sleep(0.01)
            self._inflight = object()
        self.spans.device_busy()

    def harvest(self, pipelined):
        self._inflight = object() if pipelined else None
        with span(self.spans, "harvest_wait"):
            time.sleep(0.01)
        if self._inflight is None:
            self.spans.device_idle()
        with span(self.spans, "harvest"):
            time.sleep(0.01)


@pytest.mark.parametrize("pipelined", [False, True])
def test_exposed_time_only_while_nothing_is_in_flight(pipelined):
    eng = _FakeEngine()
    with eng.spans.work():
        eng.dispatch()          # device idle until the enqueue: exposed
        eng.harvest(pipelined)  # host blocked on the device: not exposed
    snap = eng.spans.snapshot()
    exposed = snap["exposed_s"]
    assert 0.009 <= exposed["dispatch"] <= snap["segments"]["dispatch"]["s"]
    assert exposed.get("harvest_wait", 0.0) == 0.0
    if pipelined:
        # the next fold is in flight behind this one: the fan-out hides
        assert exposed.get("harvest", 0.0) == 0.0
    else:
        assert exposed["harvest"] >= 0.009
    assert sum(exposed.values()) <= snap["work_s"]


def test_no_exposed_time_outside_work():
    totals = SpanTotals()
    with span(totals, "idle"):  # nothing in flight, but no work either
        time.sleep(0.005)
    assert totals.snapshot()["exposed_s"] == {}
    assert totals.snapshot()["work_s"] == 0.0


def test_mirror_brings_registry_counters_up_to_the_totals():
    from ray_lightning_tpu.obs.registry import MetricsRegistry

    reg = MetricsRegistry()
    secs = reg.counter("t_seconds_total", "s")
    count = reg.counter("t_spans_total", "n")
    totals = SpanTotals()
    for _ in range(2):
        with span(totals, "a"):
            pass
        totals.mirror(secs, count)
        totals.mirror(secs, count)  # idempotent between spans
    assert count.value(segment="a") == 2
    assert secs.value(segment="a") == pytest.approx(
        totals.snapshot()["segments"]["a"]["s"]
    )


# ---------------------------------------------------------------------------
# The profiler sink
# ---------------------------------------------------------------------------
def test_no_annotation_without_a_profiler_session(monkeypatch):
    made = []

    class Off:
        @staticmethod
        def is_enabled():
            return False

        def __init__(self, *a, **kw):
            made.append((a, kw))

    monkeypatch.setattr(obs_trace, "_ANNOTATION", Off)
    totals = SpanTotals()
    with span(totals, "x", k=1):
        pass
    with obs_trace.step_annotation("fit", 3):
        pass
    assert made == []
    assert totals.snapshot()["segments"]["x"]["n"] == 1


def test_annotation_carries_name_and_attrs_in_a_session(monkeypatch):
    seen = []

    class On:
        @staticmethod
        def is_enabled():
            return True

        def __init__(self, name, **kw):
            seen.append(["new", name, kw])

        def __enter__(self):
            seen.append("enter")

        def __exit__(self, *exc):
            seen.append("exit")

    monkeypatch.setattr(obs_trace, "_ANNOTATION", On)
    with span(SpanTotals(), "serve.sched.admit", n=2, longest_prompt=31):
        seen.append("body")
    assert seen == [
        ["new", "serve.sched.admit", {"n": 2, "longest_prompt": 31}],
        "enter", "body", "exit",
    ]


def test_serve_spans_reach_the_profiler_trace(params, tmp_path):
    """A CPU profiler session around a serving replica: the written
    ``.xplane.pb`` holds the loop's and the RPC surface's spans on its
    host plane, with their attributes."""
    import jax

    rep = _replica(params)
    try:
        _serve(rep, n_requests=1)  # warm: nothing compiles in the session
        jax.profiler.start_trace(str(tmp_path))
        try:
            idle0 = _idle_waits(rep)
            rids = _serve(rep)
            # One whole idle wait inside the session: the one in progress
            # when the session started is not in the trace, so wait for the
            # second to end. (A fixed 0.25 s was too short for a loop thread
            # that shares its cores with five other test workers.)
            deadline = time.monotonic() + 60
            while _idle_waits(rep) < idle0 + 2:
                assert time.monotonic() < deadline, "the loop never idled"
                time.sleep(0.02)
        finally:
            jax.profiler.stop_trace()
    finally:
        rep.stop()
    found = _events(str(tmp_path), "serve.")
    for name in LOOP_SPANS + (
        "serve.rpc.submit", "serve.rpc.result", "serve.rpc.result_wait",
        "serve.rpc.stats",
    ):
        assert name in found, (name, sorted(found))
    assert found["serve.engine.harvest_wait"]
    disp = found["serve.engine.dispatch"][0]
    assert disp["fold"] == 2 and 1 <= disp["slots"] <= 2
    admit = found["serve.sched.admit"][0]
    assert admit["n"] >= 1 and admit["longest_prompt"] == 10
    assert {s["request_id"] for s in found["serve.rpc.result"]} >= set(rids)


def test_fit_spans_reach_the_profiler_trace(tmp_path):
    """The Profiler callback starts the session; the fit loop's spans
    and its step markers land in the trace it writes."""
    from ray_lightning_tpu.models import BoringModule
    from ray_lightning_tpu.trainer import JaxProfilerCallback, Trainer

    prof = JaxProfilerCallback(dirpath=str(tmp_path / "trace"), epochs=(1,))
    trainer = Trainer(
        max_epochs=2, enable_checkpointing=False, callbacks=[prof], seed=0,
        num_sanity_val_steps=0,
    )
    trainer.fit(BoringModule())
    found = _events(str(tmp_path / "trace"), "fit")
    for name in ("fit.stage", "fit.dispatch", "fit.drain_wait",
                 "fit.callbacks", "fit"):
        assert name in found, (name, sorted(found))
    steps = [s["step"] for s in found["fit.dispatch"]]
    assert steps == sorted(steps) and len(set(steps)) == len(steps)
    # StepTraceAnnotation("fit", step_num=...) around each dispatch
    assert [s["step_num"] for s in found["fit"]] == steps


# ---------------------------------------------------------------------------
# Collector pauses
# ---------------------------------------------------------------------------
@pytest.fixture
def no_gc_hook(monkeypatch):
    """The collector hook as a fresh process has it: not installed
    (whatever an earlier test of this worker left behind is set aside
    and put back)."""
    held = jaxmon._GC
    if held is not None:
        gc.callbacks.remove(held._callback)
    monkeypatch.setattr(jaxmon, "_GC", None)
    monkeypatch.setattr(jaxmon, "_GC_USERS", 0)
    yield
    if held is not None:
        gc.callbacks.append(held._callback)


def test_gc_hook_counts_a_full_collection_and_is_removed(no_gc_hook):
    stats = jaxmon.install_gc_hook()
    try:
        assert jaxmon.install_gc_hook() is stats  # shared, two users now
        before = stats.snapshot()
        gc.collect()
        after = stats.snapshot()
        assert after["2"]["n"] == before["2"]["n"] + 1
        assert after["2"]["s"] > before["2"]["s"]
        assert after["2"]["max_s"] >= before["2"]["max_s"] > -1
        jaxmon.remove_gc_hook()
        assert stats._callback in gc.callbacks  # one user left
    finally:
        jaxmon.remove_gc_hook()
    assert stats._callback not in gc.callbacks
    assert jaxmon.gc_stats() is None
    n = stats.snapshot()["2"]["n"]
    gc.collect()
    assert stats.snapshot()["2"]["n"] == n


def test_gc_mirror_feeds_a_counter(no_gc_hook):
    from ray_lightning_tpu.obs.registry import MetricsRegistry

    secs = MetricsRegistry().counter("t_gc_seconds_total", "s")
    stats = jaxmon.install_gc_hook()
    try:
        gc.collect()
        stats.mirror(secs)
        assert secs.value(gen="2") == pytest.approx(
            stats.snapshot()["2"]["s"]
        )
    finally:
        jaxmon.remove_gc_hook()


# ---------------------------------------------------------------------------
# The replica
# ---------------------------------------------------------------------------
def _flat(spans):
    """Every number of a ``stats()["spans"]`` block by its path."""
    out = {"work_s": spans["work_s"], "folds": spans["folds"]}
    for name, row in spans["segments"].items():
        for k, v in row.items():
            out[f"segments/{name}/{k}"] = v
    for name, v in spans["exposed_s"].items():
        out[f"exposed_s/{name}"] = v
    for gen, row in spans["gc"].items():
        for k, v in row.items():
            out[f"gc/{gen}/{k}"] = v
    return out


def test_replica_spans_sum_to_the_loops_working_time(params):
    """The twin of the TrainTelemetry sum test: the loop thread's
    segments are consecutive pieces of its working iterations."""
    rep = _replica(params)
    try:
        _serve(rep)
        time.sleep(0.3)  # a few idle waits
        spans = rep.stats()["spans"]
    finally:
        rep.stop()
    seg = spans["segments"]
    assert set(seg) <= set(LOOP_SPANS) | {
        "serve.rpc.submit", "serve.rpc.result", "serve.rpc.result_wait",
        "serve.rpc.stats",
    }
    busy = sum(
        row["s"] for name, row in seg.items()
        if name in LOOP_SPANS and name != "serve.loop.idle"
    )
    # What lies between the spans of an iteration (has_work, the fault
    # hooks, a few assignments) is all that separates the two.
    assert busy <= spans["work_s"]
    assert spans["work_s"] - busy <= 2e-3 + 0.05 * spans["work_s"]
    assert seg["serve.loop.idle"]["n"] >= 1
    assert spans["folds"] == seg["serve.engine.dispatch"]["n"] >= 1
    assert seg["serve.engine.harvest_wait"]["n"] == spans["folds"]
    assert seg["serve.rpc.submit"]["n"] == 3
    # Exposed host time is some of the working time, never the host
    # blocked on the device.
    assert 0.0 < sum(spans["exposed_s"].values()) <= spans["work_s"]
    # (a key fetch with nothing in flight is a round trip the device
    # idles through: serve.engine.key_wait may be charged)
    for blocked in ("serve.engine.harvest_wait", "serve.engine.admit_wait"):
        assert spans["exposed_s"].get(blocked, 0.0) == 0.0
    assert set(spans["gc"]) == {"0", "1", "2"}


def test_replica_spans_only_grow_and_reach_the_registry(params, no_gc_hook):
    from ray_lightning_tpu import obs

    rep = _replica(params)
    try:
        _serve(rep, n_requests=1)
        s0 = rep.stats()["spans"]
        _serve(rep, n_requests=2)
        gc.collect()
        s1 = rep.stats()["spans"]
        parsed = obs.parse_prometheus_text(rep.metrics_text())
    finally:
        rep.stop()
    f0, f1 = _flat(s0), _flat(s1)
    assert set(f0) <= set(f1)
    shrunk = {k: (f0[k], f1[k]) for k in f0 if f1[k] < f0[k]}
    assert not shrunk
    assert f1["segments/serve.rpc.submit/n"] == f0["segments/serve.rpc.submit/n"] + 2
    assert f1["gc/2/n"] >= f0["gc/2/n"] + 1
    assert f1["folds"] > f0["folds"]
    # the same totals on the /metrics endpoint
    label = '{segment="serve.engine.dispatch"}'
    assert parsed["rlt_serve_loop_spans_total"][label] >= f1["folds"]
    assert parsed["rlt_serve_loop_seconds_total"][label] > 0
    assert parsed["rlt_gc_pause_seconds_total"]['{gen="2"}'] > 0
    # stop() took the collector hook out again
    assert jaxmon.gc_stats() is None


def test_a_long_poll_is_not_rpc_work(params):
    """``result(wait_s > 0)`` sleeps on the condition by design; that
    sleep is ``serve.rpc.result_wait``, so ``serve.rpc.result`` (what
    ``rpc_busy_pct`` reads) stays work and lock wait."""
    rep = _replica(params)
    try:
        rid = rep.submit(list(range(1, 11)), max_new_tokens=30)
        t0 = time.perf_counter()
        # nothing lies past cursor 30: this waits for the finish
        while not rep.result(rid, cursor=30, wait_s=0.25)["done"]:
            assert time.perf_counter() - t0 < 120
        waited = time.perf_counter() - t0
        seg = rep.stats()["spans"]["segments"]
    finally:
        rep.stop()
    assert seg["serve.rpc.result_wait"]["s"] >= 0.5 * waited
    assert seg["serve.rpc.result"]["s"] < 0.02 + 0.1 * waited
    assert seg["serve.rpc.result"]["n"] == seg["serve.rpc.result_wait"]["n"]


def test_result_of_an_unknown_request_still_raises(params):
    rep = _replica(params)
    try:
        for wait_s in (0.0, 0.01):
            with pytest.raises(KeyError):
                rep.result("no-such-request", wait_s=wait_s)
    finally:
        rep.stop()


def test_profile_returns_while_the_loop_keeps_emitting(params, tmp_path):
    """``profile`` starts the capture in a thread of the replica: the
    same thread that asked goes on submitting and polling, tokens keep
    coming, and a second call collects the trace — with the loop's spans
    on it."""
    rep = _replica(params)
    try:
        _serve(rep, n_requests=1)
        assert rep.profile_result()["error"].startswith("no profile")
        t0 = time.monotonic()
        started = rep.profile(1.0, outdir=str(tmp_path / "prof"))
        assert started["ok"] and started["started"]
        assert time.monotonic() - t0 < 0.5  # it did not sleep the second
        assert not rep.profile(0.1)["ok"]  # one capture at a time
        assert rep.profile_result().get("pending")
        rids = _serve(rep, n_requests=2, max_new_tokens=12)
        served = [len(rep.result(rid)["tokens"]) for rid in rids]
        report = rep.profile_result(wait_s=120.0)
    finally:
        rep.stop()
    assert served == [12, 12]
    assert report["ok"] and report["files"], report
    found = _events(str(tmp_path / "prof"), "serve.")
    assert "serve.engine.harvest_wait" in found
    assert "serve.rpc.result" in found
