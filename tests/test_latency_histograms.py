"""Tails that are exactly a window's: the latency series' bucket bounds,
``Histogram.observe`` by bisection, ``rlt_serve_tpot_seconds``, and
``stats()["latency"]`` (per-bucket counts that only grow, so two calls
differ by exactly the requests between them)."""
import time

import numpy as np
import pytest

from ray_lightning_tpu.models.gpt import GPTConfig, init_gpt_params
from ray_lightning_tpu.obs.registry import (
    DEFAULT_BUCKETS, LATENCY_BUCKETS, MetricsRegistry, parse_prometheus_text,
)


def test_latency_bucket_bounds():
    b = LATENCY_BUCKETS
    assert b[0] == 0.0005 and b[-1] == 60.0 and len(b) == 251
    assert list(b) == sorted(set(b))
    assert max(hi / lo for lo, hi in zip(b, b[1:])) <= 1.05
    # four digits each: /metrics prints them as they are
    assert all(float(f"{x:.4g}") == x for x in b)


def _linear(buckets, v):
    """``Histogram.observe``'s search before PR 41: the first bound that is
    not below v."""
    for i, bound in enumerate(buckets):
        if v <= bound:
            return i
    return len(buckets)


@pytest.mark.parametrize("buckets", [DEFAULT_BUCKETS, LATENCY_BUCKETS, (1.0,)],
                         ids=["default", "latency", "one_bound"])
def test_observe_by_bisection_is_the_linear_scan(buckets):
    rng = np.random.default_rng(7)
    values = np.concatenate([
        rng.lognormal(np.log(0.05), 2.5, 2000),
        np.asarray(buckets),
        np.asarray(buckets) * (1 + 1e-12),
        [0.0, -1.0, 1e9],
    ])
    h = MetricsRegistry().histogram("t_seconds", "x", buckets=buckets)
    want = [0] * (len(buckets) + 1)
    for v in values:
        h.observe(float(v))
        want[_linear(h.buckets, float(v))] += 1
    row = h.row()
    assert row["counts"] == want and row["count"] == len(values) == sum(want)
    assert row["le"] == list(h.buckets)
    assert row["sum_s"] == pytest.approx(float(values.sum()))


def test_a_row_only_grows_and_is_per_series():
    h = MetricsRegistry().histogram("t_seconds", "x", buckets=LATENCY_BUCKETS)
    empty = h.row(phase="queue")
    assert empty["count"] == 0 and sum(empty["counts"]) == 0
    h.observe(0.2, phase="queue")
    r0 = h.row(phase="queue")
    h.observe(0.3, phase="queue")
    h.observe(0.3, phase="decode")
    r1 = h.row(phase="queue")
    assert r1["count"] == r0["count"] + 1 == 2
    assert all(b >= a for a, b in zip(r0["counts"], r1["counts"]))
    assert sum(b - a for a, b in zip(r0["counts"], r1["counts"])) == 1
    assert r1["sum_s"] == pytest.approx(0.5)


# ---------------------------------------------------------------------------
# A replica on the CPU
# ---------------------------------------------------------------------------
CFG = GPTConfig(
    vocab_size=97, n_layer=2, n_head=4, n_kv_head=2, d_model=32, max_seq=64,
    attn_impl="reference", compute_dtype="float32",
)


@pytest.fixture(scope="module")
def replica():
    import jax

    from ray_lightning_tpu.serve.server import ServeReplica

    rep = ServeReplica(
        params=init_gpt_params(jax.random.PRNGKey(0), CFG), model_config=CFG,
        num_slots=2, max_seq=48, prefill_buckets=[16], decode_fold=2,
        watchdog=False,
    )
    yield rep
    rep.stop()


def _serve(rep, n_requests, max_new_tokens=8):
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


def test_stats_latency_is_monotone_and_counts_the_window(replica):
    _serve(replica, 2)
    s0 = replica.stats()
    _serve(replica, 5)
    # one token: a first token and no time per output token
    _serve(replica, 1, max_new_tokens=1)
    s1 = replica.stats()
    assert set(s1["latency"]) == {"ttft", "tpot", "queue"}
    for name, grew in (("ttft", 6), ("tpot", 5), ("queue", 6)):
        r0, r1 = s0["latency"][name], s1["latency"][name]
        by_bucket = [b - a for a, b in zip(r0["counts"], r1["counts"])]
        assert r1["le"] == list(LATENCY_BUCKETS)
        assert len(r1["counts"]) == len(LATENCY_BUCKETS) + 1
        assert r1["count"] - r0["count"] == grew == sum(by_bucket), name
        assert min(by_bucket) >= 0 and r1["sum_s"] > r0["sum_s"]
    # a token after the first takes less than the whole request, and a
    # request's queue wait less than its time to first token
    lat = s1["latency"]
    mean = {k: lat[k]["sum_s"] / lat[k]["count"] for k in lat}
    assert mean["tpot"] < mean["ttft"]
    assert lat["queue"]["sum_s"] <= lat["ttft"]["sum_s"]


def test_stats_riders_add_up_to_the_ledger_between_two_calls(replica):
    """The conservation rule as the benchmark reads it: the spans'
    request-seconds of a window are the ledger's phases of the requests
    closed in it and what the open ones accrued."""
    s0 = replica.stats()
    _serve(replica, 6, max_new_tokens=12)
    s1 = replica.stats()

    def grew(read):
        return read(s1) - read(s0)

    rode_d = grew(lambda s: sum(s["spans"]["riders_s"]["decoding"].values()))
    rode_w = grew(lambda s: sum(s["spans"]["riders_s"]["waiting"].values()))
    decode = grew(lambda s: sum(
        v for k, v in s["metrics"].items()
        if k.startswith("rlt_serve_phase_seconds_sum{") and 'phase="decode"' in k
    ))
    first = grew(lambda s: s["latency"]["ttft"]["sum_s"])
    open_d = grew(lambda s: s["spans"]["riders_open_s"]["decoding"])
    open_w = grew(lambda s: s["spans"]["riders_open_s"]["waiting"])
    assert rode_d > 0 and rode_w > 0
    assert rode_d == pytest.approx(decode + open_d, rel=0.02, abs=2e-3)
    assert rode_w == pytest.approx(first + open_w, rel=0.02, abs=2e-3)
    assert s1["num_slots"] == 2


def test_metrics_still_renders_with_the_new_series(replica):
    _serve(replica, 2)
    parsed = parse_prometheus_text(replica.metrics_text())
    for name in (
        "rlt_serve_ttft_seconds", "rlt_serve_tpot_seconds",
        "rlt_serve_phase_seconds",
    ):
        assert parsed[name + "_count"] and parsed[name + "_sum"]
        les = [k for k in parsed[name + "_bucket"] if 'le="' in k]
        assert len(les) % (len(LATENCY_BUCKETS) + 1) == 0
        assert any('le="+Inf"' in k for k in les)
    # cumulated at render time: the +Inf bucket holds the count
    tpot = parsed["rlt_serve_tpot_seconds_bucket"]
    assert tpot['{le="+Inf"}'] == parsed["rlt_serve_tpot_seconds_count"][""]
    riders = parsed["rlt_serve_loop_rider_seconds_total"]
    assert any(
        'kind="decoding"' in k and 'segment="serve.engine.harvest_wait"' in k
        for k in riders
    )
    assert any('kind="waiting"' in k for k in riders)
    before = sum(riders.values())
    _serve(replica, 1)
    text = replica.metrics_text()
    after = sum(
        parse_prometheus_text(text)["rlt_serve_loop_rider_seconds_total"].values()
    )
    assert after > before
