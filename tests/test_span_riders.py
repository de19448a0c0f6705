"""Whose seconds a span takes: ``SpanTotals.riders`` charges the open
span with the time of the requests that wait through it, and the
scheduler moves the counts where its ledger's records move.

The load-bearing properties: (1) the time between two marks, times each
count, goes to the innermost open span, and to ``(no span)`` outside
every span; (2) a change of the counts inside a span splits it; (3)
everything ``riders_s`` ships only grows; (4) conservation: over requests
that ran to their end, the decoding seconds are the ledger's ``decode``
phases and the waiting seconds its ``queue`` + ``prefill``.
"""
import threading

import numpy as np
import pytest

from ray_lightning_tpu.models.gpt import GPTConfig, init_gpt_params
from ray_lightning_tpu.obs import trace as obs_trace
from ray_lightning_tpu.obs.trace import NO_SPAN, SpanTotals, span


class _Clock:
    """``time.perf_counter_ns`` by hand: every read is ``now``."""

    def __init__(self):
        self.now = 1_000_000_000

    def perf_counter_ns(self):
        return self.now

    def tick(self, ms):
        self.now += int(ms * 1_000_000)


@pytest.fixture
def clock(monkeypatch):
    c = _Clock()
    monkeypatch.setattr(obs_trace.time, "perf_counter_ns", c.perf_counter_ns)

    class Off:
        @staticmethod
        def is_enabled():
            return False

    monkeypatch.setattr(obs_trace, "_ANNOTATION", Off)
    return c


def _ms(by_name):
    return {k: round(1000.0 * v, 6) for k, v in by_name.items()}


def test_riders_are_charged_to_the_innermost_open_span(clock):
    totals = SpanTotals()
    totals.riders(waiting=2, decoding=3)
    with span(totals, "outer"):
        clock.tick(10)
        with span(totals, "inner"):
            clock.tick(4)
        clock.tick(1)
    rode = totals.snapshot()["riders_s"]
    assert _ms(rode["waiting"]) == {"inner": 8.0, "outer": 22.0}
    assert _ms(rode["decoding"]) == {"inner": 12.0, "outer": 33.0}


def test_a_count_change_inside_a_span_splits_it(clock):
    totals = SpanTotals()
    totals.riders(waiting=1, decoding=0)
    with span(totals, "admit"):
        clock.tick(5)
        totals.riders(waiting=0, decoding=1)  # its first token came
        clock.tick(3)
    rode = totals.snapshot()["riders_s"]
    assert _ms(rode["waiting"]) == {"admit": 5.0}
    assert _ms(rode["decoding"]) == {"admit": 3.0}


def test_time_outside_every_span_goes_to_no_span(clock):
    totals = SpanTotals()
    totals.riders(waiting=0, decoding=4)
    clock.tick(2)
    with span(totals, "fold"):
        clock.tick(6)
    clock.tick(1)
    rode = totals.snapshot()["riders_s"]["decoding"]
    assert _ms(rode) == {NO_SPAN: 12.0, "fold": 24.0}
    assert totals.snapshot()["riders_s"]["waiting"] == {}


def test_nothing_rides_while_both_counts_are_zero(clock):
    totals = SpanTotals()
    with span(totals, "idle"):
        clock.tick(50)
    totals.riders(waiting=1, decoding=0)  # the stale mark charges nothing
    clock.tick(1)
    totals.riders(waiting=0, decoding=0)
    with span(totals, "idle"):
        clock.tick(50)
    assert totals.snapshot()["riders_s"] == {
        "waiting": {NO_SPAN: pytest.approx(0.001)}, "decoding": {},
    }


def test_rider_snapshots_are_monotone_and_charged_up_to_the_call(clock):
    totals = SpanTotals()
    totals.riders(waiting=1, decoding=2)
    snaps = []
    with span(totals, "a"):
        for _ in range(3):
            clock.tick(7)
            snaps.append(totals.snapshot()["riders_s"])  # the span is open
    for s0, s1 in zip(snaps, snaps[1:]):
        assert s1["waiting"]["a"] == pytest.approx(s0["waiting"]["a"] + 0.007)
        assert s1["decoding"]["a"] == pytest.approx(s0["decoding"]["a"] + 0.014)
    # a clock read from before another thread's mark charges nothing back
    clock.tick(-3)
    totals.riders(waiting=5, decoding=5)
    assert totals.snapshot()["riders_s"] == snaps[-1]


def test_riders_conserve_hand_made_requests(clock):
    """Three requests by hand, each a submit, a first token and an end:
    the waiting seconds are their times to first token and the decoding
    seconds their times after it, whatever spans they fell in."""
    totals = SpanTotals()
    # (submit, first token, end) in ms from the start
    reqs = [(0, 30, 90), (10, 30, 60), (40, 70, 100)]
    marks = sorted({t for r in reqs for t in r})
    names = ["boundary", "admit", "fold"]
    t = 0
    for i, at in enumerate(marks):
        with span(totals, names[i % 3]):
            clock.tick(at - t)
        t = at
        totals.riders(
            waiting=sum(1 for s, f, _ in reqs if s <= at < f),
            decoding=sum(1 for _, f, e in reqs if f <= at < e),
        )
    rode = totals.snapshot()["riders_s"]
    assert sum(rode["waiting"].values()) == pytest.approx(
        sum(f - s for s, f, _ in reqs) / 1000.0
    )
    assert sum(rode["decoding"].values()) == pytest.approx(
        sum(e - f for _, f, e in reqs) / 1000.0
    )
    assert set(rode["waiting"]) | set(rode["decoding"]) <= set(names)


def test_mirror_brings_the_rider_counter_up_to_the_totals(clock):
    from ray_lightning_tpu.obs.registry import MetricsRegistry

    reg = MetricsRegistry()
    secs, count = reg.counter("t_seconds_total"), reg.counter("t_spans_total")
    rider = reg.counter("t_rider_seconds_total")
    totals = SpanTotals()
    totals.riders(waiting=1, decoding=2)
    for _ in range(2):
        with span(totals, "a"):
            clock.tick(5)
        totals.mirror(secs, count, rider)
        totals.mirror(secs, count, rider)  # idempotent between spans
    assert rider.value(segment="a", kind="waiting") == pytest.approx(0.010)
    assert rider.value(segment="a", kind="decoding") == pytest.approx(0.020)
    totals.mirror(secs, count)  # a totals without riders mirrors as before
    assert count.value(segment="a") == 2


def test_riders_from_another_thread_meet_the_owners_spans():
    """A submit comes on the RPC thread while the loop opens and closes
    spans: nothing is lost and nothing is charged twice."""
    totals = SpanTotals()
    stop = threading.Event()

    def loop():
        while not stop.is_set():
            with span(totals, "step"):
                pass

    th = threading.Thread(target=loop)
    th.start()
    try:
        import time

        t0 = time.perf_counter()
        for i in range(2000):
            totals.riders(waiting=1 + i % 2, decoding=1)
        totals.riders(waiting=0, decoding=0)
        wall = time.perf_counter() - t0
    finally:
        stop.set()
        th.join()
    rode = totals.snapshot()["riders_s"]
    assert sum(rode["decoding"].values()) == pytest.approx(wall, rel=0.05, abs=2e-3)
    assert wall <= sum(rode["waiting"].values()) + 2e-3
    assert sum(rode["waiting"].values()) <= 2 * wall + 2e-3


# ---------------------------------------------------------------------------
# The scheduler's counts, on the CPU toy engine
# ---------------------------------------------------------------------------
CFG = GPTConfig(
    vocab_size=97, n_layer=2, n_head=4, d_model=32, max_seq=64,
    attn_impl="reference", compute_dtype="float32",
)


@pytest.fixture(scope="module")
def params():
    import jax

    return init_gpt_params(jax.random.PRNGKey(0), CFG)


def _counts_from_the_ledger(sched):
    with_first = sum(1 for r in sched._acct.values() if "_ttft_s" in r)
    return len(sched._acct) - with_first, with_first


@pytest.mark.parametrize("chunked", [False, True])
def test_scheduler_riders_add_up_to_the_ledgers_phases(params, chunked):
    """Some dozens of requests through a scheduler, offered a few a
    step: at every step the counts are the ledger's open records, and at
    the end the spans' request-seconds are the ledger's phases."""
    from ray_lightning_tpu.serve.engine import DecodeEngine
    from ray_lightning_tpu.serve.scheduler import SamplingParams, Scheduler

    kw = dict(num_slots=3, max_seq=64, prefill_buckets=[16], decode_fold=2)
    if chunked:
        kw.update(prefill_chunk=4)
    eng = DecodeEngine(params, CFG, **kw)
    sched = Scheduler(eng, max_prefills_per_step=2)
    rng = np.random.default_rng(3)
    todo = [
        (rng.integers(0, CFG.vocab_size, size=int(rng.integers(5, 15))).tolist(),
         int(rng.integers(1, 9)))
        for _ in range(36)
    ]
    for step in range(2000):
        for prompt, n in todo[:2]:
            sched.submit(prompt, SamplingParams(max_new_tokens=n, seed=0))
        del todo[:2]
        if step == 7:
            # one request cancelled in the queue, one in its slot
            queued = sched._pending[-1][2].request_id
            in_slot = next(iter(sched._slot_req.values())).request_id
            assert sched.cancel(queued) and sched.cancel(in_slot)
        sched.step()
        assert (sched._n_waiting, sched._n_decoding) == _counts_from_the_ledger(sched)
        if not todo and not sched.has_work():
            break
    assert (sched._n_waiting, sched._n_decoding) == (0, 0)
    still = sched.riders_open()
    assert still["waiting"] == pytest.approx(0.0, abs=1e-6)
    assert still["decoding"] == pytest.approx(0.0, abs=1e-6)
    rode = sched.spans.snapshot()["riders_s"]
    costs = sched.metrics.cost_records()
    phases = sched.metrics.phase_records()  # one a cost record, in its order
    assert len(costs) == len(phases) == 36
    decode = sum(p.get("decode", 0.0) for p in phases)
    waiting = sum(p.get("queue", 0.0) + p.get("prefill", 0.0) for p in phases)
    # A cancelled request without a first token: the one cancelled in the
    # queue waited its whole life there (the ledger gives a queue phase
    # only to a request that was admitted), the one cancelled in its slot
    # (in the middle of a chunked prefill; an unchunked admission had
    # given it its first token) from its admission to the cancel in no
    # phase at all. That is the residual; with it the sums are equal.
    unphased = sum(
        c["total_s"] - p.get("queue", 0.0)
        for c, p in zip(costs, phases) if "prefill" not in p
    )
    assert sum(1 for p in phases if "prefill" not in p) == (2 if chunked else 1)
    got_d, got_w = sum(rode["decoding"].values()), sum(rode["waiting"].values())
    print(f"decoding {got_d:.6f} s against the ledger's {decode:.6f} "
          f"(residual {got_d - decode:+.6f}); waiting {got_w:.6f} s against "
          f"{waiting:.6f} (residual {got_w - waiting:+.6f}, of it {unphased:.6f} "
          f"the cancelled requests')")
    assert got_d == pytest.approx(decode, rel=0.02, abs=1e-3)
    assert got_w == pytest.approx(waiting + unphased, rel=0.02, abs=1e-3)
    # the decoders sat behind the fold and its harvest, the waiting behind
    # the admission: every name is one of the loop's
    assert set(rode["decoding"]) | set(rode["waiting"]) <= {
        NO_SPAN, "serve.sched.boundary", "serve.sched.admit",
        "serve.sched.prefill_chunks", "serve.sched.account",
        "serve.engine.dispatch", "serve.engine.harvest_wait",
        "serve.engine.harvest", "serve.engine.key_wait",
        "serve.engine.admit_wait",
    }
    # outside every span: this test's own submits and checks between the
    # steps (a replica's loop idles or ticks there, in a span) and the
    # few lines of step() between one span and the next
    assert rode["decoding"].get(NO_SPAN, 0.0) < 0.2 * got_d
