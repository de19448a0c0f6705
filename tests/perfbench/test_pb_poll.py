"""The serve kind's client poll (``kinds/serve.py:drive``, ``next_poll_in``)
against a fake client whose requests yield their first token at a known
time and then tokens at a known pace: the first and the last token's times
to within ``fine_poll_ms`` and one call, the count of ``result`` calls a
request, a request lost in mid-stream, and the same parameters in every
serve mix. On the CPU, on a clock that only ``sleep`` and the fake's calls
move (a loaded machine changes nothing), and nothing of the program runs."""
import glob
import json
import math
import os

import pytest
from test_pb_harness import HERE, ROOT

from kinds import serve

FINE, COARSE = 0.010, 0.200
#: what one call of the fake takes
CALL_S = 0.001


class Clock:
    """``time`` as ``drive`` uses it, moved by ``sleep`` and by the fake
    client's calls alone."""

    def __init__(self):
        self.t = 1000.0

    def monotonic(self):
        return self.t

    def time(self):
        return 1.7e9 + self.t

    def sleep(self, seconds):
        self.t += max(seconds, 1e-6)


@pytest.fixture
def clock(monkeypatch):
    c = Clock()
    monkeypatch.setattr(serve, "time", c)
    return c


class FakeClient:
    """``ServeClient`` as far as ``drive`` uses it. Request ``i`` yields its
    first token ``ttft_s`` after ``submit`` returned and ``burst`` further
    tokens every ``burst * token_s`` after it, as a decode fold does; a
    request in ``lost`` raises on the poll after its first tokens."""

    def __init__(self, clock, ttft_s, token_s, burst=1, lost=()):
        self.clock, self.ttft_s, self.token_s, self.burst, self.lost = clock, ttft_s, token_s, burst, set(lost)
        self.reqs = []

    def submit(self, prompt, max_new_tokens, temperature):
        self.clock.t += CALL_S
        self.reqs.append({"t": self.clock.t, "want": int(max_new_tokens), "calls": 0, "given": 0})
        return len(self.reqs) - 1

    def token_times(self, h):
        r = self.reqs[h]
        first = r["t"] + self.ttft_s
        return [first + math.ceil(j / self.burst) * self.burst * self.token_s for j in range(r["want"])]

    def result(self, h, cursor):
        r = self.reqs[h]
        r["calls"] += 1
        if h in self.lost and r["given"]:
            raise RuntimeError("replica lost")
        have = sum(t <= self.clock.t for t in self.token_times(h))
        r["given"] = have
        self.clock.t += CALL_S
        return {"tokens": list(range(cursor, have)), "done": have == r["want"], "status": "finished" if have == r["want"] else "running"}

    def stats(self):
        return [{}]


def _drive(client, wants, fine=FINE, coarse=COARSE, seconds=0.5, drain_s=5.0, burst=1):
    mix = {"lead_in_s": 0.0, "drain_s": drain_s, "fine_poll_ms": 1000 * fine, "poll_ms": 1000 * coarse,
           "replica": {"decode_fold": burst}}
    schedule = [{"due_s": 0.05 + 0.013 * i, "prompt": [1, 2, 3], "max_new_tokens": w, "counted": True}
                for i, w in enumerate(wants)]
    ctx = {"mix": mix, "seconds": seconds, "trace": False, "out_dir": ""}
    return serve.drive(ctx, client, None, schedule), mix


def _truth(client, out, i):
    """Request ``i``'s token times on the window's clock: ``drive`` stamped
    the return of ``submit`` (``submit_s`` + ``rpc_s``), which is when the
    fake took the request."""
    rec = out["records"][i]
    return [t - client.reqs[i]["t"] + rec["submit_s"] + rec["rpc_s"] for t in client.token_times(i)]


@pytest.mark.parametrize("burst", [1, 4], ids=["a_token_at_a_time", "folds_of_four"])
@pytest.mark.parametrize("want", [1, 2, 16, 384])
def test_first_and_last_token_are_timed_to_the_fine_step_and_one_call(want, burst, clock):
    """A first token 105 ms after ``submit`` is what a 50 ms poll saw 45 ms
    late; 3 ms a token puts the last of 384 at 1.25 s."""
    client = FakeClient(clock, ttft_s=0.105, token_s=0.003, burst=burst)
    out, _ = _drive(client, [want], burst=burst)
    rec, truth = out["records"][0], _truth(client, out, 0)
    assert rec["done"] and rec["status"] == "finished" and len(rec["tokens"]) == want
    # a poll is due the fine step after the last one returned, and its own return is what is stamped
    slack = FINE + 2 * CALL_S + max(out["poll_late_s"])
    assert 0.0 <= rec["recv_s"][0] - truth[0] <= slack, (rec["recv_s"][0], truth[0])
    assert 0.0 <= rec["recv_s"][-1] - truth[-1] <= slack, (rec["recv_s"][-1], truth[-1])
    assert sum(rec["recv_n"]) == want and rec["polls"] == client.reqs[0]["calls"]
    # the calls a request costs: a fine step up to the first token, the doubling up to the coarse step and the
    # halving back down (log2 each), the coarse steps between, and the fine steps of the last burst
    stream_s = truth[-1] - truth[0]
    ceiling = (math.ceil(0.105 / FINE) + 2 * math.log2(COARSE / FINE) + stream_s / COARSE
               + burst * 0.003 / FINE + 6)
    assert rec["polls"] <= ceiling, (rec["polls"], ceiling)


def test_the_same_step_everywhere_is_the_poll_as_it_was(clock):
    """``fine_poll_ms`` equal to ``poll_ms`` is the one 50 ms step every
    request had until PR 37 (from a seeded phase of its own now): a first
    token that comes 35 ms before a poll of that step is seen 35 ms late —
    the comparison above can fail."""
    first_poll = 0.050 * serve.poll_phases({}, 1)[0]
    client = FakeClient(clock, ttft_s=first_poll + 0.100 - 0.035, token_s=0.003)
    out, _ = _drive(client, [16], fine=0.050, coarse=0.050)
    rec = out["records"][0]
    late = rec["recv_s"][0] - _truth(client, out, 0)[0]
    assert 0.030 <= late <= 0.035 + 3 * CALL_S + 1e-4  # the two polls before it took a call each
    assert rec["polls"] == client.reqs[0]["calls"] <= 6  # 0.2 s of stream in steps of 50 ms


def test_requests_are_polled_in_phases_of_their_own_and_every_run_in_the_same(clock):
    """Polls of one lattice put every first-token time on a point of it;
    the phases are a function of the mix's arrangement alone."""
    a = serve.poll_phases({"arrangement_seed": 7}, 500)
    assert a == serve.poll_phases({"arrangement_seed": 7}, 500) != serve.poll_phases({"arrangement_seed": 8}, 500)
    assert all(0.0 < x <= 1.0 for x in a) and 0.45 < sum(a) / 500 < 0.55
    assert [sum(k / 10 < x <= (k + 1) / 10 for x in a) for k in range(10)] == pytest.approx([50] * 10, abs=25)
    client = FakeClient(clock, ttft_s=0.060, token_s=0.002)
    out, _ = _drive(client, [4] * 12)
    firsts = [out["records"][i]["recv_s"][0] - _truth(client, out, i)[0] for i in range(12)]
    assert max(firsts) - min(firsts) > 0.004, firsts  # not one lateness for all, as a common lattice gives


def test_the_step_doubles_between_the_two_ends_and_halves_towards_the_last_token():
    """``next_poll_in`` alone, on a request that holds tokens at 100 a
    second: fine before the first token; 10, 20, 40 ... 200 ms after it;
    half the way to the reckoned end; and fine once the rest may come in
    one burst."""
    rec = {"tokens": [], "recv_s": [], "recv_n": [], "want": 384}
    assert serve.next_poll_in(rec, 0.3, FINE, COARSE) == FINE
    rec.update(tokens=[0], recv_s=[1.0], recv_n=[1])
    steps = [serve.next_poll_in(rec, 1.0, FINE, COARSE) for _ in range(6)]
    assert steps == pytest.approx([0.010, 0.020, 0.040, 0.080, 0.160, 0.200])
    rec.update(tokens=[0] * 101, recv_s=[1.0, 2.0], recv_n=[1, 100])  # 100 tokens in a second
    assert serve.next_poll_in(rec, 2.0, FINE, COARSE) == pytest.approx(0.200)  # 2.83 s to go
    rec.update(tokens=[0] * 374, recv_s=[1.0, 4.73], recv_n=[1, 373])
    assert serve.next_poll_in(rec, 4.73, FINE, COARSE) == pytest.approx(0.045)  # 10 tokens, the last due with the one before: half of 90 ms
    assert serve.next_poll_in(rec, 4.73, FINE, COARSE, burst=4) == pytest.approx(0.030)  # the last 4 come together
    rec.update(tokens=[0] * 381, recv_s=[1.0, 4.80], recv_n=[1, 380])
    assert serve.next_poll_in(rec, 4.80, FINE, COARSE, burst=4) == FINE
    assert serve.next_poll_in(rec, 4.95, FINE, COARSE) == FINE  # overdue: fine until it is done


def test_a_request_lost_in_mid_stream_enters_both_tails_at_the_drain_limit(clock):
    """Twenty requests, the last lost after its first tokens: one in twenty
    lies beyond the 95th percentile's lower neighbour, so both tails are
    pulled towards the limit (window + drain), and it counts as failed."""
    client = FakeClient(clock, ttft_s=0.030, token_s=0.002, lost=[19])
    out, mix = _drive(client, [16] * 20, seconds=0.5, drain_s=5.0)
    recs = out["records"]
    assert recs[19]["status"] == "error:RuntimeError" and recs[19]["done"] and 0 < len(recs[19]["tokens"]) < 16
    e2e, counted, done_ok, in_window = serve.end_to_end(recs, 0.5, 5.0)
    assert len(counted) == 20 and len(done_ok) == 19
    sound, _, _, _ = serve.end_to_end(recs[:19], 0.5, 5.0)
    assert sound["ttft_p95_ms"] < 60.0 and sound["tpot_p95_ms"] < 4.0
    # linear between the 19th and the 20th of twenty: 0.05 of the way from a sound value to the limit of 5.5 s
    assert e2e["ttft_p95_ms"] > 0.05 * 5500.0 and e2e["tpot_p95_ms"] > 0.05 * 5500.0
    assert in_window == sum(len(r["tokens"]) for r in recs)  # tokens a lost request did bring still count


def test_every_poll_records_how_late_it_ran(clock):
    """What ``poll_late_p95_ms`` among a run's ``numbers`` is taken from."""
    client = FakeClient(clock, ttft_s=0.020, token_s=0.002)
    out, _ = _drive(client, [16] * 5)
    assert len(out["poll_late_s"]) == sum(r["polls"] for r in out["records"]) and min(out["poll_late_s"]) >= 0.0


@pytest.mark.parametrize("missing", ["fine_poll_ms", "poll_ms"])
def test_a_serve_mix_names_both_steps_of_its_poll(missing):
    """No silent fall back on one step for all: a mix that leaves a step
    out is refused as a fault of the benchmark's files (exit code 2)."""
    from pb.spec import SpecError

    mix = {"fine_poll_ms": 10, "poll_ms": 200}
    assert serve.poll_steps(mix) == (0.010, 0.200)
    del mix[missing]
    with pytest.raises(SpecError, match=missing):
        serve.poll_steps(mix)


SERVE_MIXES = sorted(glob.glob(os.path.join(ROOT, "perfbench", "traffic", "serve-*.json"))
                     + glob.glob(os.path.join(HERE, "toy*", "traffic", "serve-*.json")))


@pytest.mark.parametrize("path", SERVE_MIXES, ids=[os.path.relpath(p, ROOT) for p in SERVE_MIXES])
def test_every_serve_mix_polls_alike(path):
    """One metric name, one definition, one bound: the cells' mixes and the
    toy roots' carry the same two steps."""
    mix = json.load(open(path))
    assert mix["kind"] == "serve"
    assert (mix["fine_poll_ms"], mix["poll_ms"]) == (10, 200)


@pytest.mark.parametrize("ok", [True, False], ids=["correct", "not_correct"])
def test_the_result_line_ends_with_the_numbers_printed_and_the_numbers_compared(ok, capsys):
    """Traced or not, the line carries how late the generator and the polls
    ran (``numbers``: printed, compared with nothing) and, last, each number
    compared beside its limit, which are also the last lines on standard
    error."""
    import argparse
    import importlib.util

    from pb.spec import Spec

    mod_spec = importlib.util.spec_from_file_location("pb_run_py", os.path.join(ROOT, "perfbench", "run.py"))
    run_py = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(run_py)
    spec = Spec(os.path.join(HERE, "toy"))
    checks = [{"check": "widest_gap", "value": 0.1 if ok else 0.4, "limit": 0.27, "ok": ok},
              {"check": "requests_sampled", "value": 5, "limit": ">=1", "ok": True}]
    run = {"ctx": {"cell": {"name": "toy-mistral.serve-chat"}, "rehearse": False}, "checks": checks,
           "numbers": {"gen_late_p95_ms": 1.25, "poll_late_p95_ms": 0.5, "widest_gap": checks[0]["value"]},
           "e2e": {"ttft_p95_ms": 300.5, "tpot_p95_ms": 12.25, "serve_tokens_per_s": 2700.0, "setup_s": 55.0},
           "attempted": 660, "failed": 0, "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
           "memory_peak_bytes": 8_361_000_000}
    rc = run_py.report(spec, argparse.Namespace(trace=0, rehearse=False), run)
    said = capsys.readouterr()
    line = json.loads(said.out.strip().splitlines()[-1])
    assert rc == 0 and line["correct"] is ok
    assert list(line) == ["correct", "attempted", "failed", "metrics", "device", "numbers", "checks"]
    assert line["numbers"]["gen_late_p95_ms"] == 1.25 and line["numbers"]["poll_late_p95_ms"] == 0.5
    assert line["checks"]["widest_gap"] == {"value": checks[0]["value"], "limit": 0.27}
    assert set(line["metrics"]) == {"ttft_p95_ms", "tpot_p95_ms", "serve_tokens_per_s", "setup_s"}
    last = said.err.strip().splitlines()[-2:]
    assert last[0] == f"check widest_gap: value={checks[0]['value']} limit=0.27 -> {'ok' if ok else 'FAILED'}"
    assert last[1] == "check requests_sampled: value=5 limit=>=1 -> ok"


@pytest.mark.parametrize("name", ["first_token_p50_ms", "first_token_p95_ms"])
def test_the_first_tokens_are_read_per_layer_where_their_tail_is_no_end_to_end_metric(name):
    """Two entries, one reader (``metrics/<name>.json`` names it and the
    percentile): first token seen less the time the request was due, over
    the requests due in the window; one that saw no token enters at the
    drain limit (5 + 30 s), and a lead-in request is not counted."""
    from pb.spec import Spec

    spec = Spec(ROOT)
    params = spec.metric_params(name)
    assert params["reader"] == "first_token_ms"
    recs = [{"counted": True, "due_s": 1.0 * i, "recv_s": [1.0 * i + 0.1 * (i + 1)], "recv_n": [1]} for i in range(4)]
    recs.append({"counted": True, "due_s": 4.0, "recv_s": [], "recv_n": []})
    recs.append({"counted": False, "due_s": -1.0, "recv_s": [9.0], "recv_n": [1]})
    ctx = {"program": {"records": recs}, "seconds": 5.0, "mix": {"drain_s": 30.0}, "params": params}
    # 100, 200, 300, 400 ms and 35 s: the median is the third, the 95th percentile lies 0.8 of the way to the fifth
    assert spec.reader(name)(ctx) == pytest.approx(300.0 if name == "first_token_p50_ms" else 0.8 * 35000.0 + 0.2 * 400.0)
    assert spec.reader(name)({**ctx, "program": {"records": recs[-1:]}}) is None
    entry = next(m for m in spec.bench["per_layer"] if m["name"] == name)
    ttft = next(m for m in spec.bench["end_to_end"] if m["name"] == "ttft_p95_ms")
    assert entry["workloads"] and not set(entry["workloads"]) & set(ttft["workloads"])
