"""The six readers of PR 41 and their three ``.chat`` twins
(``metrics/waits.entries.json``): each reader's arithmetic on a hand-made
pair of ``stats`` dictionaries, nothing where the program ships no
``riders_s`` / ``latency`` block (a parent commit), every entry listed
only for cells that report the end-to-end metric it moves, and one
rehearsal of the toy serve cell with the entries appended."""
import json
import os
import shutil

import pytest
from test_pb_harness import ROOT, TOY, _rehearse

from pb import waits
from pb.spec import Spec

ENTRIES = json.load(open(os.path.join(ROOT, "perfbench", "metrics", "waits.entries.json")))["per_layer"]
READERS = ["decode_behind_admit_pct", "slots_decoding_pct", "ttft_in_prefill_pct", "replica_tpot_p95_ms",
           "replica_ttft_p95_ms", "queue_wait_exact_p95_ms"]
TWINS = ["ttft_in_prefill_pct.chat", "replica_ttft_p95_ms.chat", "queue_wait_exact_p95_ms.chat"]
CHAT = "mistral-7b-v0.1-d8.serve-chat"
#: bucket bounds of the hand-made rows: 10 ms apart up to 100 ms
LE = [0.01 * i for i in range(1, 11)]


def _row(counts, sum_s):
    return {"le": LE, "counts": list(counts) + [0] * (11 - len(counts)), "count": sum(counts), "sum_s": sum_s}


def _stats(k):
    """``stats()`` after ``k`` units of everything (monotone in k). A unit:
    10 decoding request-seconds, 2 waiting; 20 requests."""
    return {
        "num_slots": 8,
        "spans": {
            "segments": {}, "exposed_s": {}, "work_s": 1.0 * k, "folds": 10 * k, "gc": {},
            "riders_s": {
                "decoding": {"serve.engine.harvest_wait": 5.0 * k, "serve.engine.key_wait": 1.5 * k,
                             "serve.sched.admit": 0.5 * k, "serve.engine.admit_wait": 2.0 * k,
                             "serve.sched.prefill_chunks": 0.5 * k, "serve.loop.tick": 0.5 * k},
                "waiting": {"serve.engine.admit_wait": 0.9 * k, "serve.sched.admit": 0.1 * k,
                            "serve.engine.key_wait": 0.6 * k, "serve.engine.harvest_wait": 0.4 * k},
            },
            # what the requests still open had accrued: 3 s more at the end than at the start
            "riders_open_s": {"decoding": 4.0 + 1.5 * k, "waiting": 0.2 * k},
        },
        "latency": {
            # 20 k values: 10 k in (20, 30] ms, 8 k in (30, 40], 2 k in (40, 50]
            "tpot": _row([0, 0, 10 * k, 8 * k, 2 * k], 0.62 * k),
            "ttft": _row([0, 0, 0, 0, 0, 0, 10 * k, 8 * k, 2 * k], 1.80 * k),
            "queue": _row([10 * k, 8 * k, 2 * k], 0.22 * k),
        },
        "metrics": {
            'rlt_serve_phase_seconds_sum{phase="decode",role="mixed"}': 8.5 * k,
            'rlt_serve_phase_seconds_sum{phase="queue",role="mixed"}': 0.22 * k,
            'rlt_serve_phase_seconds_count{phase="decode",role="mixed"}': 20 * k,
        },
    }


def _ctx(name, blocks=True, e2e=None):
    stats0, stats1 = ({"num_slots": 8, "spans": {"segments": {}, "exposed_s": {}, "work_s": 0.0, "folds": 0, "gc": {}}}
                      for _ in range(2))
    if blocks:
        stats0, stats1 = _stats(1), _stats(3)
    # the window is what lies between the two stats() calls: 5.0 s here
    return {"program": {"stats0": stats0, "stats1": stats1, "marks": {"stats0_s": 0.5, "stats1_s": 5.5}},
            "seconds": 30.0, "params": Spec(ROOT).metric_params(name),
            "e2e": e2e or {"ttft_p95_ms": 95.0, "tpot_p95_ms": 41.0, "serve_tokens_per_s": 900.0}}


WANT = {
    # of 20 decoding request-seconds: 1.0 + 4.0 + 1.0 behind an admission
    "decode_behind_admit_pct": 100.0 * 6.0 / 20.0,
    # 20 request-seconds over 8 slots x 5 s
    "slots_decoding_pct": 100.0 * 20.0 / 40.0,
    # of 4 waiting request-seconds: 1.8 + 0.2 behind an admission
    "ttft_in_prefill_pct": 100.0 * 2.0 / 4.0,
    # 40 values, rank 38: 2 of the 4 in (40, 50] ms lie under it
    "replica_tpot_p95_ms": 45.0,
    "replica_ttft_p95_ms": 85.0,
    "queue_wait_exact_p95_ms": 25.0,
}


@pytest.mark.parametrize("name", READERS + TWINS)
def test_each_reader_on_a_hand_made_window(name, capsys):
    got = Spec(ROOT).reader(name)(_ctx(name))
    assert got == pytest.approx(WANT[name.replace(".chat", "")])
    said = capsys.readouterr().out
    if name == "decode_behind_admit_pct":
        # the split by span, largest first, the fold in flight by name, and the residual: the ledger closed
        # 17 s of decode phases and the open requests accrued 3 s more: 20 s, as the spans were charged
        assert "serve.engine.harvest_wait 10.000 s, serve.engine.admit_wait 4.000 s, serve.engine.key_wait 3.000 s" in said
        assert "serve.engine.key_wait, the fold in flight that an admission waits out (their own work): 3.000" in said
        assert "17.000 s of decode phases of the requests closed in the window and +3.000 s more" in said
        assert "residual 0.0000 s (0.000%)" in said
    if name.startswith("ttft_in_prefill_pct"):
        # 3.6 s of first tokens given in the window and 0.4 s accrued: 4 s
        assert "3.600 s to the first tokens given in the window and +0.400 s more" in said
        assert "residual 0.0000 s (0.000%)" in said
        assert "the other 2.000 waited for the loop to reach the admission" in said
    if name.startswith("replica_ttft_p95_ms"):
        assert "mean 90.000 ms over 40 first tokens" in said and "+10.000 ms the front's" in said
    if name == "replica_tpot_p95_ms":
        assert "mean 31.000 ms over 40 requests" in said and "the client's p95 of this run: 41.0" in said
    if name == "slots_decoding_pct":
        assert "= 4.00 slots in the mean; waiting for a first token: 0.80 requests" in said


@pytest.mark.parametrize("name", READERS + TWINS)
def test_a_program_without_the_blocks_gives_nothing(name, capsys):
    assert Spec(ROOT).reader(name)(_ctx(name, blocks=False)) is None
    assert Spec(ROOT).reader(name)({"program": {"stats0": None, "stats1": {}}, "seconds": 30.0, "e2e": {}}) is None
    assert capsys.readouterr().out == ""


def test_a_window_without_a_value_gives_nothing():
    ctx = _ctx("replica_tpot_p95_ms")
    ctx["program"]["stats1"] = _stats(1)  # nothing ended, nobody waited, between the two calls
    for name in READERS:
        if name != "slots_decoding_pct":
            assert Spec(ROOT).reader(name)(ctx) is None, name
    assert Spec(ROOT).reader("slots_decoding_pct")(ctx) == 0.0


@pytest.mark.parametrize("counts,q,want", [
    ([0, 4, 0, 4], 50, 0.02),          # the rank falls on a bucket's upper bound
    ([0, 4, 0, 4], 75, 0.035),         # half way into (30, 40] ms
    ([10], 95, 0.0095),                # the first bucket starts at 0
    ([0] * 10 + [3], 95, 0.1),         # past every bound: the last bound, no less
    ([0] * 11, 95, None),
], ids=["on_a_bound", "inside_a_bucket", "first_bucket", "past_the_bounds", "no_values"])
def test_percentile_of_bucket_counts(counts, q, want):
    got = waits.percentile(LE, counts + [0] * (11 - len(counts)), q)
    assert got is None if want is None else got == pytest.approx(want)


def test_the_percentile_of_the_programs_buckets_is_within_three_percent():
    """10,000 lognormal latencies into the bounds the program's three
    series have, read back from the counts."""
    import bisect

    import numpy as np

    from pb import stats
    from ray_lightning_tpu.obs.registry import LATENCY_BUCKETS

    values = np.random.default_rng(41).lognormal(np.log(0.15), 0.6, 10_000)
    counts = [0] * (len(LATENCY_BUCKETS) + 1)
    for v in values:
        counts[bisect.bisect_left(LATENCY_BUCKETS, v)] += 1
    for q in (50, 95, 99):
        assert waits.percentile(LATENCY_BUCKETS, counts, q) == pytest.approx(stats.percentile(values.tolist(), q), rel=0.03)


def test_every_entry_lists_only_cells_that_report_what_it_moves():
    """The benchmark's contract, held before the entries are in
    ``BENCHMARK.json``: six readers and three twins, each with its reader
    and its parameters, listed for cells that report its ``moves``."""
    spec = Spec(ROOT)
    assert [m["name"] for m in ENTRIES] == READERS + TWINS
    assert not {m["name"] for m in ENTRIES} & {m["name"] for m in spec.bench["per_layer"]}
    e2e_of = {c["name"]: {m["name"] for m in spec.end_to_end(c["name"])} for c in spec.bench["workloads"]}
    serve = [c for c in e2e_of if "serve_tokens_per_s" in e2e_of[c]]
    for m in ENTRIES:
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert m["workloads"] and all(m["moves"] in e2e_of[cell] for cell in m["workloads"]), m["name"]
        params = spec.metric_params(m["name"])
        assert params["name"] == m["name"] and params["how"]
        for key in ("layer", "unit", "source", "moves"):
            assert params[key] == m[key], (m["name"], key)
        assert callable(spec.reader(m["name"]))
    by_name = {m["name"]: m for m in ENTRIES}
    for twin in TWINS:
        reader = twin[: -len(".chat")]
        assert spec.metric_params(twin)["reader"] == reader and by_name[twin]["workloads"] == [CHAT]
        assert not os.path.exists(os.path.join(ROOT, "perfbench", "metrics", twin + ".py"))
        assert by_name[twin]["better"] == by_name[reader]["better"]
        # the reader's own entry and its twin cover the serve cells between them, once each
        assert sorted(by_name[reader]["workloads"] + [CHAT]) == sorted(serve)
    for name in ("decode_behind_admit_pct", "slots_decoding_pct", "replica_tpot_p95_ms"):
        assert sorted(by_name[name]["workloads"]) == sorted(serve)


def test_the_toy_serve_cell_rehearses_with_the_entries(tmp_path):
    """Files and entries only: a copy of the toy root with the six readers'
    entries appended walks the serve cell, and each reader finds the
    program's blocks; the residuals it prints are small."""
    root = str(tmp_path / "bench")
    shutil.copytree(TOY, root)
    b = json.load(open(os.path.join(root, "BENCHMARK.json")))
    b["per_layer"] += [dict(m, workloads=["toy-mistral.serve-chat"]) for m in ENTRIES if m["name"] in READERS]
    json.dump(b, open(os.path.join(root, "BENCHMARK.json"), "w"))
    p = _rehearse("toy-mistral.serve-chat", ["--trace", "1"], root=root, seconds="3")
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    assert "REHEARSAL finished: correct=True" in p.stdout
    for said in ("decoding request-seconds behind the loop's spans: ", "waiting request-seconds behind the loop's spans: ",
                 "decoders behind admissions: ", "slots decoding: ", "first tokens behind admissions: ",
                 "time per output token at the replica: p95 ", "time to first token at the replica: p95 ",
                 "queue wait at the replica: p95 "):
        assert said in p.stdout, (said, p.stdout[-3000:])
    for line in p.stdout.splitlines():
        if "request-seconds behind the loop's spans" in line:
            residual = float(line.rsplit("(", 1)[1].split("%")[0])
            assert abs(residual) < 2.0, line
