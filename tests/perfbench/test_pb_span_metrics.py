"""The six per-layer metrics that read the replica's ``stats()["spans"]``:
each reader's arithmetic on a canned pair of ``stats`` dictionaries,
nothing where the program ships no spans (a parent commit), and one
rehearsal of the toy serve cell with the six entries appended."""
import json
import os
import shutil

import pytest
from test_pb_harness import ROOT, TOY, _rehearse

from pb.spec import Spec

NAMES = ["loop_host_ms_per_fold", "host_exposed_pct.serve", "admit_ms", "result_rpcs_per_s", "rpc_busy_pct",
         "gc_pause_pct.serve"]


def _row(n, s, max_s=0.0):
    return {"n": n, "s": s, "max_s": max_s}


def _spans(k):
    """``stats()["spans"]`` after ``k`` units of everything (monotone in k)."""
    return {
        "segments": {
            "serve.loop.idle": _row(5 * k, 0.5 * k),
            "serve.loop.publish": _row(10 * k, 0.010 * k),
            "serve.loop.tick": _row(10 * k, 0.020 * k),
            "serve.sched.boundary": _row(10 * k, 0.005 * k),
            "serve.sched.admit": _row(4 * k, 0.080 * k),
            "serve.engine.key_wait": _row(6 * k, 0.300 * k),
            "serve.engine.admit_wait": _row(4 * k, 0.100 * k),
            "serve.sched.prefill_chunks": _row(10 * k, 0.001 * k),
            "serve.sched.account": _row(10 * k, 0.004 * k),
            "serve.engine.dispatch": _row(10 * k, 0.030 * k),
            "serve.engine.harvest_wait": _row(10 * k, 1.5 * k),
            "serve.engine.harvest": _row(10 * k, 0.050 * k),
            "serve.rpc.submit": _row(8 * k, 0.008 * k),
            "serve.rpc.result": _row(800 * k, 0.160 * k),
            "serve.rpc.result_wait": _row(1 * k, 3.0 * k),
            "serve.rpc.stats": _row(1 * k, 0.032 * k),
        },
        "exposed_s": {"serve.engine.dispatch": 0.030 * k, "serve.sched.admit": 0.010 * k},
        "work_s": 1.7 * k,
        "folds": 10 * k,
        "gc": {"0": _row(20 * k, 0.004 * k, 0.001), "1": _row(2 * k, 0.002 * k, 0.002),
               "2": _row(1 * k, 0.014 * k, 0.014 * k)},
    }


def _ctx(with_spans=True):
    stats0, stats1 = {"compiles_since_init": 0}, {"compiles_since_init": 0}
    if with_spans:
        stats0["spans"], stats1["spans"] = _spans(1), _spans(3)
    # the window is what lies between the two stats() calls: 2.0 s here
    return {"program": {"stats0": stats0, "stats1": stats1, "marks": {"stats0_s": 0.25, "stats1_s": 2.25}},
            "seconds": 30.0}


WANT = {
    # (0.010 + 0.020 + 0.005 + 0.080 + 0.001 + 0.004 + 0.030 + 0.050) * 2 units / 20 folds, in ms: idle and
    # the waits on the device (harvest_wait, key_wait, admit_wait) are not host work
    "loop_host_ms_per_fold": 1000.0 * 0.200 * 2 / 20,
    "host_exposed_pct.serve": 100.0 * 0.040 * 2 / 2.0,
    # the admission whole: its own time and the two waits on the device inside it (0.300 + 0.100)
    "admit_ms": 1000.0 * (0.160 + 0.800) / 8,
    "result_rpcs_per_s": 1600 / 2.0,
    # submit + result + stats; the long poll's sleep (result_wait) is left out
    "rpc_busy_pct": 100.0 * (0.008 + 0.160 + 0.032) * 2 / 2.0,
    "gc_pause_pct.serve": 100.0 * 0.020 * 2 / 2.0,
}


@pytest.mark.parametrize("name", NAMES)
def test_reader_arithmetic_over_the_window(name, capsys):
    value = Spec(ROOT).reader(name)(_ctx())
    assert value == pytest.approx(WANT[name], rel=1e-9)
    assert capsys.readouterr().out.strip()  # each says what it read on a line of the run


@pytest.mark.parametrize("name", NAMES)
def test_reader_reports_nothing_without_spans(name):
    """A program from before the spans (the parent, measured with this
    benchmark laid over it) ships no such key: no value, no error."""
    assert Spec(ROOT).reader(name)(_ctx(with_spans=False)) is None


def test_every_reader_of_the_serve_cell_reads_a_hand_made_run_with_spans():
    """``test_pb_arithmetic``'s hand-made run dates from before the spans,
    so there the six report nothing; the same run with the block in its two
    ``stats`` gives every reader of the cell, old and new, something to
    read."""
    from pb import costs

    spec = Spec(ROOT)
    cell = spec.cell("mistral-7b-v0.1-d8.serve-chat")
    program = {
        "records": [{"counted": True, "rpc_s": 0.001, "submit_s": 0.1, "due_s": 0.09, "recv_s": [0.5, 20.0],
                     "recv_n": [1, 99], "prompt_len": 100, "tokens": [1] * 100}],
        "stats0": {"compiles_since_init": 0, "spans": _spans(1),
                   "attn": {"rows_allocated": 1000, "rows_visited": 40, "rows_live": 30}},
        "stats1": {"compiles_since_init": 0, "ttft_queue_p95_s": 0.2, "occupancy": 0.6, "spans": _spans(3),
                   "attn": {"rows_allocated": 3000, "rows_visited": 100, "rows_live": 80}},
        "info1": {"ready_wall": 5.0}, "spawn_wall": 1.0,
    }
    e2e = {"ttft_p95_ms": 459.0, "tpot_p95_ms": 53.0, "serve_tokens_per_s": 938.0}
    ctx = {"cell": cell["name"], "chips": 1, "dims": spec.dims(spec.config(cell["config"])),
           "mix": spec.traffic(cell["traffic"]), "config": spec.config(cell["config"]), "program": program, "e2e": e2e,
           "trace": {"devices": 1, "busy_s": 2.9, "window_s": 3.0, "op_seconds": {"tpu_custom_call/x": 0.79},
                     "modules": {"jit_step_impl(1)": [0.1654, 0.1654]}, "collective_exposed_s": 0.0},
           "seconds": 30.0, "costs": costs, "peaks": costs.peaks("TPU v5 lite")}
    seen = {}
    for m in spec.per_layer(cell["name"], list(e2e)):
        ctx["params"] = spec.metric_params(m["name"])
        seen[m["name"]] = spec.reader(m["name"])(ctx)
    assert set(NAMES) <= {name.removesuffix(".chat") for name in seen} and len(seen) > len(NAMES)
    assert all(v is not None for v in seen.values()), seen
    assert seen["result_rpcs_per_s.chat"] == pytest.approx(1600 / 30.0)  # no marks: the run's seconds
    assert seen["attn_visited_pct"] == pytest.approx(3.0)  # 60 of the window's 2,000 allocated rows


def test_window_falls_back_on_the_run_seconds_and_keeps_the_longest_pause():
    from pb import spans

    ctx = _ctx()
    del ctx["program"]["marks"]
    w = spans.window(ctx)
    assert w["seconds"] == 30.0
    assert w["folds"] == 20 and w["work_s"] == pytest.approx(3.4)
    assert w["gc"]["2"] == {"n": 2, "s": pytest.approx(0.028)}
    assert w["gc_max_s"] == pytest.approx(0.042)  # a maximum has no difference: since the replica was built


def test_a_span_new_in_the_window_counts_from_zero():
    from pb import spans

    ctx = _ctx()
    del ctx["program"]["stats0"]["spans"]["segments"]["serve.sched.admit"]
    del ctx["program"]["stats0"]["spans"]["exposed_s"]["serve.sched.admit"]
    w = spans.window(ctx)
    assert w["segments"]["serve.sched.admit"] == {"n": 12, "s": pytest.approx(0.240)}
    assert w["exposed_s"]["serve.sched.admit"] == pytest.approx(0.030)


def test_every_entry_has_its_reader_and_parameters():
    """The six are present, in the issue's order (entries of later PRs may
    stand between and after them), and each is read in the chat cell (and
    in whichever serve cells came later): by itself, or by its ``.chat``
    twin where the entry moves an end-to-end metric the chat cell does not
    report (the twin's ``.json`` names the same reader)."""
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"] if m["name"] in NAMES] == NAMES
    spec = Spec(ROOT)
    for name in NAMES:
        entry = entries[name]
        twin = entries.get(name + ".chat", {"workloads": []})
        assert "mistral-7b-v0.1-d8.serve-chat" in entry["workloads"] + twin["workloads"] and entry["better"] == "lower"
        assert callable(spec.reader(name))
        if twin["workloads"]:
            assert spec.metric_params(twin["name"])["reader"] == name and twin["better"] == entry["better"]


@pytest.mark.parametrize("name", [m["name"] for m in json.load(open(os.path.join(ROOT, "BENCHMARK.json")))["per_layer"]])
def test_an_entry_and_its_readers_parameters_say_the_same(name):
    """``metrics/<name>.json`` repeats the entry's layer, unit, source and
    moved metric beside how the number is made: a reader that is re-pointed
    in one place and not the other is caught here."""
    spec = Spec(ROOT)
    entry = next(m for m in spec.bench["per_layer"] if m["name"] == name)
    params = spec.metric_params(name)
    assert params["name"] == name and params["how"]
    for key in ("layer", "unit", "source", "moves"):
        assert params[key] == entry[key], (name, key)
    assert set(entry["workloads"]) <= {c["name"] for c in spec.bench["workloads"]}


def test_the_toy_serve_cell_rehearses_with_the_six_entries(tmp_path):
    """Files and entries only: a copy of the toy root with the six entries
    appended (their readers are the harness's own, found by name) walks
    the serve cell, and each reader finds the program's spans."""
    root = str(tmp_path / "bench")
    shutil.copytree(TOY, root)
    real = {m["name"]: m for m in json.load(open(os.path.join(ROOT, "BENCHMARK.json")))["per_layer"]}
    b = json.load(open(os.path.join(root, "BENCHMARK.json")))
    b["per_layer"] += [dict(real[name], workloads=["toy-mistral.serve-chat"]) for name in NAMES]
    json.dump(b, open(os.path.join(root, "BENCHMARK.json"), "w"))
    p = _rehearse("toy-mistral.serve-chat", ["--trace", "1"], root=root, seconds="3")
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    assert "REHEARSAL finished: correct=True" in p.stdout
    for said in ("loop host time per fold over ", "exposed host time ", "admissions: ", "result RPCs: ",
                 "RPC thread busy: ", "collector pauses in the window: "):
        assert said in p.stdout, (said, p.stdout[-3000:])
    assert "serve.engine.dispatch" in p.stdout and "serve.rpc.result" in p.stdout
