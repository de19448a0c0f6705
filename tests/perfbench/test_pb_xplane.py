"""The reduction from a trace to numbers, on a small trace recorded on the
chip (``data/trace_small.json``: the first 400 operations of one training
dispatch of GPT-2 medium) and on hand-made events."""
import json
import os

import pytest

from pb import xplane

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "data", "trace_small.json")) as f:
        return json.load(f)


def test_recorded_trace_busy_idle_and_kernel_time(recorded):
    r = xplane.reduce(recorded)
    assert r["devices"] == 1
    # the dispatch's while loop spans the whole sample: the device is busy all of it
    assert r["window_s"] == pytest.approx(0.676928468, rel=1e-6)
    assert r["busy_s"] == pytest.approx(r["window_s"], rel=1e-6)
    # operation times are exclusive, so they add up to the busy time
    assert sum(r["op_seconds"].values()) == pytest.approx(r["busy_s"], rel=1e-6)
    # five calls of the Mosaic flash forward kernel lie in the sample, 0.92 ms each
    flash = xplane.match_seconds(r["op_seconds"], ["^tpu_custom_call/"])
    assert flash == pytest.approx(0.00461892, rel=1e-4)
    assert r["device_ops"][0][0] == "while" and r["device_ops"][1][0] == "tpu_custom_call/closed_call"
    # three dispatches of the one executable, 0.677 s each, with the host's gaps between them
    (name, durs), = r["modules"].items()
    assert name.startswith("jit_kstep") and len(durs) == 3
    assert all(d == pytest.approx(0.677, abs=2e-3) for d in durs)
    assert r["collective_s"] == 0.0 and r["collective_exposed_s"] == 0.0


def _dev(ops, modules=(), async_=()):
    return {"ops": [list(o) for o in ops], "modules": [list(m) for m in modules], "async": [list(a) for a in async_]}


def test_idle_gaps_and_who_they_belong_to():
    ev = {"devices": {"/device:TPU:0": _dev(
        [("%fusion.1 = f32[8]", 0.0, 1.0), ("%fusion.2 = f32[8]", 1.5, 1.0), ("%copy.3 = f32[8]", 2.5, 0.5),
         ("%fusion.9 = f32[8]", 3.2, 0.8)],
        modules=[("jit_step(1)", 0.0, 2.5), ("jit_step(1)", 2.5, 1.5)])}}
    r = xplane.reduce(ev)
    assert r["busy_s"] == pytest.approx(3.3) and r["window_s"] == pytest.approx(4.0)
    assert [round(g[1], 6) for g in r["gaps"]] == [0.5, 0.2]
    named = dict(xplane.name_gaps(r["gaps"], r["module_spans"], "host"))
    # both gaps lie inside an executable's span: the trace cannot say what the device waited for
    assert named == {"unattributed (inside an executable)": pytest.approx(0.7)}
    named = dict(xplane.name_gaps([[2.5, 0.3, "d"]], [(0.0, 2.5), (2.8, 4.0)], "host"))
    assert named == {"host": pytest.approx(0.3)}


def test_nested_operations_count_once():
    ops = [("%while.1 = (s32[])", 0.0, 10.0), ("%fusion.2 = f32[8]", 1.0, 2.0), ("%fusion.3 = f32[8]", 4.0, 3.0),
           ("%convert.4 = bf16[8]", 4.5, 1.0)]
    assert xplane.self_seconds(ops) == [5.0, 2.0, 2.0, 1.0]
    r = xplane.reduce({"devices": {"/device:TPU:0": _dev(ops)}})
    assert r["busy_s"] == pytest.approx(10.0)
    assert r["op_seconds"] == {"while": 5.0, "fusion": 4.0, "convert": 1.0}


def test_an_exposed_collective_is_what_no_compute_covers():
    # an all-reduce in flight from 1.0 to 3.0 (async line); compute covers 0..2; its done-wait runs 2.0..3.0
    ev = {"devices": {
        "/device:TPU:0": _dev(
            [("%fusion.1 = f32[8]", 0.0, 2.0), ("%all-reduce-done.7 = f32[8]", 2.0, 1.0), ("%fusion.2 = f32[8]", 3.0, 1.0)],
            async_=[("%all-reduce-start.7 = f32[8]", 1.0, 2.0)]),
        "/device:TPU:1": _dev(
            [("%fusion.1 = f32[8]", 0.0, 3.0), ("%all-gather.5 = f32[8]", 3.0, 0.5), ("%fusion.2 = f32[8]", 3.5, 0.5)]),
    }}
    r = xplane.reduce(ev)
    assert r["devices"] == 2
    assert r["collective_s"] == pytest.approx((2.0 + 0.5) / 2)
    assert r["collective_exposed_s"] == pytest.approx((1.0 + 0.5) / 2)
    assert r["busy_s"] == pytest.approx(4.0)
    assert xplane.reduce({"devices": {}}) == {"devices": 0}


def test_short_names_are_stable_across_renumbering():
    assert xplane._short("%fusion.123 = f32[4]{0} fusion(...)") == "fusion"
    assert xplane._short("%convolution_add_fusion.7 = bf16[4]") == "convolution_add_fusion"
    assert xplane._short('%closed_call.112 = (bf16[64,1024,64]) custom-call(...), custom_call_target="tpu_custom_call"') \
        == "tpu_custom_call/closed_call"
    assert xplane.COLLECTIVE.search("all-reduce-start") and not xplane.COLLECTIVE.search("fusion")
