"""``state_slots_visited_pct`` (PR 48): of the decode folds' slot-steps, the
share whose running state the state layers' update read and wrote — the
live ones under the kernel that walks them (``ops/ssm_step.py``), all of
them under the XLA pass."""
import os

import pytest

from pb.spec import Spec

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = "state_slots_visited_pct"
CELLS = ["nemotron-3-super-d11-ep4.serve-shortchat", "falcon-h1-34b-d6.serve-burstchat"]
DIMS = {"family": "falcon_h1"}


def _program(visited, steps=1000, slots=64, live=24):
    """A window of ``steps`` token steps over ``slots`` slots, ``live`` of
    them live in each, bracketed by two ``stats()`` calls."""
    def ssm(k):
        decode = {"slot_steps": slots * steps * k, "slot_steps_live": live * steps * k}
        if visited is not None:
            decode["slot_steps_visited"] = visited * steps * k
        return {"ssm": {"state_layers": 6, "decode": decode, "prefill": {"rows_scanned": 512 * k, "rows_real": 300 * k}}}

    return {"stats0": ssm(1), "stats1": ssm(3)}


def test_the_entry_is_listed_by_name_for_the_cells_with_state_layers():
    spec = Spec(ROOT)
    entry, = [m for m in spec.bench["per_layer"] if m["name"] == NAME]  # by name: a later PR appends after it
    assert entry == {"name": NAME, "unit": "%", "better": "lower", "source": "program_counter", "layer": "kernels",
                     "moves": "tpot_p95_ms", "workloads": CELLS}
    tpot, = [m for m in spec.bench["end_to_end"] if m["name"] == "tpot_p95_ms"]
    assert set(CELLS) <= set(tpot["workloads"])
    params = spec.metric_params(NAME)
    assert params["name"] == NAME and "reader" not in params
    assert os.path.exists(os.path.join(ROOT, "perfbench", "metrics", NAME + ".py"))


@pytest.mark.parametrize("family", ["falcon_h1", "nemotron_h"])
@pytest.mark.parametrize("visited,want", [(24, 37.5), (64, 100.0), (0, 0.0)],
                         ids=["the_kernel_visits_the_live_slots", "the_xla_pass_visits_every_slot", "no_slot_live"])
def test_it_reads_the_share_of_the_slot_steps_the_update_visited(family, visited, want, capsys):
    read = Spec(ROOT).reader(NAME)
    assert read({"dims": {"family": family}, "program": _program(visited)}) == pytest.approx(want)
    # beside state_live_pct's own number in its print: 24 of 64 live in each of the window's 2,000 steps
    assert f"visited {visited * 2000} of 128000 slot-steps" in capsys.readouterr().out


def test_a_program_from_before_the_counter_moved_every_slots_state():
    """The parent counts ``slot_steps`` and ``slot_steps_live`` alone, and
    its one update is the XLA pass: every slot-step was visited. (The
    benchmark's own ``test_pb_arithmetic.py`` hands every listed reader
    such a run and wants a number from each.)"""
    assert Spec(ROOT).reader(NAME)({"dims": DIMS, "program": _program(None)}) == 100.0


@pytest.mark.parametrize("program", [
    {"stats0": {}, "stats1": {}}, {"stats0": _program(24)["stats0"], "stats1": _program(24)["stats0"]},
], ids=["no_state_layers", "no_step_in_the_window"])
def test_a_program_without_state_counters_or_without_a_step_gives_nothing(program):
    assert Spec(ROOT).reader(NAME)({"dims": DIMS, "program": program}) is None


def test_a_family_without_state_layers_gives_nothing():
    assert Spec(ROOT).reader(NAME)({"dims": {"family": "mistral"}, "program": _program(24)}) is None
