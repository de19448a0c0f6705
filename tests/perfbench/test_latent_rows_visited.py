"""``latent_rows_visited_pct`` (PR 39): the decode kernel's counter of the
latent layers' rows under the kanana cell's name. Data only — the metric's
file names ``attn_visited_pct``'s reader and brings none of its own."""
import os

import pytest

from pb.spec import Spec

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME, CELL = "latent_rows_visited_pct", "kanana-2-30b-a3b-d16-ep8.serve-docqa"


def test_the_metric_is_data_listed_for_the_kanana_cell_alone():
    spec = Spec(ROOT)
    assert spec.metric_params(NAME)["reader"] == "attn_visited_pct"
    assert not os.path.exists(os.path.join(ROOT, "perfbench", "metrics", NAME + ".py"))
    entry = [m for m in spec.bench["per_layer"] if m["name"] == NAME]
    assert entry and entry[0]["workloads"] == [CELL] and entry[0]["moves"] == "tpot_p95_ms"
    assert [m["workloads"] for m in spec.bench["per_layer"] if m["name"] == "attn_visited_pct"] == [
        ["mistral-7b-v0.1-d8.serve-chat"]]


@pytest.mark.parametrize("visited,want", [(64 * 6656, 100.0), (96_000, 100.0 * 96_000 / (64 * 6656))],
                         ids=["the_xla_read_visits_every_allocated_row", "the_kernel_visits_the_live_slots_blocks"])
def test_it_reads_the_share_of_the_allocated_rows_a_step_visited(visited, want):
    def attn(k):
        return {"attn": {"rows_allocated": 16 * 64 * 6656 * 400 * k, "rows_visited": 16 * visited * 400 * k,
                         "rows_live": 16 * 90_000 * 400 * k}}

    read = Spec(ROOT).reader(NAME)
    assert read({"program": {"stats0": attn(1), "stats1": attn(3)}}) == pytest.approx(want)


def test_a_program_without_the_counter_gives_nothing():
    assert Spec(ROOT).reader(NAME)({"program": {"stats0": {}, "stats1": {}}}) is None
