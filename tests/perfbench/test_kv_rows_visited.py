"""``kv_rows_visited_pct`` (PR 46): the decode kernel's counter of a mixed
configuration's full K/V layers under the mimo and nemotron cells' name.
Data only — the metric's file names ``attn_visited_pct``'s reader and brings
none of its own."""
import os

import pytest

from pb.spec import Spec

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = "kv_rows_visited_pct"
CELLS = ["mimo-v2-flash-d7-ep16.serve-mixedlen", "nemotron-3-super-d11-ep4.serve-shortchat"]


def test_the_metric_is_data_listed_for_the_cells_with_full_layers():
    spec = Spec(ROOT)
    assert spec.metric_params(NAME)["reader"] == "attn_visited_pct"
    assert not os.path.exists(os.path.join(ROOT, "perfbench", "metrics", NAME + ".py"))
    entry = spec.bench["per_layer"][-1]
    assert entry["name"] == NAME and entry["workloads"] == CELLS and entry["moves"] == "tpot_p95_ms"
    tpot = [m for m in spec.bench["end_to_end"] if m["name"] == "tpot_p95_ms"][0]
    assert set(CELLS) <= set(tpot["workloads"])


@pytest.mark.parametrize("layers,slots,rows,visited,want", [
    (2, 64, 5120, 64 * 5120, 100.0), (2, 64, 5120, 72_000, 100.0 * 72_000 / (64 * 5120)),
    (1, 128, 2048, 30_000, 100.0 * 30_000 / (128 * 2048)),
], ids=["the_xla_read_visits_every_allocated_row", "the_kernel_visits_the_live_slots_blocks", "one_full_layer"])
def test_it_reads_the_share_of_the_allocated_rows_a_step_visited(layers, slots, rows, visited, want):
    def attn(k):
        return {"attn": {"rows_allocated": layers * slots * rows * 400 * k, "rows_visited": layers * visited * 400 * k,
                         "rows_live": layers * 20_000 * 400 * k}}

    read = Spec(ROOT).reader(NAME)
    assert read({"program": {"stats0": attn(1), "stats1": attn(3)}}) == pytest.approx(want)


def test_a_program_that_does_not_count_the_full_layers_gives_nothing():
    assert Spec(ROOT).reader(NAME)({"program": {"stats0": {}, "stats1": {}}}) is None
