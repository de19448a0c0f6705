"""``prefill_kernel_rows_pct`` (PR 49): the share of the admissions'
row-layers of attention the forward flash kernel read, from the four
prefill counters of ``stats()["attn"]``."""
import os

import pytest

from pb.spec import Spec

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = "prefill_kernel_rows_pct"
CELLS = ["mimo-v2-flash-d7-ep16.serve-mixedlen", "nemotron-3-super-d11-ep4.serve-shortchat",
         "kanana-2-30b-a3b-d16-ep8.serve-docqa", "falcon-h1-34b-d6.serve-burstchat"]
DECODE = {"rows_allocated": 1000, "rows_visited": 40, "rows_live": 30}


def test_the_entry_is_listed_for_the_cells_of_mixed_layer_kinds_and_moves_their_first_tokens():
    spec = Spec(ROOT)
    entry, = [m for m in spec.bench["per_layer"] if m["name"] == NAME]
    assert entry["workloads"] == CELLS and entry["moves"] == "ttft_p95_ms" and entry["better"] == "higher"
    assert entry["layer"] == "kernels" and entry["source"] == "program_counter" and entry["unit"] == "%"
    ttft, = [m for m in spec.bench["end_to_end"] if m["name"] == "ttft_p95_ms"]
    assert set(CELLS) == set(ttft["workloads"])
    assert spec.metric_params(NAME)["name"] == NAME and callable(spec.reader(NAME))


@pytest.mark.parametrize("layers,kernel_layers,want", [(16, 16, 100.0), (7, 2, 100.0 * 2 / 7), (6, 0, 0.0)],
                         ids=["every_layer_latent", "two_full_of_seven", "every_bucket_under_the_crossing"])
def test_it_reads_the_share_of_the_prefilled_row_layers_the_kernel_read(layers, kernel_layers, want, capsys):
    def attn(k):
        # k units of 120 admissions of 4,096 rows, 25 of their 36 tiles holding a row of the prompt
        return {"attn": dict(DECODE, prefill_rows=120 * 4096 * layers * k, prefill_rows_kernel=120 * 4096 * kernel_layers * k,
                             prefill_tiles=120 * 36 * layers * k,
                             prefill_tiles_visited=120 * (25 * kernel_layers + 36 * (layers - kernel_layers)) * k)}

    got = Spec(ROOT).reader(NAME)({"program": {"stats0": attn(1), "stats1": attn(3)}})
    assert got == pytest.approx(want)
    visited = 240 * (25 * kernel_layers + 36 * (layers - kernel_layers))
    assert f"{visited} of {240 * 36 * layers} score tiles" in capsys.readouterr().out


def test_a_program_without_the_counters_read_no_row_through_the_kernel():
    read = Spec(ROOT).reader(NAME)
    assert read({"program": {"stats0": {"attn": dict(DECODE)}, "stats1": {"attn": dict(DECODE)}}}) == 0.0
    # counters that did not move in the window (no admission): no row through the kernel either
    still = {"attn": dict(DECODE, prefill_rows=5, prefill_rows_kernel=5, prefill_tiles=1, prefill_tiles_visited=1)}
    assert read({"program": {"stats0": still, "stats1": still}}) == 0.0


def test_a_program_without_attention_counters_gives_nothing():
    assert Spec(ROOT).reader(NAME)({"program": {"stats0": {}, "stats1": {}}}) is None
    assert Spec(ROOT).reader(NAME)({"program": {}}) is None
