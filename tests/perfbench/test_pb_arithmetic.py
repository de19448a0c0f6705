"""Generator, metric arithmetic and the counts from shapes: CPU only, no
jax needed."""
import json
import math
import os

import pytest

from pb import costs, stats, traffic
from pb.spec import Spec, SpecError

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
MIX = {
    "arrival": {"process": "poisson", "rate_rps": 10.0},
    "prompt_tokens": {"dist": "lognormal", "median": 192, "sigma": 0.8, "min": 32, "max": 1024},
    "output_tokens": {"dist": "lognormal", "median": 96, "sigma": 0.7, "min": 16, "max": 384},
    "lead_in_s": 4,
}


# -- the generator -----------------------------------------------------------
def test_schedule_is_a_pure_function_of_the_seed():
    a = traffic.serve_schedule(MIX, 7, 10, 32000)
    b = traffic.serve_schedule(MIX, 7, 10, 32000)
    assert a == b
    assert a != traffic.serve_schedule(MIX, 8, 10, 32000)


@pytest.mark.parametrize("seed", [0, 1, 2**31 + 5])
def test_the_seed_changes_the_contents_and_not_the_work(seed):
    shape = lambda s: [(r["due_s"], len(r["prompt"]), r["max_new_tokens"], r["counted"]) for r in s]  # noqa: E731
    base = traffic.serve_schedule(MIX, 123, 10, 32000)
    other = traffic.serve_schedule(MIX, seed, 10, 32000)
    assert shape(base) == shape(other)
    assert [r["prompt"] for r in base] != [r["prompt"] for r in other]
    assert sum(r["counted"] for r in base) == 100 and sum(not r["counted"] for r in base) == 40


def test_another_arrangement_is_the_same_multiset_in_another_order():
    a = traffic.serve_schedule(MIX, 1, 10, 32000)
    b = traffic.serve_schedule(dict(MIX, arrangement_seed=5), 1, 10, 32000)
    for counted in (False, True):
        for key in (lambda r: len(r["prompt"]), lambda r: r["max_new_tokens"]):
            assert sorted(key(r) for r in a if r["counted"] is counted) == sorted(
                key(r) for r in b if r["counted"] is counted)
    assert [len(r["prompt"]) for r in a] != [len(r["prompt"]) for r in b]


def test_schedule_phases_and_bounds():
    s = traffic.serve_schedule(MIX, 3, 10, 32000)
    lead = [r for r in s if not r["counted"]]
    win = [r for r in s if r["counted"]]
    assert all(-4 <= r["due_s"] < 0 for r in lead) and all(0 <= r["due_s"] < 10 for r in win)
    assert [r["due_s"] for r in s] == sorted(r["due_s"] for r in s)
    assert all(32 <= len(r["prompt"]) <= 1024 and 16 <= r["max_new_tokens"] <= 384 for r in s)
    assert all(0 <= t < 32000 for r in s for t in r["prompt"])
    med = sorted(len(r["prompt"]) for r in win)[len(win) // 2]
    assert 170 <= med <= 215


def test_gaps_sum_to_the_span_and_are_exponential_quantiles():
    g = traffic.arrival_gaps({"process": "poisson", "rate_rps": 5}, 50, 10.0)
    assert math.isclose(sum(g), 10.0, rel_tol=1e-12)
    assert max(g) / min(g) > 50  # bursty, not a metronome
    assert traffic.arrival_gaps({"process": "uniform"}, 4, 2.0) == [0.5] * 4


def test_what_requests_share_is_the_mixs_and_keeps_the_work():
    plain = traffic.serve_schedule(MIX, 7, 10, 32000)
    shared = traffic.serve_schedule(dict(MIX, sharing={"kind": "prefix", "groups": 3, "prefix_tokens": 16}), 7, 10, 32000)
    assert [(r["due_s"], len(r["prompt"]), r["max_new_tokens"]) for r in plain] == [
        (r["due_s"], len(r["prompt"]), r["max_new_tokens"]) for r in shared]
    heads = {tuple(r["prompt"][:16]) for r in shared}
    assert len(heads) == 3 and len({tuple(r["prompt"][:16]) for r in plain}) == len(plain)
    assert all(a["prompt"][16:] == b["prompt"][16:] for a, b in zip(plain, shared))
    assert traffic.serve_schedule(dict(MIX, sharing="none"), 7, 10, 32000) == plain


def test_a_piece_the_generator_lacks_is_found_by_its_name(tmp_path, monkeypatch):
    """A size distribution, an arrival process and a way of sharing, each a
    file under ``generators/`` of some directory on the path: no edit of
    ``pb/traffic.py``."""
    g = tmp_path / "generators"
    g.mkdir()
    (g / "bimodal.py").write_text("def quantile(dist, u):\n    return dist['low'] if u < dist['share'] else dist['high']\n")
    (g / "bursts.py").write_text("def raw_gaps(arrival, n):\n    return [1.0 if i % arrival['burst'] == 0 else 0.01 for i in range(n)]\n")
    (g / "echo_first.py").write_text(
        "def share(reqs, sharing, seed, vocab):\n    for r in reqs:\n        r['prompt'][0] = sharing['token']\n")
    monkeypatch.syspath_prepend(str(tmp_path))
    mix = dict(MIX, prompt_tokens={"dist": "bimodal", "low": 64, "high": 900, "share": 0.75, "min": 32, "max": 1024},
               arrival={"process": "bursts", "rate_rps": 10.0, "burst": 5}, sharing={"kind": "echo_first", "token": 9})
    s = [r for r in traffic.serve_schedule(mix, 1, 10, 32000) if r["counted"]]
    assert sorted(len(r["prompt"]) for r in s) == [64] * 75 + [900] * 25
    assert all(r["prompt"][0] == 9 for r in s)
    gaps = sorted(b["due_s"] - a["due_s"] for a, b in zip(s, s[1:]))
    assert gaps[-1] / gaps[0] > 20
    with pytest.raises(Exception, match="generators/no_such_dist.py"):
        traffic.stratified({"dist": "no-such-dist", "min": 1, "max": 2}, 4)


def test_fake_text_rows_differ_and_follow_the_seed():
    a = traffic.fake_text(64, 32, 512, seed=1)
    assert a.shape == (64, 33) and a.min() >= 0 and a.max() < 512
    assert len({r.tobytes() for r in a}) == 64
    assert (a == traffic.fake_text(64, 32, 512, seed=1)).all()
    assert not (a == traffic.fake_text(64, 32, 512, seed=2)).all()


# -- percentiles and failures ------------------------------------------------------
def test_percentile_interpolates():
    assert stats.percentile([1, 2, 3, 4, 5], 50) == 3
    assert stats.percentile(list(range(101)), 95) == 95
    assert stats.percentile([10.0], 95) == 10.0
    assert math.isclose(stats.percentile([0, 10], 95), 9.5)
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_a_tail_needs_ten_samples_beyond_it():
    assert stats.tail_supported(200, 95) and not stats.tail_supported(199, 95)
    assert stats.tail_supported(20, 50) and not stats.tail_supported(19, 50)


def test_a_failed_request_misses_every_limit():
    lat = stats.latency_with_missing([0.1] * 90 + [None] * 10, missing_value=60.0)
    assert len(lat) == 100
    assert stats.percentile(lat, 95) == 60.0  # ten failures in a hundred: the tail is the drain limit
    assert stats.percentile(stats.latency_with_missing([0.1] * 99 + [None], 60.0), 95) == pytest.approx(0.1)


def test_spread_is_the_interquartile_share_of_the_median():
    import statistics

    v = [100, 101, 102, 103, 104, 105]
    q = statistics.quantiles(v, n=4)
    assert stats.spread(v) == pytest.approx((q[2] - q[0]) / 102.5)


def test_union_of_intervals():
    assert stats.union_seconds([(0, 1), (0.5, 2), (3, 4)]) == pytest.approx(3.0)
    assert stats.union_seconds([(0, 5), (1, 2)]) == pytest.approx(5.0)
    assert stats.union_seconds([]) == 0.0


# -- counts from shapes, against hand-worked values --------------------------------
#: huggingface.co/openai-community/gpt2-large config.json: the four-chip cell that waits under
#: PERF.md's Open questions has no data file yet; the counts for it are guarded all the same
GPT2_LARGE = {"model_type": "gpt2", "n_embd": 1280, "n_head": 20, "n_layer": 36, "n_positions": 1024,
              "vocab_size": 50257, "layer_norm_epsilon": 1e-05}


def _dims(name):
    spec = Spec(ROOT)
    return spec.dims(GPT2_LARGE if name == "gpt2-large" else spec.config(name))


def test_gpt2_medium_counts():
    d = _dims("gpt2-medium")
    # 24 x (4 d^2 attention + 8 d^2 MLP) = 24 x 12 x 1024^2 = 301,989,888; head 50257 x 1024 = 51,463,168
    assert costs.matmul_params(d) == 301_989_888 + 51_463_168
    assert costs.total_params(d) == 353_453_056 + 1024 * 1024
    # attention at S = 1024, causal: 4 x 24 x 1024 x 512.5 = 50,380,800 forward FLOPs a token
    assert costs.attn_flops_per_token_fwd(d, 1024) == pytest.approx(50_380_800)
    assert costs.train_flops_per_token(d, 1024) == pytest.approx(3 * (2 * 353_453_056 + 50_380_800))


def test_gpt2_large_counts():
    d = _dims("gpt2-large")
    assert costs.matmul_params(d) == 36 * 12 * 1280 * 1280 + 50257 * 1280
    assert costs.total_params(d) == 707_788_800 + 64_328_960 + 1024 * 1280  # 773.4 M
    assert costs.train_flops_per_token(d, 1024) == pytest.approx(3 * (2 * 772_117_760 + 4 * 36 * 1280 * 512.5))


def test_mistral_d8_counts():
    d = _dims("mistral-7b-v0.1-d8")
    per_layer = 4096 * 4096 * 2 + 2 * 4096 * 1024 + 3 * 4096 * 14336  # q and o, k and v (8 x 128), gate/up/down
    assert per_layer == 218_103_808
    assert costs.matmul_params(d) == 8 * per_layer + 32000 * 4096
    assert costs.total_params(d) == 8 * per_layer + 2 * 32000 * 4096  # 2.007 B
    # a decode step reads the layers and the head in bf16: 3.75 GB; a live token's K and V are 32 KiB
    assert costs.decode_step_bytes(d, 0) == 2 * (8 * per_layer + 131_072_000) == 3_751_804_928
    assert costs.decode_step_bytes(d, 1000) - costs.decode_step_bytes(d, 0) == 1000 * 32768


def test_flash_cost_and_roofline_side():
    c = costs.flash_train_cost(batch=4, heads=16, seq=1024, head_dim=64, layers=24)
    fwd = 4 * 4 * 16 * 1024 * 1024 * 64 / 2
    assert c["flops"] == pytest.approx(3.5 * fwd * 24)
    assert c["bytes"] == pytest.approx(12 * 4 * 16 * 1024 * 64 * 2 * 24)
    r = costs.roofline_seconds(c["flops"], c["bytes"], costs.peaks("TPU v5 lite"))
    assert r["bound"] == "compute" and r["seconds"] == pytest.approx(c["flops"] / 197e12)
    assert costs.roofline_seconds(1.0, 1e9, costs.peaks("TPU v5 lite"))["bound"] == "memory"


def test_an_unknown_device_is_an_error():
    with pytest.raises(KeyError, match="not in the benchmark's peaks"):
        costs.peaks("cpu")
    assert costs.peaks("TPU v5 lite")["ici_bytes_per_s"] == 200e9


# -- the data files ---------------------------------------------------------------
def test_benchmark_json_names_files_that_exist():
    spec = Spec(ROOT)
    b = spec.bench
    assert set(b) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    e2e = {m["name"] for m in b["end_to_end"]}
    assert "setup_s" in e2e
    from pb import plug

    for cell in b["workloads"]:
        spec.limits(cell["name"])
        assert spec.dims(spec.config(cell["config"]))["family"]
        kind = spec.traffic(cell["traffic"])["kind"]
        assert os.path.isfile(os.path.join(ROOT, "perfbench", "kinds", kind + ".py"))
        assert callable(plug.module("refs", kind).run)
        assert len(cell["why"]) <= 200
        reported = [m["name"] for m in spec.end_to_end(cell["name"])]
        assert len(reported) >= 2
        assert spec.per_layer(cell["name"], reported)
    for m in b["per_layer"]:
        assert m["moves"] in e2e
        assert callable(spec.reader(m["name"]))
    assert sum(c["chips"] == 4 for c in b["workloads"]) <= max(1, len(b["workloads"]) // 4)
    with pytest.raises(SpecError):
        spec.cell("no-such-cell")


def _counted(k, expert_layers):
    """``stats()["moe"]``, ``["ssm"]`` and ``["attn"]`` after ``k`` units of everything: monotone totals,
    so that the window is the difference of two. A unit is 500 decode token steps of 128 slots."""
    return {
        "moe": {"expert_layers": expert_layers,
                "decode": {"token_steps": 500 * k, "experts_hit": 500 * k * expert_layers * 10,
                           "pairs_routed": 80_000 * k, "pairs_held": 5_000 * k},
                "prefill": {"pairs_routed": 420_000 * k, "pairs_held": 26_250 * k}},
        "ssm": {"decode": {"slot_steps": 64_000 * k, "slot_steps_live": 38_400 * k},
                "prefill": {"rows_scanned": 12_800 * k, "rows_real": 9_600 * k}},
        "attn": {"rows_allocated": 500_000_000 * k, "rows_visited": 15_500_000 * k, "rows_live": 11_450_000 * k},
    }


def test_every_reader_of_every_cell_reads_a_hand_made_run_at_the_chips_peaks():
    """On the CPU a rehearsal has no peaks and the roofline readers return
    early; here each reader runs on a run written by hand, with the v5e's
    peaks and the counts of the cell's own family. The two ``stats`` carry
    every block a reader of some cell reads (``spans``, ``moe``, ``ssm``,
    ``attn``) after one unit and after three: the window is two units."""
    from test_pb_span_metrics import _spans

    spec = Spec(ROOT)
    trace = {"devices": 1, "busy_s": 2.9, "window_s": 3.0, "op_seconds": {"tpu_custom_call/x": 0.79},
             "modules": {"jit_step_impl(1)": [0.1654, 0.1654], "jit_admit_impl(2)": [0.0130, 0.0174, 0.0397]},
             "collective_exposed_s": 0.0}
    seen = {}
    for cell in spec.bench["workloads"]:
        mix = spec.traffic(cell["traffic"])
        dims = spec.dims(spec.config(cell["config"]))
        # the dense layer + one period of MiMo-V2-Flash has six expert layers, a period of Nemotron-3 five
        expert_layers = {"mimo-v2-flash-d7-ep16": 6, "nemotron-3-super-d11-ep4": 5}.get(cell["config"], 0)
        program = {
            "window": {"data_wait_s": 0.1, "dispatch_s": 0.1, "drain_s": 29.0, "chunks": 44, "compiles": 0,
                       "steps": 176, "dispatches": 44},
            "trace": {"dispatches": 3}, "worker_ready_wall": 10.0, "fit_call_wall": 1.0,
            "records": [{"counted": True, "rpc_s": 0.001, "submit_s": 0.1, "due_s": 0.09, "recv_s": [0.5, 20.0],
                         "recv_n": [1, 99], "prompt_len": 100, "tokens": [1] * 100}],
            "stats0": {"compiles_since_init": 0, "spans": _spans(1), **_counted(1, expert_layers)},
            "stats1": {"compiles_since_init": 0, "ttft_queue_p95_s": 0.2, "occupancy": 0.6, "spans": _spans(3),
                       **_counted(3, expert_layers)},
            "info1": {"ready_wall": 5.0}, "spawn_wall": 1.0,
        }
        e2e = ({"train_tokens_per_s_per_chip": 24078.0} if mix["kind"] == "train"
               else {"ttft_p95_ms": 459.0, "tpot_p95_ms": 53.0, "serve_tokens_per_s": 938.0})
        ctx = {"cell": cell["name"], "chips": 1, "dims": dims, "mix": mix,
               "config": spec.config(cell["config"]), "program": program, "e2e": e2e, "trace": trace,
               "seconds": 30.0, "costs": costs, "peaks": costs.peaks("TPU v5 lite")}
        for m in spec.per_layer(cell["name"], list(e2e)):
            ctx["params"] = spec.metric_params(m["name"])
            seen[cell["name"], m["name"]] = spec.reader(m["name"])(ctx)
    assert all(v is not None for v in seen.values()), seen
    assert {name for _, name in seen} == {m["name"] for m in spec.bench["per_layer"]}
    train, chat = "gpt2-medium.train-1chip", "mistral-7b-v0.1-d8.serve-chat"
    mimo, nemo = "mimo-v2-flash-d7-ep16.serve-mixedlen", "nemotron-3-super-d11-ep4.serve-shortchat"
    # 24,078 tokens/s/chip x 2.27 GFLOP a token over 197 TFLOP/s
    assert seen[train, "mfu_pct"] == pytest.approx(100 * 24078.0 * 3 * (2 * 353_453_056 + 50_380_800) / 197e12)
    assert seen[chat, "decode_step_ms"] == pytest.approx(165.4 / 4)
    assert 0 < seen[train, "flash_roofline_pct.train"] < 100
    # the one request holds 100 + 100 / 2 positions from 0.5 s to 20 s of the 30: 97.5 live positions a step
    want = 100.0 * (3_751_804_928 + 97.5 * 32768) / 819e9 / (0.1654 / 4)
    assert seen[chat, "decode_hbm_roofline_pct"] == pytest.approx(want) and 0 < want < 100
    # the counters, over the window's two units: 31.0 M rows visited of 1,000 M allocated
    assert seen[chat, "attn_visited_pct"] == pytest.approx(3.1)
    for cell in (mimo, nemo):
        # 10 experts hit a layer and step; (10,000 + 52,500) of 1,000,000 pairs landed on held experts
        assert seen[cell, "experts_hit_per_step"] == pytest.approx(10.0)
        assert seen[cell, "routed_here_pct"] == pytest.approx(6.25)
    assert seen[nemo, "state_live_pct"] == pytest.approx(60.0)  # 76,800 of 128,000 slot-steps
    assert seen[nemo, "admit_device_ms"] == pytest.approx(17.4)  # the median of the three admissions
    assert 0 < seen[mimo, "sparse_decode_hbm_roofline_pct"] < 100
    assert 0 < seen[nemo, "hybrid_decode_hbm_roofline_pct"] < 100
    # the spans' readers on the window between the two stats() calls (no marks: the run's 30 s)
    # the chat cell reads the quantities that move the first tokens through twins of their own (the same
    # readers: ``metrics/<name>.chat.json`` names them), since its first tokens' tail is no end-to-end metric
    for cell, twin in ((chat, ".chat"), (mimo, ""), (nemo, "")):
        assert seen[cell, "result_rpcs_per_s" + twin] == pytest.approx(1600 / 30.0)
        assert seen[cell, "admit_ms" + twin] == pytest.approx(1000.0 * (0.160 + 0.800) / 8)
        assert seen[cell, "gen_late_p95_ms" + twin] == pytest.approx(10.0)
        assert seen[cell, "client_rpc_ms" + twin] == pytest.approx(1.0)
    # the one request saw its first token at 0.5 s and was due at 0.09 s: both percentiles are that one
    assert seen[chat, "first_token_p95_ms"] == seen[chat, "first_token_p50_ms"] == pytest.approx(410.0)
    # the benchmark's contract: every cell an entry lists reports the end-to-end metric the entry moves
    e2e_of = {c["name"]: {m["name"] for m in spec.end_to_end(c["name"])} for c in spec.bench["workloads"]}
    moves = {m["name"]: m["moves"] for m in spec.bench["per_layer"]}
    assert all(moves[name] in e2e_of[cell] for cell, name in seen)
