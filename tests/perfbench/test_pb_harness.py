"""The harness end to end on the CPU at the toy size: the documented
rehearsal of each kind of cell, a fourth cell added as files only, a
third family and a new kind of cell added as files only, a broken timed
path that has to come out as not correct, and a machine with no chip."""
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
HERE = os.path.join(ROOT, "tests", "perfbench")
TOY = os.path.join(HERE, "toy")
RUN = [sys.executable, os.path.join(ROOT, "perfbench", "run.py")]


def _run(args, timeout=600, **more_env):
    env = {k: v for k, v in os.environ.items() if k != "BENCH_RUN"}
    env.update(more_env, BENCH_RUN="ignored-by-the-benchmark")
    return subprocess.run(RUN + args, cwd=ROOT, env=env, timeout=timeout,
                          stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)


def _rehearse(cell, extra=(), root=TOY, seconds="2", **more_env):
    return _run(["--bench-root", root, "--rehearse", "--workload", cell, "--seed", str(2**31 + 17),
                 "--seconds", seconds, *extra], **more_env)


def _checks(out):
    return {ln.split(":")[0][6:]: ln.rstrip().endswith("ok") for ln in out.splitlines() if ln.startswith("check ")}


@pytest.mark.parametrize("cell,trace", [
    ("toy-gpt2.train-1chip", "0"), ("toy-gpt2.train-4chip-zero1", "1"), ("toy-mistral.serve-chat", "1")])
def test_rehearsal_walks_the_cell_and_prints_no_metrics(cell, trace):
    p = _rehearse(cell, ["--trace", trace])
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    assert "NOT a chip result" in p.stdout
    assert "REHEARSAL finished: correct=True" in p.stdout
    assert '"metrics"' not in p.stdout
    checks = _checks(p.stdout)
    assert checks and all(checks.values())
    if "4chip" in cell:
        assert {"batch_shards", "optimizer_state_sharded_leaves", "devices_hold_same_params"} <= set(checks)
    assert "leftovers: none" in p.stdout


def _with_fault(tmp_path, cell, kind):
    """A copy of the toy root with the fault's kind file beside it and the
    cell's traffic mix pointed at it: faults are planted by a kind of the
    test's own, never through a switch of the harness."""
    root = str(tmp_path / "bench")
    shutil.copytree(TOY, root)
    shutil.copytree(os.path.join(HERE, "faults", "kinds"), os.path.join(root, "kinds"))
    traffic = next(c["traffic"] for c in json.load(open(os.path.join(root, "BENCHMARK.json")))["workloads"]
                   if c["name"] == cell)
    path = os.path.join(root, "traffic", traffic + ".json")
    mix = json.load(open(path))
    mix["kind"] = kind
    json.dump(mix, open(path, "w"))
    return root


@pytest.mark.parametrize("cell,kind,caught_by", [
    ("toy-gpt2.train-1chip", "train_frozen", "delta_norm_gap"),
    ("toy-gpt2.train-1chip", "train_wrong_lr", "delta_norm_gap"),
    ("toy-gpt2.train-1chip", "train_half_batch", "mu_norm_gap"),
    ("toy-mistral.serve-chat", "serve_head_rows", "widest_gap"),
])
def test_a_broken_timed_path_is_not_correct(tmp_path, cell, kind, caught_by):
    p = _rehearse(cell, root=_with_fault(tmp_path, cell, kind))
    assert p.returncode == 1, p.stdout[-2000:] + p.stderr[-2000:]
    assert "REHEARSAL finished: correct=False" in p.stdout
    assert _checks(p.stdout)[caught_by] is False


def test_a_replica_that_compiled_its_programs_is_replaced_before_the_window(tmp_path):
    """The first run of a checkout: the replica that filled the compile
    cache is stopped and the one that is measured starts from the cache,
    as in every later run; nothing of the first is left behind."""
    root = _with_fault(tmp_path, "toy-mistral.serve-chat", "serve_cold_once")
    p = _rehearse("toy-mistral.serve-chat", root=root, PB_TEST_MARK_DIR=str(tmp_path))
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    assert "compiled 20 programs into the cache" in p.stdout and "was replaced by one that loads them" in p.stdout
    assert "REHEARSAL finished: correct=True" in p.stdout and "leftovers: none" in p.stdout
    again = _rehearse("toy-mistral.serve-chat", root=root, PB_TEST_MARK_DIR=str(tmp_path))
    assert again.returncode == 0 and "was replaced" not in again.stdout


@pytest.mark.parametrize("cell", ["toy-untied.forward", "toy-untied.train-1chip"])
def test_a_third_family_and_a_new_kind_are_files_only(tmp_path, cell):
    """``third/`` holds an architecture the harness has no file for
    (``families/gpt2_untied.py``) and a kind of cell it has none for
    (``kinds/forward.py`` with ``refs/forward.py``), with their data files
    and entries. Run from a temporary directory: no file of the harness
    is touched, and the harness runs both the new kind and, with the new
    family, a kind of its own."""
    root = str(tmp_path / "elsewhere")
    shutil.copytree(os.path.join(HERE, "third"), root)
    p = _rehearse(cell, ["--trace", "1"], root=root, seconds="1")
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    assert "REHEARSAL finished: correct=True" in p.stdout
    checks = _checks(p.stdout)
    assert checks.get("loss_abs") is True


def test_an_unknown_kind_or_family_is_an_error_that_names_the_file(tmp_path):
    root = str(tmp_path / "bench")
    shutil.copytree(TOY, root)
    path = os.path.join(root, "traffic", "train-1chip.json")
    json.dump(dict(json.load(open(path)), kind="no_such_kind"), open(path, "w"))
    p = _rehearse("toy-gpt2.train-1chip", root=root)
    assert p.returncode == 2 and "kinds/no_such_kind.py" in p.stderr
    path = os.path.join(root, "configs", "toy-mistral.json")
    json.dump(dict(json.load(open(path)), model_type="no-such-family"), open(path, "w"))
    p = _rehearse("toy-mistral.serve-chat", root=root)
    assert p.returncode == 2 and "families/no_such_family.py" in p.stderr


def test_a_fourth_cell_is_files_and_entries_only(tmp_path):
    """A new configuration, a new traffic mix, a new per-layer metric and
    the cell that joins them, added beside a copy of the toy root: no file
    of the harness is touched, and the harness runs the cell."""
    root = str(tmp_path / "bench")
    shutil.copytree(TOY, root)
    cfg = json.load(open(os.path.join(root, "configs", "toy-gpt2.json")))
    cfg.update(n_layer=3)
    cfg["program_config"]["n_layer"] = 3
    json.dump(cfg, open(os.path.join(root, "configs", "toy-gpt2-d3.json"), "w"))
    mix = json.load(open(os.path.join(root, "traffic", "train-1chip.json")))
    mix.update(per_chip_batch=2)
    json.dump(mix, open(os.path.join(root, "traffic", "train-small-batch.json"), "w"))
    shutil.copy(os.path.join(root, "limits", "toy-gpt2.train-1chip.json"),
                os.path.join(root, "limits", "toy-gpt2-d3.train-small-batch.json"))
    os.makedirs(os.path.join(root, "metrics"), exist_ok=True)
    with open(os.path.join(root, "metrics", "steps_per_dispatch.py"), "w") as f:
        f.write("def read(ctx):\n    w = ctx['program']['window']\n    return w['steps'] / w['dispatches']\n")
    b = json.load(open(os.path.join(root, "BENCHMARK.json")))
    b["configs"].append({"name": "toy-gpt2-d3", "source": "toy", "file": "configs/toy-gpt2-d3.json",
                         "reduced": [], "why": "toy"})
    b["workloads"].append({"name": "toy-gpt2-d3.train-small-batch", "config": "toy-gpt2-d3",
                           "traffic": "train-small-batch", "chips": 1, "why": "toy"})
    b["end_to_end"][0]["workloads"].append("toy-gpt2-d3.train-small-batch")
    b["per_layer"].append({"name": "steps_per_dispatch", "unit": "count", "better": "higher",
                           "source": "program_counter", "layer": "trainer",
                           "moves": "train_tokens_per_s_per_chip",
                           "workloads": ["toy-gpt2-d3.train-small-batch"]})
    json.dump(b, open(os.path.join(root, "BENCHMARK.json"), "w"))
    p = _rehearse("toy-gpt2-d3.train-small-batch", ["--trace", "1"], root=root)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    assert "REHEARSAL finished: correct=True" in p.stdout
    # and its reader is found by name
    sys.path.insert(0, os.path.join(ROOT, "perfbench"))
    from pb.spec import Spec

    spec = Spec(root)
    names = [m["name"] for m in spec.per_layer("toy-gpt2-d3.train-small-batch", ["train_tokens_per_s_per_chip"])]
    assert names == ["steps_per_dispatch"]
    assert spec.reader("steps_per_dispatch")({"program": {"window": {"steps": 8, "dispatches": 2}}}) == 4


def test_no_chip_is_an_error_and_prints_no_result():
    p = _run(["--workload", "gpt2-medium.train-1chip", "--seed", "1", "--seconds", "1", "--trace", "0"], timeout=300)
    assert p.returncode == 2
    assert "no accelerator" in p.stderr
    assert '"correct"' not in p.stdout and '"metrics"' not in p.stdout


def test_alone_in_a_directory_is_an_error(tmp_path):
    """Only BENCHMARK.json and the files under ``paths``: nothing to measure."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "perfbench"), tmp_path / "perfbench")
    p = subprocess.run([sys.executable, "perfbench/run.py", "--workload", "gpt2-medium.train-1chip", "--seed", "1",
                        "--seconds", "1", "--trace", "0"], cwd=tmp_path, timeout=120,
                       stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout


def test_an_unknown_cell_is_an_error():
    p = _run(["--workload", "no-such-cell", "--seed", "1"], timeout=120)
    assert p.returncode == 2 and "unknown workload" in p.stderr
