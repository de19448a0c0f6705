"""What keeps a CPU run from passing for a chip run — checked without a
chip and without spawning an actor: the compile-cache rule, the chip probe,
chip pinning, ``chip_smoke.py``'s refusal, and the flash fallback warning."""
import logging
import os
import subprocess
import sys

import pytest

from ray_lightning_tpu import fabric
from ray_lightning_tpu.fabric import core

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


# -- the compile cache is placed from outside ------------------------------
def test_compile_cache_env_set_is_left_alone(monkeypatch, tmp_path):
    import jax

    from ray_lightning_tpu.utils.compile_cache import place_compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(_CACHE_ENV, str(tmp_path))
    assert place_compile_cache() == str(tmp_path)
    assert os.environ[_CACHE_ENV] == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # nothing set in code


def test_compile_cache_default_is_fixed_in_checkout(monkeypatch):
    import jax

    from ray_lightning_tpu.utils.compile_cache import place_compile_cache

    expected = os.path.join(REPO_ROOT, ".jax_cache")
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv(_CACHE_ENV, raising=False)
    try:
        assert place_compile_cache() == expected
        # In os.environ, so exec'd workers inherit it.
        assert os.environ[_CACHE_ENV] == expected
        assert place_compile_cache() == expected  # second call: same path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    # Another pid resolves the same directory: no pid, clock or temp dir.
    env = {k: v for k, v in os.environ.items() if k != _CACHE_ENV}
    env["PYTHONPATH"] = REPO_ROOT
    other = subprocess.run(
        [
            sys.executable, "-c",
            "from ray_lightning_tpu.utils.compile_cache import "
            "place_compile_cache as p; print(p())",
        ],
        capture_output=True, text=True, env=env, timeout=120, cwd="/",
    )
    assert other.stdout.strip().splitlines()[-1] == expected, other.stderr[-500:]


# -- the chip probe fails loud ---------------------------------------------
@pytest.fixture
def probing(monkeypatch):
    """An environment in which capacity detection really probes."""
    monkeypatch.delenv("RLT_NUM_TPU_CHIPS", raising=False)
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.delenv("RLT_REQUIRE_TPU", raising=False)

    def fake(result):
        def run(*args, **kwargs):
            if isinstance(result, BaseException):
                raise result
            return result

        monkeypatch.setattr(subprocess, "run", run)

    return fake


def test_probe_crash_raises_with_child_stderr(probing):
    probing(subprocess.CompletedProcess([], 1, stdout="", stderr="libtpu: boom"))
    with pytest.raises(fabric.FabricError, match="libtpu: boom"):
        core._detect_local_capacity()


def test_probe_timeout_raises(probing):
    probing(subprocess.TimeoutExpired(cmd="probe", timeout=90, stderr=b"stuck"))
    with pytest.raises(fabric.FabricError, match="timed out.*stuck"):
        core._detect_local_capacity()


def test_probe_clean_zero_is_a_cpu_host(probing):
    probing(subprocess.CompletedProcess([], 0, stdout="warn\n0\n", stderr=""))
    assert "TPU" not in core._detect_local_capacity()


def test_use_tpu_auto_lets_probe_errors_through(monkeypatch):
    from ray_lightning_tpu.strategies import RayTPUStrategy

    def boom():
        raise fabric.FabricError("TPU probe exited with code 1")

    monkeypatch.setattr(fabric, "cluster_resources", boom)
    with pytest.raises(fabric.FabricError, match="probe"):
        RayTPUStrategy(num_workers=1).plan_workers()


# -- one process per chip ----------------------------------------------------
def test_pin_chips_gives_disjoint_aligned_groups():
    node = core.Node("n", "127.0.0.1", {"CPU": 8.0, "TPU": 4.0})
    assert core._pin_chips(node, "whole", {"TPU": 4.0}) == {}
    assert core._pin_chips(node, "cpu-only", {"CPU": 1.0}) == {}
    a = core._pin_chips(node, "a", {"TPU": 1.0})
    b = core._pin_chips(node, "b", {"TPU": 2.0})
    c = core._pin_chips(node, "c", {"TPU": 1.0})
    assert a["TPU_VISIBLE_CHIPS"] == "0" and c["TPU_VISIBLE_CHIPS"] == "1"
    assert b["TPU_VISIBLE_CHIPS"] == "2,3"
    assert b["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,2,1"
    assert a["TPU_PROCESS_BOUNDS"] == "1,1,1"
    assert a["TPU_PROCESS_PORT"] != b["TPU_PROCESS_PORT"]
    node.pinned.pop("a")
    assert core._pin_chips(node, "d", {"TPU": 1.0})["TPU_VISIBLE_CHIPS"] == "0"


@pytest.mark.parametrize("tpus", [3.0, 0.5])
def test_pin_chips_refuses_what_it_cannot_isolate(tpus):
    node = core.Node("n", "127.0.0.1", {"CPU": 8.0, "TPU": 4.0})
    with pytest.raises(fabric.FabricError, match="cannot isolate"):
        core._pin_chips(node, "a", {"TPU": tpus})
    assert not node.pinned


def test_refused_reservation_spawns_nothing_and_leaks_nothing(start_fabric):
    f = start_fabric(num_cpus=2, num_tpus=4)
    with pytest.raises(fabric.FabricError, match="cannot isolate"):
        f.remote(dict).options(num_tpus=3).remote()
    assert f.available_resources()["TPU"] == 4.0


def test_plan_part_of_a_host_is_one_pinned_actor(start_fabric):
    from ray_lightning_tpu.strategies import RayTPUStrategy

    start_fabric(num_cpus=4, num_tpus=4)
    plans, use_tpu = RayTPUStrategy(num_workers=2).plan_workers()
    assert use_tpu and len(plans) == 1 and plans[0].resources["TPU"] == 2.0
    with pytest.raises(ValueError, match="whole TPU hosts"):
        RayTPUStrategy(num_workers=6).plan_workers()


# -- chip_smoke.py runs on the chip only -------------------------------------
def test_chip_smoke_without_tpu_fails_fast_and_says_why():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "chip_smoke.py")],
        capture_output=True, text=True, timeout=120, cwd=REPO_ROOT,
        env={
            **{k: v for k, v in os.environ.items() if k != "RLT_NUM_TPU_CHIPS"},
            "JAX_PLATFORMS": "cpu",
        },
    )
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert '"ok"' not in proc.stdout  # no result line


# -- the flash fallback is visible -------------------------------------------
def test_flash_warns_once_per_shape_handed_to_the_reference(caplog):
    import jax.numpy as jnp

    from ray_lightning_tpu.ops.flash_attention import _warned, flash_attention

    _warned.clear()
    q = jnp.ones((1, 65, 2, 8))  # a 65-row block does not tile
    with caplog.at_level(logging.WARNING, logger="ray_lightning_tpu"):
        flash_attention(q, q, q, causal=False)
        flash_attention(q, q, q, causal=False)
    hits = [r for r in caplog.records if "attention_reference" in r.getMessage()]
    assert len(hits) == 1
    assert "(1, 65, 2, 8)" in hits[0].getMessage()
    assert "not 8-aligned" in hits[0].getMessage()
