"""Test configuration.

Tests run on CPU with 8 virtual XLA devices (the JAX analog of the
reference's fake clusters, per SURVEY.md §4): JAX_PLATFORMS=cpu +
--xla_force_host_platform_device_count=8 must be set before jax is imported
anywhere in the test process. Real-TPU tests are gated behind RLT_TPU=1,
mirroring the reference's CLUSTER=1 gate (test_ddp_gpu.py:126-129).
"""
import os

# Must happen before any jax import (including transitive ones).
os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
# Workers inherit the same virtual-device config unless a test overrides it.
os.environ.setdefault("RLT_NUM_TPU_CHIPS", "0")

import pytest  # noqa: E402


@pytest.fixture
def start_fabric():
    """Init the fabric with given resources; always shut down after the test."""
    from ray_lightning_tpu import fabric

    created = []

    def _start(**kwargs):
        fabric.init(**kwargs)
        created.append(True)
        return fabric

    yield _start
    fabric.shutdown()


@pytest.fixture
def fabric_head():
    """Start a fabric head server subprocess; yield its host:port address.

    Shared by the client-mode suites (tests/test_client.py, test_cli.py).
    A reader thread owns the server's stdout: the boot wait has a real
    timeout even if the server wedges before printing its ready line, and
    the pipe keeps draining for the whole test so the server (and workers
    sharing its stdout) can never block on a full pipe buffer.
    """
    import queue
    import subprocess
    import sys
    import threading
    import time

    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env["PYTHONPATH"] = os.pathsep.join(
        [repo_root, env.get("PYTHONPATH", "")]
    ).rstrip(os.pathsep)
    proc = subprocess.Popen(
        [sys.executable, "-m", "ray_lightning_tpu.fabric.server",
         "--port", "0", "--num-cpus", "8"],
        env=env,
        stdout=subprocess.PIPE,
        text=True,
    )
    lines: "queue.Queue[str]" = queue.Queue()

    def _drain() -> None:
        for line in proc.stdout:
            lines.put(line)

    threading.Thread(target=_drain, daemon=True).start()

    address = None
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            raise RuntimeError("fabric server died during boot")
        try:
            line = lines.get(timeout=0.5)
        except queue.Empty:
            continue
        if line.startswith("FABRIC_SERVER_READY"):
            parts = line.split()
            address = parts[1]
            # Per-server generated key (Jupyter-token model): hand it to
            # the client side via the env var, which also flows into CLI
            # subprocess tests that copy os.environ.
            key = next(
                (p[len("key=") :] for p in parts[2:] if p.startswith("key=")),
                None,
            )
            break
    assert address, "server never printed ready line"
    prev_key = os.environ.get("RLT_FABRIC_AUTHKEY")
    if key:
        os.environ["RLT_FABRIC_AUTHKEY"] = key
    try:
        yield address
    finally:
        if key:
            if prev_key is None:
                os.environ.pop("RLT_FABRIC_AUTHKEY", None)
            else:
                os.environ["RLT_FABRIC_AUTHKEY"] = prev_key
        from ray_lightning_tpu.fabric import client

        client.disconnect()
        proc.terminate()
        proc.wait(timeout=30)


def pytest_collection_modifyitems(config, items):
    if os.environ.get("RLT_TPU") != "1":
        skip_tpu = pytest.mark.skip(reason="needs real TPU (set RLT_TPU=1)")
        for item in items:
            if "tpu_hw" in item.keywords:
                item.add_marker(skip_tpu)
