"""One process per chip: what libtpu does, asked on the chip.

Re-establishes the facts ``fabric/core.py`` rests on (PERF.md "Chip
bring-up" records the last answers). This process stays off JAX; every
experiment runs in children with time limits, and every child is killed
before the next experiment. Prints one JSON line per experiment and writes
them to ``--out``.

  whole  an unpinned child opens every chip of the host.
  two    a second unpinned child while the first holds the chips.
  kill   ``fabric.kill`` of an actor holding the chips, then at once a new
         one; then the same after SIGKILL of a raw child, and whether
         /tmp/libtpu_lockfile is left behind.
  pin    (hosts with more than one chip) actors reserving 1 and 2 of N
         chips through the fabric: the devices each sees, two at once.
"""
import argparse
import json
import os
import signal
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LOCKFILE = "/tmp/libtpu_lockfile"

#: A raw child: open the backend, say what it sees, hold it until killed.
HOLD = (
    "import jax, time; d = jax.devices(); "
    "print('DEVICES', len(d), d[0].platform, flush=True); time.sleep(600)"
)


def start_child() -> subprocess.Popen:
    return subprocess.Popen(
        [sys.executable, "-c", HOLD],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
    )


def wait_devices(proc: subprocess.Popen, timeout: float) -> dict:
    """Wait for the child's DEVICES line, its death, or the time limit."""
    import selectors

    t0 = time.monotonic()
    sel = selectors.DefaultSelector()
    sel.register(proc.stdout, selectors.EVENT_READ)
    out: dict = {}
    while time.monotonic() - t0 < timeout:
        if sel.select(timeout=0.5):
            line = proc.stdout.readline()
            if line.startswith("DEVICES"):
                _, n, platform = line.split()
                out = {"devices": int(n), "platform": platform}
                break
        if proc.poll() is not None:
            break
    out["elapsed_s"] = round(time.monotonic() - t0, 1)
    if not out.get("devices"):
        if proc.poll() is None:
            out["outcome"] = f"no answer in {timeout:.0f} s (hung)"
        else:
            out["outcome"] = f"exited {proc.returncode}"
            out["stderr_tail"] = proc.stderr.read()[-1200:]
    return out


def reap(proc: subprocess.Popen) -> None:
    if proc.poll() is None:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(20)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(20)


def exp_whole() -> dict:
    a = start_child()
    try:
        return {"experiment": "whole", **wait_devices(a, 120)}
    finally:
        reap(a)


def exp_two() -> dict:
    a = start_child()
    b = None
    try:
        first = wait_devices(a, 120)
        b = start_child()
        second = wait_devices(b, 90)
        return {"experiment": "two", "first": first, "second": second}
    finally:
        for p in (b, a):
            if p is not None:
                reap(p)


class Holder:
    """Fabric actor: opens the backend in its constructor."""

    def __init__(self) -> None:
        import jax

        self.devs = jax.devices()

    def devices(self) -> dict:
        import jax.numpy as jnp

        x = jnp.ones((1024, 1024), jnp.bfloat16)
        y = float((x @ x).sum())  # the chips answer, not just enumerate
        return {
            "n": len(self.devs),
            "platform": self.devs[0].platform,
            "coords": [list(getattr(d, "coords", ())) for d in self.devs],
            "visible_chips": os.environ.get("TPU_VISIBLE_CHIPS"),
            "matmul_sum": y,
        }


def exp_kill(chips: int) -> list:
    from ray_lightning_tpu import fabric

    rows = []
    cls = fabric.remote(Holder)
    a = cls.options(num_tpus=chips, init_timeout=180).remote()
    fabric.get(a.devices.remote(), timeout=120)
    t0 = time.monotonic()
    fabric.kill(a)
    kill_s = round(time.monotonic() - t0, 1)
    t1 = time.monotonic()
    row = {"experiment": "kill:fabric.kill", "kill_s": kill_s}
    try:
        b = cls.options(num_tpus=chips, init_timeout=180).remote()
        row["next_actor"] = fabric.get(b.devices.remote(), timeout=120)
        fabric.kill(b)
    except Exception as exc:  # noqa: BLE001 - the failure IS the record
        row["next_actor_error"] = f"{type(exc).__name__}: {exc}"[:1200]
    row["next_actor_s"] = round(time.monotonic() - t1, 1)
    row["lockfile_after"] = os.path.exists(LOCKFILE)
    rows.append(row)

    c = start_child()
    wait_devices(c, 120)
    c.kill()  # SIGKILL: no atexit, no PJRT teardown
    c.wait(20)
    row = {"experiment": "kill:SIGKILL", "lockfile_after": os.path.exists(LOCKFILE)}
    d = start_child()
    try:
        row["next_child"] = wait_devices(d, 120)
    finally:
        reap(d)
    rows.append(row)
    return rows


def exp_pin(chips: int) -> list:
    from ray_lightning_tpu import fabric

    rows = []
    cls = fabric.remote(Holder)
    for k in (1, 2):
        if k >= chips:
            continue
        # As many k-chip actors as fit, opening their chips at once.
        actors = [
            cls.options(num_tpus=k, lazy_init=True).remote()
            for _ in range(chips // k)
        ]
        try:
            rows.append(
                {
                    "experiment": f"pin:{k}of{chips}",
                    "actors": fabric.get(
                        [a.devices.remote() for a in actors], timeout=180
                    ),
                }
            )
        except Exception as exc:  # noqa: BLE001 - the failure IS the record
            rows.append(
                {
                    "experiment": f"pin:{k}of{chips}",
                    "error": f"{type(exc).__name__}: {exc}"[:1500],
                }
            )
        finally:
            for a in actors:
                fabric.kill(a)
    return rows


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--only", default="whole,two,kill,pin")
    p.add_argument(
        "--out", default=os.path.join(HERE, "chiprun_out", "chip_probe.json")
    )
    args = p.parse_args()
    sys.path.insert(0, HERE)  # run as `python tools/chip_probe.py`
    from ray_lightning_tpu import fabric

    fabric.init()
    chips = int(fabric.cluster_resources().get("TPU", 0))
    if chips < 1:
        print("chip_probe: needs a TPU host", file=sys.stderr)
        return 2
    rows: list = [{"experiment": "host", "chips": chips}]
    printed = 0
    try:
        for name in args.only.split(","):
            if name == "whole":
                rows.append(exp_whole())
            elif name == "two":
                rows.append(exp_two())
            elif name == "kill":
                rows.extend(exp_kill(chips))
            elif name == "pin":
                rows.extend(exp_pin(chips))
            for row in rows[printed:]:
                print(json.dumps(row), flush=True)
            printed = len(rows)
    finally:
        fabric.shutdown()
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
