"""The reference's own process: ``python perfbench/pb/refproc.py <out_dir>``.

It starts only after the program's process has ended, so it has the chip
to itself and the program's peak memory stays the program's. It imports
nothing of the program; it reads the seed, the sizes and the inputs from
``<out_dir>/ref_in.json``, hands them to the reference's side of the
cell's kind (``refs/<kind>.py``, found by name) and writes
``<out_dir>/reference.json``.
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(out_dir: str) -> int:
    with open(os.path.join(out_dir, "ref_in.json")) as f:
        spec = json.load(f)
    import jax

    from pb import plug

    devs = jax.devices()
    if spec["require_tpu"] and devs[0].platform != "tpu":
        print(f"refproc: runs on {devs[0].platform!r}, not on a TPU", file=sys.stderr)
        return 2
    t0 = time.time()
    out = {"device": {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}}
    out.update(plug.module("refs", spec["kind"]).run(spec, out_dir, devs))
    out["seconds"] = time.time() - t0
    with open(os.path.join(out_dir, "reference.json"), "w") as f:
        json.dump(out, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
