"""A cell's root with the nine entries of ``metrics/waits.entries.json``
appended to its ``per_layer`` list, so that a traced run prints them:

    python3 perfbench/waits_root.py <out_dir> <cell> [traffic.key.path=value ...]
    python3 perfbench/run.py --bench-root <out_dir> --workload <cell> --seed 5 --seconds 30 --trace 1

``variant.py``'s root of the cell (whose edits it takes) with the entries
that list the cell. They are not in ``BENCHMARK.json`` until the hand-made
run of ``tests/perfbench/test_pb_arithmetic.py`` carries the blocks they
read (``metrics/waits.entries.json`` says why). Used by no run of the
driver.
"""
from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)


def main(argv: list) -> int:
    import variant

    rc = variant.main(argv)
    if rc:
        return rc
    out, cell = argv[0], argv[1]
    with open(os.path.join(HERE, "metrics", "waits.entries.json")) as f:
        entries = [m for m in json.load(f)["per_layer"] if cell in m["workloads"]]
    path = os.path.join(out, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["per_layer"] += entries
    with open(path, "w") as f:
        json.dump(bench, f, indent=1)
    print(f"{out}: with {[m['name'] for m in entries]}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
