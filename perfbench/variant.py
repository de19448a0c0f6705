"""Make a variant of a cell as a benchmark root of its own.

    python3 perfbench/variant.py <out_dir> <cell> [traffic.key.path=value ...]

The harness takes no switch that changes what a cell measures: a knee
sweep, or the serving check's control through the program's own int8
weights, is the same cell with one number of its traffic mix changed,
written out as data and run with ``--bench-root``:

    python3 perfbench/variant.py .perfbench_out/rate10 mistral-7b-v0.1-d8.serve-chat traffic.arrival.rate_rps=10
    python3 perfbench/run.py --bench-root .perfbench_out/rate10 --workload mistral-7b-v0.1-d8.serve-chat --seed 5 --seconds 20

    python3 perfbench/variant.py .perfbench_out/int8 mistral-7b-v0.1-d8.serve-chat traffic.replica.int8=true

Families, kinds and metric readers are still the harness's own
(``pb/spec.py`` falls back on them). Used by no run of the driver.
"""
from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)


def main(argv: list) -> int:
    if len(argv) < 2:
        print(__doc__, file=sys.stderr)
        return 2
    from pb.spec import Spec

    out, name, edits = argv[0], argv[1], argv[2:]
    spec = Spec(os.path.dirname(HERE))
    cell = spec.cell(name)
    files = {
        "traffic": (f"traffic/{cell['traffic']}.json", spec.traffic(cell["traffic"])),
        "config": (f"configs/{cell['config']}.json", spec.config(cell["config"])),
        "limits": (f"limits/{name}.json", spec.limits(name)),
    }
    for e in edits:
        path, value = e.split("=", 1)
        which, *keys = path.split(".")
        node = files[which][1]
        for k in keys[:-1]:
            node = node.setdefault(k, {})
        node[keys[-1]] = json.loads(value)
    for rel, data in files.values():
        os.makedirs(os.path.dirname(os.path.join(out, rel)), exist_ok=True)
        with open(os.path.join(out, rel), "w") as f:
            json.dump(data, f, indent=1)
    bench = dict(spec.bench, paths=["."], workloads=[cell])
    bench["configs"] = [dict(c, file=files["config"][0]) for c in spec.bench["configs"] if c["name"] == cell["config"]]
    with open(os.path.join(out, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f, indent=1)
    print(f"{out}: {name} with {edits or 'nothing changed'}; run it with --bench-root {out}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
