"""Find a cell's files by the names ``BENCHMARK.json`` gives.

A cell is ``{name, config, traffic, chips}``. Its configuration is the
``file`` of the ``configs`` entry; its traffic mix is
``<path>/traffic/<traffic>.json``, its check limits
``<path>/limits/<cell>.json`` and each per-layer metric's reader
``<path>/metrics/<metric>.py`` (or the one its ``<metric>.json`` names),
looked for under every directory in ``paths``. The configuration's ``model_type`` names its family
(``families/<model_type>.py``), the mix's ``kind`` the driver of the cell
(``kinds/<kind>.py``), and a size distribution, an arrival process or a
way of sharing that the generator does not have built in a piece of it
(``generators/<name>.py``): see ``pb/plug.py``. Adding a cell, a
configuration, an architecture, a kind of cell or a metric is adding
files and entries; nothing here names one.
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys
from typing import Any, Callable, Dict, List, Optional

HARNESS_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


class SpecError(RuntimeError):
    pass


class Spec:
    """``BENCHMARK.json`` under ``root`` and the data files it names."""

    def __init__(self, root: str = HARNESS_ROOT) -> None:
        self.root = os.path.abspath(root)
        path = os.path.join(self.root, "BENCHMARK.json")
        if not os.path.isfile(path):
            raise SpecError(f"no BENCHMARK.json under {self.root}")
        with open(path) as f:
            self.bench: Dict[str, Any] = json.load(f)
        # ``families``, ``kinds`` and ``generators`` are imported by name from
        # every directory of ``paths`` (and from the harness's own).
        for p in [os.path.join(HARNESS_ROOT, "perfbench")] + [
            os.path.join(self.root, p) for p in self.bench["paths"]
        ]:
            p = os.path.abspath(p)
            if p not in sys.path:
                sys.path.insert(1, p)

    # -- lookup ----------------------------------------------------------
    def _find(self, rel: str) -> Optional[str]:
        for p in self.bench["paths"]:
            cand = os.path.join(self.root, p, rel)
            if os.path.isfile(cand):
                return cand
        # A root other than the harness's own (a test's, the rehearsal's)
        # falls back on the harness's files: its metric readers above all.
        cand = os.path.join(HARNESS_ROOT, "perfbench", rel)
        return cand if os.path.isfile(cand) else None

    def _json(self, rel: str, what: str) -> Dict[str, Any]:
        path = self._find(rel)
        if path is None:
            raise SpecError(
                f"{what}: no {rel} under any of paths={self.bench['paths']}"
            )
        with open(path) as f:
            return json.load(f)

    def cell(self, name: str) -> Dict[str, Any]:
        for c in self.bench["workloads"]:
            if c["name"] == name:
                return c
        raise SpecError(
            f"unknown workload {name!r}; BENCHMARK.json has "
            f"{[c['name'] for c in self.bench['workloads']]}"
        )

    def config(self, name: str) -> Dict[str, Any]:
        for c in self.bench["configs"]:
            if c["name"] == name:
                with open(os.path.join(self.root, c["file"])) as f:
                    return json.load(f)
        raise SpecError(f"unknown config {name!r}")

    def dims(self, cfg: Dict[str, Any]) -> Dict[str, Any]:
        """The sizes of a configuration as its family reads them, with the
        family's name among them: a plain dict, so that it travels to the
        worker and to the reference's process."""
        from pb import plug

        name = cfg.get("family") or cfg.get("model_type")
        if not name:
            raise SpecError("a configuration names its family by 'model_type' (or 'family')")
        return dict(plug.module("families", name).dims(cfg), family=plug.ident(name))

    def traffic(self, name: str) -> Dict[str, Any]:
        return self._json(f"traffic/{name}.json", f"traffic mix {name!r}")

    def limits(self, cell: str) -> Dict[str, Any]:
        return self._json(f"limits/{cell}.json", f"check limits of {cell!r}")

    # -- metrics ---------------------------------------------------------
    def end_to_end(self, cell: str) -> List[Dict[str, Any]]:
        return [
            m for m in self.bench["end_to_end"]
            if "workloads" not in m or cell in m["workloads"]
        ]

    def per_layer(self, cell: str, reported: List[str]) -> List[Dict[str, Any]]:
        """The per-layer metrics this cell reports: those that list it, and
        those with no list whose ``moves`` metric the cell reports."""
        out = []
        for m in self.bench["per_layer"]:
            if "workloads" in m:
                if cell in m["workloads"]:
                    out.append(m)
            elif m["moves"] in reported:
                out.append(m)
        return out

    def reader(self, metric: str) -> Callable[[Dict[str, Any]], Optional[float]]:
        """``read(ctx) -> value or None`` from ``metrics/<metric>.py``, or from
        the ``metrics/<reader>.py`` that ``metrics/<metric>.json`` names: two
        entries that read one quantity (in cells that report different
        end-to-end metrics, or at two settings of a parameter) share a reader."""
        path = self._find(f"metrics/{self.metric_params(metric).get('reader', metric)}.py")
        if path is None:
            raise SpecError(f"per-layer metric {metric!r} has no reader file")
        mod_spec = importlib.util.spec_from_file_location(
            "pb_metric_" + metric.replace(".", "_").replace("-", "_"), path
        )
        mod = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(mod)
        return mod.read

    def metric_params(self, metric: str) -> Dict[str, Any]:
        """``metrics/<metric>.json``, a reader's own parameters; {} if none."""
        path = self._find(f"metrics/{metric}.json")
        if path is None:
            return {}
        with open(path) as f:
            return json.load(f)
