"""Find a family, a kind of cell, the reference's side of a kind or a
piece of the traffic generator by its name: ``families/<name>.py``,
``kinds/<name>.py``, ``refs/<name>.py`` and ``generators/<name>.py``
under any directory of ``BENCHMARK.json``'s ``paths`` (``Spec`` puts
those on ``sys.path``; the four are packages without an ``__init__``,
so each directory adds to them). Nothing in the
harness names one: a later PR adds a file and edits none.

Found by import, not by path, so that the fit worker, the replica and the
reference's process — which get the parent's ``sys.path`` — find the
same module under the same name, and a class defined in one pickles by
reference.
"""
from __future__ import annotations

import importlib
import re
from typing import Any, Dict


class PlugError(RuntimeError):
    pass


def ident(name: str) -> str:
    """A published name as a module name (``deepseek-v3`` -> ``deepseek_v3``)."""
    return re.sub(r"[^0-9A-Za-z_]", "_", str(name))


def module(group: str, name: str) -> Any:
    try:
        return importlib.import_module(f"{group}.{ident(name)}")
    except ModuleNotFoundError as exc:
        if exc.name not in (group, f"{group}.{ident(name)}"):
            raise
        raise PlugError(
            f"no {group}/{ident(name)}.py under any directory of the benchmark's paths"
        ) from None


def family_of(dims: Dict[str, Any]) -> Any:
    return module("families", dims["family"])
