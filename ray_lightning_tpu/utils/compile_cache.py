"""Where JAX's persistent compilation cache lives.

One rule for every entry point (``chip_smoke.py``, ``perfbench/run.py``,
``tools/*.py``, ``rlt``): ``JAX_COMPILATION_CACHE_DIR`` decides. When the
operator set it, nothing here touches it. When it is unset, it is set — in
``os.environ``, so fabric workers inherit it through their exec
environment — to ``<checkout>/.jax_cache``. The directory is part of the
cache key, so it never depends on a pid, a clock or a temp dir: two
processes of one run, and two runs from one checkout, resolve the same
path and share compiled programs.
"""
from __future__ import annotations

import os
import sys

_ENV = "JAX_COMPILATION_CACHE_DIR"
#: The directory holding the ``ray_lightning_tpu`` package (the checkout
#: root when run from source).
_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def place_compile_cache() -> str:
    """Resolve the cache directory (see module docstring) and return it.

    Call before any worker is spawned. A process that already imported jax
    read the variable at import time, so the default is also applied to
    its live config; an operator-set variable needs no such step.
    """
    path = os.environ.get(_ENV)
    if path:
        return path
    path = os.path.join(_CHECKOUT, ".jax_cache")
    os.environ[_ENV] = path
    if "jax" in sys.modules:
        import jax

        jax.config.update("jax_compilation_cache_dir", path)
    return path

