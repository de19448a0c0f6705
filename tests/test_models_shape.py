"""Guards of the shape ``models/`` and the engine took in PR 50, read off the
sources (``ast`` / ``inspect``; nothing is traced and no engine is built):
the arrows between the model modules run one way, the engine knows the model
by its public names, and the refactoring bought no option."""
import ast
import dataclasses
import inspect
import os

import pytest

PKG = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "ray_lightning_tpu")


def _imports(path):
    """``(module, name)`` of every ``from module import name`` and
    ``(module, None)`` of every ``import module`` in a source file, wherever
    in it (a function's lazy import is an import)."""
    with open(os.path.join(PKG, path)) as f:
        tree = ast.parse(f.read())
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            yield from ((node.module, alias.name) for alias in node.names)
        elif isinstance(node, ast.Import):
            yield from ((alias.name, None) for alias in node.names)


def _arrows_one_way():
    """``layers.py <- gpt.py -> mixed.py -> ssm.py``: neither ``mixed.py`` nor
    ``layers.py`` imports ``gpt.py``, and the leaves import no model module."""
    models = "ray_lightning_tpu.models"
    for path, allowed in (("models/mixed.py", {"layers", "ssm"}), ("models/layers.py", set()),
                          ("models/ssm.py", set()), ("parallel/moe.py", set())):
        for module, name in _imports(path):
            if module == models:
                assert name in allowed, f"{path} imports {name} from {models}"
            elif module.startswith(models + "."):
                assert module[len(models) + 1:] in allowed, f"{path} imports {module}"


def _engine_knows_public_names():
    for module, name in _imports("serve/engine.py"):
        if module.startswith(("ray_lightning_tpu.models", "ray_lightning_tpu.ops")):
            assert name is not None and not name.startswith("_"), f"serve/engine.py imports {name} from {module}"
            assert not module.rsplit(".", 1)[-1].startswith("_"), module


def _no_new_option():
    from ray_lightning_tpu import cli
    from ray_lightning_tpu.models.gpt import GPTConfig
    from ray_lightning_tpu.serve.engine import DecodeEngine

    assert len(inspect.signature(DecodeEngine.__init__).parameters) - 1 == 26  # self apart
    assert len(dataclasses.fields(GPTConfig)) == 50
    assert len(cli._SERVE_KEYS) == 87


@pytest.mark.parametrize("guard", [_arrows_one_way, _engine_knows_public_names, _no_new_option],
                         ids=lambda f: f.__name__.strip("_"))
def test_the_model_modules_keep_the_shape_of_pr50(guard):
    guard()
