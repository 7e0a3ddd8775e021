"""The one-way picture of the language models, held by their sources (read
with ``ast``; nothing is imported, no jax): ``models/layers.py`` <- one file
per model <- ``experiments/lm.py`` <- one file per experiment. No model
imports another of the seven, none takes an underscore name from any module,
and no experiment imports another: what two of them need lives in
``models/layers.py`` or ``experiments/lm.py``."""

import ast
import os

import pytest

PACKAGE = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "network_distributed_pytorch_tpu")
MODELS = ("nemotron_h", "afmoe", "qwen3_next", "lfm2", "mellum", "phi4flash", "sdar")
EXPERIMENTS = tuple(f"powersgd_{name}" for name in ("nemotron", "afmoe", "qwen3_next", "lfm2", "mellum", "phi4flash", "sdar"))


def imports_of(folder: str, module: str):
    """Every import of ``folder/module.py``, inside functions too, as
    ``(the module's dotted path as written, the names taken from it)``."""
    with open(os.path.join(PACKAGE, folder, f"{module}.py")) as f:
        tree = ast.parse(f.read())
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            yield "." * node.level + (node.module or ""), [alias.name for alias in node.names]
        elif isinstance(node, ast.Import):
            for alias in node.names:
                yield alias.name, []


def siblings_imported(folder: str, module: str, siblings) -> set:
    others = set(siblings) - {module}
    found = set()
    for path, names in imports_of(folder, module):
        found |= others & set(path.split("."))  # from .afmoe import ..., from ..models.afmoe import ...
        if path.strip(".") in ("", folder):  # from . import afmoe, from ..models import afmoe
            found |= others & set(names)
    return found


@pytest.mark.parametrize("module", MODELS)
def test_a_language_model_imports_no_other_and_no_underscore_name(module):
    assert not siblings_imported("models", module, MODELS)
    private = [(path, name) for path, names in imports_of("models", module) for name in names if name.startswith("_")]
    assert not private, private


@pytest.mark.parametrize("module", EXPERIMENTS)
def test_a_language_model_experiment_imports_no_other(module):
    assert not siblings_imported("experiments", module, EXPERIMENTS)
