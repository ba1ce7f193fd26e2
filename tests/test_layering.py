"""Which way the imports point between ``ray_tpu/models``, ``parallel`` and
``ops``, read off the source (no JAX, nothing imported): models are built
from ``models/lm.py`` (with its sibling ``models/exchange.py``),
``parallel/`` and ``ops/``; none of those knows a model, and no model
imports another: ``lm.py`` <- family, nothing sideways."""

import ast
import pathlib

import pytest

PACKAGE = pathlib.Path(__file__).resolve().parent.parent / "ray_tpu"
SHARED = ("lm", "exchange")
MODELS = sorted(p.stem for p in (PACKAGE / "models").glob("*.py")
                if p.stem not in ("__init__",) + SHARED)


def _imports(path):
    """[(module, name or None, name bound)] of every import in the file, at
    any depth: ``import a.b as c`` gives ("a.b", None, "c"), ``from a
    import b`` ("a", "b", "b")."""
    found = []
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            found += [(alias.name, None, alias.asname or alias.name)
                      for alias in node.names]
        elif isinstance(node, ast.ImportFrom):
            base = "." * node.level + (node.module or "")
            found += [(base, alias.name, alias.asname or alias.name)
                      for alias in node.names]
    return found


def _model_modules(path):
    """{name bound in the file: model it comes from} for every import of, or
    from, a module of ray_tpu.models other than lm."""
    bound = {}
    for module, name, bound_as in _imports(path):
        model = name if module == "ray_tpu.models" else \
            module.removeprefix("ray_tpu.models.")
        if model in MODELS:
            bound[bound_as] = model
    return bound


def test_the_readers_read_what_they_are_meant_to(tmp_path):
    source = tmp_path / "m.py"
    source.write_text(
        "import ray_tpu.models.gpt as g\n"
        "from ray_tpu.models import gpt as _gpt, lm\n"
        "def f():\n    from ray_tpu.models.t5 import _block\n")
    assert _imports(source) == [
        ("ray_tpu.models.gpt", None, "g"), ("ray_tpu.models", "gpt", "_gpt"),
        ("ray_tpu.models", "lm", "lm"),
        ("ray_tpu.models.t5", "_block", "_block")]
    assert _model_modules(source) == {"g": "gpt", "_gpt": "gpt",
                                      "_block": "t5"}


@pytest.mark.parametrize("directory", ["parallel", "ops"])
def test_nothing_under_models_is_imported_from_below(directory):
    offenders = [
        (str(path.relative_to(PACKAGE)), module, name)
        for path in sorted((PACKAGE / directory).rglob("*.py"))
        for module, name, _ in _imports(path)
        if module.startswith("ray_tpu.models")
        or (module == "ray_tpu" and name == "models")]
    assert not offenders


@pytest.mark.parametrize("shared", SHARED)
def test_what_the_models_share_imports_no_model(shared):
    path = PACKAGE / "models" / f"{shared}.py"
    assert not _model_modules(path)
    assert not [(module, name) for module, name, _ in _imports(path)
                if module.startswith(".")]


@pytest.mark.parametrize("model", MODELS)
def test_no_model_imports_another_model(model):
    """What two families share lives in ``models/lm.py``; a family that
    wants another's function moves it there."""
    assert not _model_modules(PACKAGE / "models" / f"{model}.py")
