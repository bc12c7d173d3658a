"""The dependencies point one way: ``launch/train`` -> ``core``/``fed`` ->
``models``/``kernels``. No module of the engine, the models or the kernels
imports the launcher package."""
import ast
from pathlib import Path

import pytest

SRC = Path(__file__).resolve().parents[1] / "src"
MODULES = sorted(p.relative_to(SRC).as_posix()
                 for pkg in ("models", "core", "kernels")
                 for p in (SRC / "repro" / pkg).rglob("*.py"))


def _imports(tree):
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            yield from (a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom) and node.module:
            yield node.module
            yield from (f"{node.module}.{a.name}" for a in node.names)


@pytest.mark.parametrize("module", MODULES)
def test_module_imports_nothing_from_launch(module):
    tree = ast.parse((SRC / module).read_text(), filename=module)
    bad = [m for m in _imports(tree)
           if m == "repro.launch" or m.startswith("repro.launch.")]
    assert not bad, f"{module} imports {bad}"
