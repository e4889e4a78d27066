"""Layering, by reading the source: the packages below the drivers of
``dist/`` do not know that ``slate_tpu.sched`` exists. The sharded
streams issue through the task graph; everything under them (the
single-engine streams of ``linalg/`` among it) runs without it."""

import ast
from pathlib import Path

import pytest

PKG = Path(__file__).resolve().parent.parent / "slate_tpu"


def _imports(path: Path):
    """Absolute dotted names a module imports, relative ones resolved
    against its own package; ``from a import b`` yields ``a.b``."""
    here = ("slate_tpu",) + path.relative_to(PKG).parent.parts
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            for alias in node.names:
                yield alias.name
        elif isinstance(node, ast.ImportFrom):
            base = list(here[:len(here) - node.level + 1]) \
                if node.level else []
            base += node.module.split(".") if node.module else []
            for alias in node.names:
                yield ".".join(base + [alias.name])


@pytest.mark.parametrize("package", ["core", "ops", "linalg", "parallel"])
def test_package_does_not_import_sched(package):
    found = [(str(path.relative_to(PKG)), name)
             for path in sorted((PKG / package).rglob("*.py"))
             for name in _imports(path)
             if (name + ".").startswith("slate_tpu.sched.")]
    assert found == []
