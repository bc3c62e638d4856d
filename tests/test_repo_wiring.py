"""Nothing in the tree points at a module, a name or a script that is gone.

Every module under ``mpcium_tpu/`` imports; every ``mpcium_tpu`` import
in the entry points (``scripts/*.py``, ``bench.py``, ``chip_smoke.py``)
resolves, found by an ``ast`` walk so that no script's ``main`` runs; and
every script a ``Makefile`` recipe names exists. A deletion that leaves
one of these dangling fails here, not at an operator's prompt."""
import ast
import importlib
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
ENTRY_POINTS = sorted(
    p.relative_to(ROOT).as_posix()
    for p in [*ROOT.glob("scripts/*.py"), ROOT / "bench.py",
              ROOT / "chip_smoke.py"]
)


def _module_name(path: Path) -> str:
    parts = path.relative_to(ROOT).with_suffix("").parts
    return ".".join(parts[:-1] if parts[-1] == "__init__" else parts)


def test_every_package_module_imports():
    failed = {}
    for path in sorted((ROOT / "mpcium_tpu").rglob("*.py")):
        name = _module_name(path)
        try:
            importlib.import_module(name)
        except Exception as e:  # noqa: BLE001 — collect them all, then fail
            failed[name] = repr(e)
    assert not failed


def _package_imports(path: Path):
    """(module, imported name or None, line) for every absolute
    ``mpcium_tpu`` import anywhere in the file, function bodies too."""
    for node in ast.walk(ast.parse(path.read_text(), str(path))):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.name.split(".")[0] == "mpcium_tpu":
                    yield a.name, None, node.lineno
        elif (isinstance(node, ast.ImportFrom) and node.level == 0
              and (node.module or "").split(".")[0] == "mpcium_tpu"):
            for a in node.names:
                yield node.module, a.name, node.lineno


@pytest.mark.parametrize("rel", ENTRY_POINTS)
def test_entry_point_imports_resolve(rel):
    dangling = []
    for module, name, line in _package_imports(ROOT / rel):
        try:
            mod = importlib.import_module(module)
            if name not in (None, "*") and not hasattr(mod, name):
                importlib.import_module(f"{module}.{name}")
        except ImportError as e:
            dangling.append(f"{rel}:{line}: {module} {name or ''}: {e}")
    assert not dangling


def test_makefile_names_only_scripts_that_exist():
    named = set(re.findall(r"(?<![\w/.-])((?:scripts/)?[\w-]+\.(?:py|sh))\b",
                           (ROOT / "Makefile").read_text()))
    assert "scripts/check_all.py" in named and "bench.py" in named
    assert not sorted(n for n in named if not (ROOT / n).is_file())
