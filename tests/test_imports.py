"""Every module under src/repro must import.

Regression guard for the bug class where tests or launchers reference a
package that was never committed (repro.dist originally shipped that way):
a missing module now fails here instead of crashing collection elsewhere
or lying dormant until launch time.
"""
import ast
import importlib
import pathlib

import jax
import pytest

import repro

# Initialize the jax backend *before* importing repro.launch.dryrun: that
# module sets XLA_FLAGS=--xla_force_host_platform_device_count=512 at import
# for standalone use, which must not re-shape this test process's devices.
jax.devices()

_ROOT = pathlib.Path(list(repro.__path__)[0])


def _all_modules():
    mods = []
    for py in sorted(_ROOT.rglob("*.py")):
        rel = py.relative_to(_ROOT.parent)
        parts = list(rel.with_suffix("").parts)
        if parts[-1] == "__init__":
            parts = parts[:-1]
        mods.append(".".join(parts))
    return mods


MODULES = _all_modules()


def test_module_list_nonempty():
    assert len(MODULES) > 50, MODULES  # the repo has ~90 modules


@pytest.mark.parametrize("mod", MODULES)
def test_module_imports(mod):
    importlib.import_module(mod)


def _imported_modules(py: pathlib.Path, package: str):
    """(line, dotted name) of everything `py` imports, at any level of
    the file, relative imports resolved against `package`."""
    for node in ast.walk(ast.parse(py.read_text())):
        if isinstance(node, ast.Import):
            for a in node.names:
                yield node.lineno, a.name
        elif isinstance(node, ast.ImportFrom):
            base = node.module or ""
            if node.level:
                parts = package.split(".")[:len(package.split("."))
                                           - node.level + 1]
                base = ".".join(parts + ([base] if base else []))
            for a in node.names:
                yield node.lineno, f"{base}.{a.name}"


def test_core_never_imports_explore():
    """`repro.core` sits below `repro.explore`: no module under core
    imports it, at module level or inside a function."""
    bad = [f"{py.name}:{line} {name}"
           for py in sorted((_ROOT / "core").rglob("*.py"))
           for line, name in _imported_modules(py, "repro.core")
           if name == "repro.explore" or name.startswith("repro.explore.")]
    assert not bad, bad
