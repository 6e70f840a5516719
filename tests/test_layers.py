"""The package's import arrows point one way: down the layer table.

Each module of ``src/repro`` gets one case that checks two things:

* it imports in process (a stale import of a deleted module fails here);
* the ``repro.*`` modules it reaches through module-level imports, read
  from the AST and followed transitively, sit in its own layer or a
  lower one.

The module list is read from the tree, so a new module whose package
has no entry in :data:`LAYERS` fails its case until it is given one.
Imports inside a function body run at call time and are not checked.
"""

import ast
import importlib
import pathlib

import pytest

import repro

# Lowest first.  The key is the name under ``repro`` (a subpackage or a
# top-level module); ``""`` is the ``repro`` package itself.
LAYERS = {
    "quant": 0, "obs": 0, "compile_cache": 0,
    "dist": 1, "launch": 1,
    "core": 2,
    "kernels": 3,
    "analysis": 4,
    "tune": 5,
    "dispatch": 6,
    "streaming": 7,
    "serving": 8,
    "api": 9, "": 9,
}

SRC = pathlib.Path(repro.__file__).resolve().parent


def _module_name(path: pathlib.Path) -> str:
    parts = path.relative_to(SRC.parent).with_suffix("").parts
    if parts[-1] == "__init__":
        parts = parts[:-1]
    return ".".join(parts)


MODULES = {_module_name(p): p for p in sorted(SRC.rglob("*.py"))}


def _unit(module: str) -> str:
    parts = module.split(".")
    return parts[1] if len(parts) > 1 else ""


def _module_level_imports(module: str) -> set[str]:
    """``repro.*`` modules named by imports outside any function body."""
    path = MODULES[module]
    package = module if path.name == "__init__.py" else \
        module.rpartition(".")[0]
    found = set()

    def visit(node):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.Lambda)):
                continue
            if isinstance(child, ast.Import):
                found.update(a.name for a in child.names)
            elif isinstance(child, ast.ImportFrom):
                base = child.module or ""
                if child.level:
                    parts = package.split(".")
                    parts = parts[:len(parts) - (child.level - 1)]
                    base = ".".join(parts + ([base] if base else []))
                for alias in child.names:
                    sub = f"{base}.{alias.name}"
                    found.add(sub if sub in MODULES else base)
            visit(child)

    visit(ast.parse(path.read_text()))
    return {m for m in found if m.split(".")[0] == "repro"}


def _reached(module: str) -> set[str]:
    seen, todo = set(), [module]
    while todo:
        for dep in _module_level_imports(todo.pop()):
            if dep not in seen:
                seen.add(dep)
                if dep in MODULES:
                    todo.append(dep)
    return seen


@pytest.mark.parametrize("module", sorted(MODULES))
def test_module_imports_only_its_layer_or_lower(module):
    importlib.import_module(module)
    unit = _unit(module)
    assert unit in LAYERS, (
        f"{module}: package {unit!r} has no layer in tests/test_layers.py")
    upward = sorted(
        f"{dep} (layer {LAYERS.get(_unit(dep), 'none')})"
        for dep in _reached(module)
        if LAYERS.get(_unit(dep), len(LAYERS)) > LAYERS[unit])
    assert not upward, (f"{module} (layer {LAYERS[unit]}) imports upward: "
                        f"{', '.join(upward)}")
