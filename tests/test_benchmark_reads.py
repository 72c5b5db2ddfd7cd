"""The seam the benchmark reads: every name that a file under ``perfbench/``
or ``tests/perfbench/`` takes from ``ray_tpu`` resolves.

Those two directories are closed to most PRs (``BENCHMARK.json``'s ``paths``),
so a refactor that moves or renames what they import cannot repair them: it
has to keep the name. Each file is parsed with ``ast`` (nothing of it runs):
every ``import ray_tpu...`` / ``from ray_tpu... import name`` and every
attribute chain read off a name so bound (``llama._paged_attention_block``,
``latent.attend_masked``, ``glm_dsa.MODEL.cache_layout``) is one case
``(module, dotted name)``, and the case passes when ``getattr`` walks it. An
attribute of an INSTANCE the benchmark builds (``runner.params``) is not
seen: its tests are ``tests/perfbench/``'s own.
"""

from __future__ import annotations

import ast
import glob
import importlib
import importlib.util
import os
from typing import Dict, List, Set, Tuple

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
READERS = ("perfbench", os.path.join("tests", "perfbench"))


def _is_module(name: str) -> bool:
    try:
        return importlib.util.find_spec(name) is not None
    except (ImportError, ValueError):
        return False


def _chain(node: ast.AST) -> List[str]:
    """``a.b.c`` as ``["a", "b", "c"]``; empty where the root is no plain name."""
    names: List[str] = []
    while isinstance(node, ast.Attribute):
        names.append(node.attr)
        node = node.value
    if not isinstance(node, ast.Name):
        return []
    return [node.id, *reversed(names)]


def _reads_of(path: str) -> Set[Tuple[str, str]]:
    """``{(module, dotted name)}`` one file reads of ``ray_tpu``; a bare import
    of a module is ``(module, "")``."""
    with open(path) as f:
        tree = ast.parse(f.read(), path)
    bound: Dict[str, Set[Tuple[str, str]]] = {}  # a local name -> what it may be: (module, dotted name)
    reads: Set[Tuple[str, str]] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                if alias.name.split(".")[0] != "ray_tpu":
                    continue
                reads.add((alias.name, ""))
                if alias.asname:
                    bound.setdefault(alias.asname, set()).add((alias.name, ""))
                else:  # ``import ray_tpu.a.b`` binds ``ray_tpu``
                    bound.setdefault("ray_tpu", set()).add(("ray_tpu", ""))
        elif isinstance(node, ast.ImportFrom) and node.level == 0 and node.module:
            if node.module.split(".")[0] != "ray_tpu":
                continue
            for alias in node.names:
                whole = f"{node.module}.{alias.name}"
                target = (whole, "") if _is_module(whole) else (node.module, alias.name)
                reads.add(target)
                bound.setdefault(alias.asname or alias.name, set()).add(target)
    for node in ast.walk(tree):
        names = _chain(node) if isinstance(node, ast.Attribute) else []
        for module, name in bound.get(names[0], ()) if names else ():
            # ``ray_tpu.models.llama.x``: the longest prefix that is a module is the module
            rest = names[1:]
            while rest and not name and _is_module(f"{module}.{rest[0]}"):
                module, rest = f"{module}.{rest[0]}", rest[1:]
            reads.add((module, ".".join(filter(None, [name, *rest]))))
    return reads


def _cases() -> List[Tuple[str, str]]:
    reads: Set[Tuple[str, str]] = set()
    for directory in READERS:
        for path in glob.glob(os.path.join(ROOT, directory, "**", "*.py"), recursive=True):
            reads |= _reads_of(path)
    return sorted(reads)


def _resolves(obj, dotted: str) -> Tuple[bool, str]:
    """Whether ``getattr`` walks ``dotted`` from ``obj``. The walk ends, and
    passes, where it reaches a value whose attributes are its own business and
    not the package's (a field's default, what a function returns): what is
    held is the name ``ray_tpu`` owns, a module's, a class's or a record's."""
    for i, part in enumerate(dotted.split(".") if dotted else ()):
        if not hasattr(obj, part):
            return False, f"{type(obj).__name__} {getattr(obj, '__name__', obj)!r} has no {part!r}"
        obj = getattr(obj, part)
        owned = isinstance(obj, type) or callable(obj) or type(obj).__module__.split(".")[0] == "ray_tpu"
        if not (owned or type(obj).__name__ == "module"):
            break
    return True, ""


CASES = _cases()


def test_the_benchmark_reads_something():
    """The parse finds the seam at all (an empty list would pass every case)."""
    modules = {module for module, _ in CASES}
    assert len(CASES) > 50 and "ray_tpu.models.llama" in modules and "ray_tpu.models.latent" in modules


@pytest.mark.parametrize("module,name", CASES, ids=[f"{m}:{n}" if n else m for m, n in CASES])
def test_a_name_the_benchmark_reads_resolves(module, name):
    found, why = _resolves(importlib.import_module(module), name)
    assert found, (
        f"{module}{':' + name if name else ''} is read under perfbench/ or tests/perfbench/ "
        f"(closed to a PR that is not a benchmark's) and does not resolve: {why}"
    )
