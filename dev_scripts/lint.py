#!/usr/bin/env python3
"""Dependency-free lint gate — the reference wires scalastyle + Apache RAT
into its `check` task (/root/reference/build.gradle:48+,
scalastyle-config.xml); this is the same discipline for a Python/JAX tree
using only the stdlib (no ruff/flake8 in the image).

Checks, per file:
  syntax        file must parse (ast.parse)
  tabs          no tab indentation
  trailing-ws   no trailing whitespace
  line-length   <= 99 columns
  bare-except   no `except:` without an exception class
  mutable-default  no list/dict/set literals as parameter defaults
  star-import   no `from x import *`
  unused-import imported name never referenced (skipped in __init__.py,
                which re-exports; names starting with _ are exempt)

Exit 0 = clean. Run via tests.sh or directly:
    python dev_scripts/lint.py [paths...]
"""

from __future__ import annotations

import ast
import sys
from pathlib import Path

MAX_LINE = 99
DEFAULT_PATHS = ["photon_ml_tpu", "tests", "dev_scripts",
                 "__graft_entry__.py"]


def _imported_names(tree: ast.AST):
    """(local_name, node) for every import binding."""
    out = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                out.append(((a.asname or a.name).split(".")[0], node))
        elif isinstance(node, ast.ImportFrom):
            if node.module == "__future__":
                continue
            for a in node.names:
                if a.name != "*":
                    out.append((a.asname or a.name, node))
    return out


def _used_names(tree: ast.AST):
    used = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Name):
            used.add(node.id)
        elif isinstance(node, ast.Constant) and isinstance(node.value, str):
            # Identifier-shaped strings count as uses: string type
            # annotations (PEP 563 forward refs, incl. dotted forms like
            # 'np.ndarray') and __all__ entries.
            for tok in (node.value.replace("[", " ").replace("]", " ")
                        .replace(".", " ").replace(",", " ").split()):
                if tok.isidentifier():
                    used.add(tok)
    return used


def lint_file(path: Path, src: str = None) -> list:
    """``src`` lets a caller that already read the file (dev_scripts/
    jaxlint.py's shared walk) skip the second read."""
    problems = []
    if src is None:
        src = path.read_text()
    try:
        tree = ast.parse(src, filename=str(path))
    except SyntaxError as e:
        return [(path, e.lineno or 0, f"syntax error: {e.msg}")]

    for i, line in enumerate(src.splitlines(), 1):
        if line != line.rstrip():
            problems.append((path, i, "trailing whitespace"))
        if "\t" in line:
            problems.append((path, i, "tab character"))
        if len(line) > MAX_LINE:
            problems.append((path, i, f"line length {len(line)} > {MAX_LINE}"))

    for node in ast.walk(tree):
        if isinstance(node, ast.ExceptHandler) and node.type is None:
            problems.append((path, node.lineno, "bare except"))
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for d in list(node.args.defaults) + [
                    d for d in node.args.kw_defaults if d is not None]:
                if isinstance(d, (ast.List, ast.Dict, ast.Set)):
                    problems.append(
                        (path, d.lineno, "mutable default argument"))
        elif isinstance(node, ast.ImportFrom):
            if any(a.name == "*" for a in node.names):
                problems.append((path, node.lineno, "star import"))

    if path.name != "__init__.py":
        used = _used_names(tree)
        for name, node in _imported_names(tree):
            if name.startswith("_") or name in used:
                continue
            problems.append((path, node.lineno, f"unused import {name!r}"))
    return problems


def main(argv) -> int:
    roots = [Path(p) for p in (argv or DEFAULT_PATHS)]
    files = []
    for r in roots:
        files += sorted(r.rglob("*.py")) if r.is_dir() else [r]
    problems = []
    for f in files:
        problems += lint_file(f)
    for path, line, msg in problems:
        print(f"{path}:{line}: {msg}")
    print(f"lint: {len(files)} files, {len(problems)} problem(s)")
    return 1 if problems else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
