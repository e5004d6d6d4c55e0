#!/usr/bin/env python
"""Import/name hygiene linter (`make lint`).

Runs ``ruff check`` when the binary exists; otherwise falls back to a
dependency-free AST pass implementing the same ruleset declared in
``ruff.toml``:

- F401  unused import (module-level; ``__all__`` and ``# noqa`` honored)
- F811  redefinition of an imported/defined name in the same scope
- E722  bare ``except:``

One repo-specific rule runs on **every** invocation, with or without
ruff (ruff is not configured for it here):

- T201  ``print(...)`` call inside ``src/repro/`` — library code must
  not write to stdout (stray prints corrupt machine-read benchmark
  and report output); the launch CLIs route terminal output through
  ``repro.launch.console.emit``.

A ``# noqa`` (optionally ``# noqa: CODE``) comment on the offending
line suppresses a finding, matching ruff's semantics closely enough
that the two paths agree on this tree.

Usage: PYTHONPATH=src python tools/lint.py [paths...]  (default: src
tools tests)
"""
from __future__ import annotations

import ast
import pathlib
import shutil
import subprocess
import sys

DEFAULT_PATHS = ("src", "tools", "tests")

# Library tree where the T201 no-print rule applies (the launch CLIs
# inside it use repro.launch.console.emit instead).
LIBRARY_TREE = pathlib.Path("src") / "repro"


def _noqa_lines(source: str) -> dict:
    """line number -> set of suppressed codes (empty set = all)."""
    out = {}
    for i, line in enumerate(source.splitlines(), 1):
        if "# noqa" not in line:
            continue
        _, _, rest = line.partition("# noqa")
        rest = rest.strip()
        if rest.startswith(":"):
            out[i] = {c.strip().upper()
                      for c in rest[1:].replace(",", " ").split()}
        else:
            out[i] = set()
    return out


def _used_names(tree: ast.AST) -> set:
    """Every identifier the module body reads, including attribute roots
    and names referenced inside docstring-free string annotations."""
    used = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Name):
            used.add(node.id)
        elif isinstance(node, ast.Attribute):
            n = node
            while isinstance(n, ast.Attribute):
                n = n.value
            if isinstance(n, ast.Name):
                used.add(n.id)
    return used


def _exported(tree: ast.AST) -> set:
    for node in tree.body if hasattr(tree, "body") else ():
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, ast.Name) and t.id == "__all__":
                    try:
                        return set(ast.literal_eval(node.value))
                    except ValueError:
                        return set()
    return set()


def _import_bindings(node):
    """(local name, lineno) pairs bound by an import statement.
    ``from __future__ import ...`` binds nothing lintable."""
    if isinstance(node, ast.Import):
        for a in node.names:
            yield (a.asname or a.name.split(".")[0]), node.lineno
    elif isinstance(node, ast.ImportFrom):
        if node.module == "__future__":
            return
        for a in node.names:
            if a.name == "*":
                continue
            yield (a.asname or a.name), node.lineno


def _in_library(path: pathlib.Path) -> bool:
    return "src/repro" in path.resolve().as_posix()


def _check_prints(path: pathlib.Path) -> list:
    """T201: ``print(...)`` calls in library code (AST-based, so
    docstrings and comments mentioning print are fine)."""
    source = path.read_text()
    try:
        tree = ast.parse(source, filename=str(path))
    except SyntaxError as e:
        return [(path, e.lineno or 0, "E999", f"syntax error: {e.msg}")]
    noqa = _noqa_lines(source)
    findings = []
    for node in ast.walk(tree):
        if (isinstance(node, ast.Call) and isinstance(node.func, ast.Name)
                and node.func.id == "print"):
            codes = noqa.get(node.lineno)
            if codes is not None and (not codes or "T201" in codes):
                continue
            findings.append((path, node.lineno, "T201",
                             "`print` call in library code — use "
                             "repro.launch.console.emit (CLIs) or return "
                             "data to the caller"))
    return findings


def _check_module(path: pathlib.Path) -> list:
    source = path.read_text()
    try:
        tree = ast.parse(source, filename=str(path))
    except SyntaxError as e:
        return [(path, e.lineno or 0, "E999", f"syntax error: {e.msg}")]
    noqa = _noqa_lines(source)
    findings = []

    def keep(lineno, code, msg):
        codes = noqa.get(lineno)
        if codes is not None and (not codes or code in codes):
            return
        findings.append((path, lineno, code, msg))

    # E722 everywhere, F811 per scope, F401 at module level only (a
    # function-local import is a lazy-import idiom here, and its "use"
    # may be the import itself for side effects).
    used = _used_names(tree)
    exported = _exported(tree)
    is_pkg_init = path.name == "__init__.py"
    for node in ast.walk(tree):
        if isinstance(node, ast.ExceptHandler) and node.type is None:
            keep(node.lineno, "E722",
                 "bare `except:` swallows SystemExit/KeyboardInterrupt "
                 "— catch Exception (or narrower)")
    for node in tree.body:
        for name, lineno in _import_bindings(node):
            if name in used or name in exported or name == "_":
                continue
            if is_pkg_init:
                continue   # re-export surface; __init__ uses noqa anyway
            keep(lineno, "F401", f"`{name}` imported but unused")

    # F811: a def/class/import rebinding a name already bound in the
    # same (module or class/function body) scope.
    def scope_defs(body):
        seen = {}
        for node in body:
            names = []
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)):
                if not any(isinstance(d, ast.Name)
                           and d.id.endswith("setter")
                           or isinstance(d, ast.Attribute)
                           for d in node.decorator_list):
                    names = [(node.name, node.lineno)]
            elif isinstance(node, (ast.Import, ast.ImportFrom)):
                names = list(_import_bindings(node))
            for name, lineno in names:
                if name in seen:
                    keep(lineno, "F811",
                         f"redefinition of `{name}` from line "
                         f"{seen[name]}")
                seen[name] = lineno
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)):
                scope_defs(node.body)
            elif isinstance(node, (ast.If, ast.Try)):
                pass   # conditional/fallback rebinds are intentional
    scope_defs(tree.body)
    return findings


def main(argv=None) -> int:
    paths = (argv or sys.argv[1:]) or list(DEFAULT_PATHS)
    files = []
    for p in paths:
        pp = pathlib.Path(p)
        files += sorted(pp.rglob("*.py")) if pp.is_dir() else [pp]
    library_files = [f for f in files if _in_library(f)]

    # T201 runs on every invocation; ruff (when present) is not
    # configured for it, so the scan cannot be delegated.
    findings = []
    for f in library_files:
        findings += _check_prints(f)

    ruff = shutil.which("ruff")
    if ruff:
        rc = subprocess.call([ruff, "check", *paths])
        for path, lineno, code, msg in findings:
            print(f"{path}:{lineno}: {code} {msg}")
        if findings:
            print(f"lint: {len(findings)} T201 finding"
                  f"{'s' if len(findings) != 1 else ''} in "
                  f"{len(library_files)} library files")
        return rc or (1 if findings else 0)

    for f in files:
        findings += _check_module(f)
    findings.sort(key=lambda x: (str(x[0]), x[1]))
    for path, lineno, code, msg in findings:
        print(f"{path}:{lineno}: {code} {msg}")
    n = len(findings)
    print(f"lint: {n} finding{'s' if n != 1 else ''} in "
          f"{len(files)} files" + (" (AST fallback; install ruff for "
                                   "the full ruleset)" if n else ""))
    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main())
