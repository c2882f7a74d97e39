#!/usr/bin/env python
"""Dependency-free lint gate (the reference CI runs fmt + clippy,
``.github/workflows/check.yml``; this environment has no third-party
linters, so the checks are implemented on the ast module):

- unused imports (skipped in ``__init__.py`` re-export modules and on
  lines marked ``# noqa``),
- trailing whitespace / tab indentation,
- bare ``except:`` clauses.

Usage: python scripts/lint.py [paths...]  (default: tnc_tpu tests scripts)
"""

from __future__ import annotations

import ast
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _used_names(tree: ast.AST) -> set[str]:
    used: set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Name):
            used.add(node.id)
        elif isinstance(node, ast.Attribute):
            # record the root of dotted uses: np.foo -> np
            inner = node
            while isinstance(inner, ast.Attribute):
                inner = inner.value
            if isinstance(inner, ast.Name):
                used.add(inner.id)
    # names referenced inside string annotations / docstring doctests are
    # not tracked; __all__ entries count as used
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, ast.Name) and t.id == "__all__":
                    for elt in getattr(node.value, "elts", []):
                        if isinstance(elt, ast.Constant):
                            used.add(str(elt.value))
    return used


def lint_file(path: str) -> list[str]:
    problems: list[str] = []
    with open(path, encoding="utf-8") as f:
        source = f.read()
    lines = source.splitlines()
    for i, line in enumerate(lines, 1):
        if line.rstrip("\n") != line.rstrip():
            problems.append(f"{path}:{i}: trailing whitespace")
        if line.startswith("\t"):
            problems.append(f"{path}:{i}: tab indentation")
    try:
        tree = ast.parse(source)
    except SyntaxError as e:
        return [f"{path}:{e.lineno}: syntax error: {e.msg}"]

    for node in ast.walk(tree):
        if isinstance(node, ast.ExceptHandler) and node.type is None:
            problems.append(f"{path}:{node.lineno}: bare except")

    if os.path.basename(path) != "__init__.py":
        used = _used_names(tree)
        doctext = "\n".join(
            n.value.value
            for n in ast.walk(tree)
            if isinstance(n, ast.Expr)
            and isinstance(n.value, ast.Constant)
            and isinstance(n.value.value, str)
        )
        for node in ast.walk(tree):
            names: list[tuple[str, int]] = []
            if isinstance(node, ast.ImportFrom) and node.module == "__future__":
                continue
            if isinstance(node, ast.Import):
                names = [
                    ((a.asname or a.name).split(".")[0], node.lineno)
                    for a in node.names
                ]
            elif isinstance(node, ast.ImportFrom):
                names = [
                    (a.asname or a.name, node.lineno) for a in node.names
                ]
            for name, lineno in names:
                if name == "*":
                    continue
                line = lines[lineno - 1] if lineno <= len(lines) else ""
                if "noqa" in line:
                    continue
                if name not in used and name not in doctext:
                    problems.append(f"{path}:{lineno}: unused import '{name}'")
    return problems


def main(argv: list[str]) -> int:
    roots = argv or [
        "tnc_tpu", "tests", "scripts", "bench.py", "chip_smoke.py",
        "__graft_entry__.py",
    ]
    files: list[str] = []
    for root in roots:
        full = os.path.join(REPO, root)
        if os.path.isfile(full):
            files.append(full)
        else:
            for dirpath, _, fnames in os.walk(full):
                files.extend(
                    os.path.join(dirpath, f) for f in fnames if f.endswith(".py")
                )
    problems: list[str] = []
    for path in sorted(files):
        problems.extend(lint_file(path))
    for p in problems:
        print(p)
    print(f"lint: {len(files)} files, {len(problems)} problems")
    return 1 if problems else 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
