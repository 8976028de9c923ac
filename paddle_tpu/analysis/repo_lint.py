"""Lightweight AST lint with project rules for the project sources
(``paddle_tpu/``, ``tools/``, ``examples/``, ``__graft_entry__.py``).

Complements the jaxpr linter: some invariants live in *source*, not in
traced graphs — host clocks inside kernel modules, constant PRNG seeds in
library code, flag access that bypasses the registry. Pure ``ast``, no
imports of the scanned modules, so it is safe (and fast) as a tier-1 test.

Rules:
  R001  ``time.time()`` / ``time.perf_counter()`` in a Pallas kernel
        module — host clocks don't measure device work and break under
        tracing                                               [error]
  R002  constant ``PRNGKey(<literal>)`` outside tests — replays the same
        stream every call                                     [warning]
  R003  ``os.environ[...FLAGS_...]`` access outside ``core/flags.py`` —
        flags must go through the registry so set_flags works [error]

Suppress a finding on a specific line with ``# repo-lint: allow R002``
(the project's noqa). The CLI (`tools/lint_graph.py --all`) and
``tests/test_repo_lint.py`` gate error severity.
"""

from __future__ import annotations

import ast
import os
from typing import Iterable, List, Optional

from .jaxpr_lint import Diagnostic, ERROR, WARNING

__all__ = ["lint_file", "lint_tree", "ALLOW_MARK", "DEFAULT_SUBTREES"]

ALLOW_MARK = "repo-lint: allow"

_TIME_FNS = {"time", "perf_counter", "monotonic", "process_time"}


def _allowed(src_lines: List[str], lineno: int, rule: str) -> bool:
    if 0 < lineno <= len(src_lines):
        line = src_lines[lineno - 1]
        if ALLOW_MARK in line and rule in line.split(ALLOW_MARK, 1)[1]:
            return True
    return False


def _dotted(node: ast.AST) -> str:
    """'jax.random.PRNGKey' for an Attribute/Name chain, '' otherwise."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def _is_kernel_module(relpath: str) -> bool:
    return "_pallas" in relpath.replace(os.sep, "/")


def _is_test_path(relpath: str) -> bool:
    p = relpath.replace(os.sep, "/")
    return p.startswith("tests/") or "/tests/" in p or \
        os.path.basename(p).startswith("test_")


def lint_file(path: str, relpath: Optional[str] = None) -> List[Diagnostic]:
    relpath = relpath or path
    try:
        with open(path, "r", encoding="utf-8") as f:
            src = f.read()
        tree = ast.parse(src, filename=path)
    except (OSError, SyntaxError) as e:
        return [Diagnostic(rule="R000", name="unparsable", severity=ERROR,
                           message=f"cannot parse: {e}", source=relpath)]
    lines = src.splitlines()
    diags: List[Diagnostic] = []

    def add(rule, name, severity, node, message, hint=""):
        if _allowed(lines, node.lineno, rule):
            return
        diags.append(Diagnostic(
            rule=rule, name=name, severity=severity, message=message,
            source=f"{relpath}:{node.lineno}", hint=hint))

    in_kernel = _is_kernel_module(relpath)
    in_tests = _is_test_path(relpath)
    is_flags_module = relpath.replace(os.sep, "/").endswith("core/flags.py")

    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            # R003 also matches subscripts: os.environ["FLAGS_x"]
            if isinstance(node, ast.Subscript) and not is_flags_module:
                base = _dotted(node.value)
                key = node.slice
                if base in ("os.environ", "environ") and \
                        isinstance(key, ast.Constant) and \
                        isinstance(key.value, str) and \
                        key.value.startswith("FLAGS_"):
                    add("R003", "env-flag-bypass", ERROR, node,
                        f"direct os.environ[{key.value!r}] access bypasses "
                        "the flag registry (runtime set_flags changes are "
                        "invisible here)",
                        hint="use core.flags.flag(name) / get_flags")
            continue
        dotted = _dotted(node.func)
        # R001: host clocks in kernel modules
        if in_kernel and dotted.startswith("time.") and \
                dotted.split(".", 1)[1] in _TIME_FNS:
            add("R001", "host-clock-in-kernel", ERROR, node,
                f"{dotted}() in a Pallas kernel module measures host "
                "wall-clock, not device time, and is a trace-time "
                "constant under jit",
                hint="use the profiler-trace device timing "
                     "(profiler.statistic.device_total_ms)")
        # R002: constant PRNG seeds in library code
        if not in_tests and dotted.endswith("PRNGKey") and node.args and \
                isinstance(node.args[0], ast.Constant):
            add("R002", "constant-prng-seed", WARNING, node,
                f"{dotted}({node.args[0].value!r}) seeds an identical "
                "stream at every call site",
                hint="derive keys from core.random.next_key() or fold in "
                     "program state; add '# repo-lint: allow R002' if the "
                     "constant seed is the point")
        # R003: env-var flag reads via .get
        if not is_flags_module and dotted in ("os.environ.get",
                                              "environ.get") and \
                node.args and isinstance(node.args[0], ast.Constant) and \
                isinstance(node.args[0].value, str) and \
                node.args[0].value.startswith("FLAGS_"):
            add("R003", "env-flag-bypass", ERROR, node,
                f"os.environ.get({node.args[0].value!r}) bypasses the "
                "flag registry (runtime set_flags changes are invisible "
                "here)",
                hint="use core.flags.flag(name) / get_flags")
    return diags


# Default coverage: the package tree, the CLI tools (they carry real
# logic — hbm accounting, lint drivers, trace viewers), the example
# scripts (the first code users copy — a constant seed or a flag bypass
# there propagates), and the driver entry module. A bare filename entry
# lints that single file.
DEFAULT_SUBTREES = ("paddle_tpu", "tools", "examples", "__graft_entry__.py")


def lint_tree(root: str, subdir: Optional[str] = None) -> List[Diagnostic]:
    """Lint the project's Python sources under ``root`` (skips native/
    blobs). With ``subdir`` given, only that subtree; by default the
    :data:`DEFAULT_SUBTREES` — ``paddle_tpu/``, ``tools/``,
    ``examples/`` and ``__graft_entry__.py``."""
    subtrees = (subdir,) if subdir is not None else DEFAULT_SUBTREES
    out: List[Diagnostic] = []
    for sub in subtrees:
        base = os.path.join(root, sub)
        if os.path.isfile(base):
            out.extend(lint_file(base, os.path.relpath(base, root)))
            continue
        for dirpath, dirnames, filenames in os.walk(base):
            dirnames[:] = [d for d in dirnames
                           if d not in ("__pycache__", ".git")]
            for fn in sorted(filenames):
                if not fn.endswith(".py"):
                    continue
                full = os.path.join(dirpath, fn)
                out.extend(lint_file(full, os.path.relpath(full, root)))
    return out
