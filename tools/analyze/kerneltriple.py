"""Checker (d) — kernel-triple completeness.

Every Pallas kernel in this repo ships as a triple (ROADMAP discipline,
established in PR 3):

  * ``kernel.py`` — the Pallas implementation;
  * ``ref.py``    — the pure-jnp oracle the kernel is verified against;
  * ``ops.py``    — the dispatch layer, which MUST carry an interpret-mode
    fallback (an ``interpret`` keyword threaded into ``pallas_call``) so
    CPU CI and non-TPU users run the same code path, correctness-only.

A kernel directory missing its ref or its interpret path is a kernel that
cannot be conformance-tested on CI — exactly how silent drift ships.
Suppress (e.g. for a kernel whose ref intentionally lives elsewhere) with
``# kernel: ok(<reason>)`` at the top of the offending dir's __init__.py.
"""

from __future__ import annotations

import ast
from pathlib import Path
from typing import List

from tools.analyze import common

CHECKER = "kerneltriple"

REQUIRED = ("kernel.py", "ref.py", "ops.py")


def _has_interpret_kwarg(path: Path) -> bool:
    """Does the file mention an `interpret` keyword (in a call or a
    function signature)?  The dispatch idiom is
    `interpret = interpret_default() if interpret is None else interpret`
    (`repro.kernels.interpret_default`: interpret on the CPU backend only)."""
    tree = ast.parse(path.read_text(), filename=str(path))
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            if any(kw.arg == "interpret" for kw in node.keywords):
                return True
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            args = (node.args.posonlyargs + node.args.args
                    + node.args.kwonlyargs)
            if any(a.arg == "interpret" for a in args):
                return True
    return False


def _dir_suppressed(kdir: Path, root: Path) -> bool:
    init = kdir / "__init__.py"
    if not init.exists():
        return False
    src = common.SourceFile(init, root)
    return any("kernel" in tags for tags in src.suppressions.values())


def check(root: Path, sub: str = "src/repro/kernels"
          ) -> List[common.Violation]:
    base = root / sub
    violations: List[common.Violation] = []
    if not base.exists():
        return violations
    for kdir in sorted(p for p in base.iterdir() if p.is_dir()
                       and p.name != "__pycache__"):
        rel = kdir.relative_to(root).as_posix()
        if not (kdir / "__init__.py").exists():
            continue                     # not a kernel package
        if _dir_suppressed(kdir, root):
            continue
        for req in REQUIRED:
            if not (kdir / req).exists():
                violations.append(common.Violation(
                    CHECKER, rel, 1, kdir.name, f"missing-{req}",
                    f"kernel dir {rel}/ lacks {req} — every kernel ships "
                    "kernel.py (Pallas) + ref.py (jnp oracle) + ops.py "
                    "(dispatch with interpret fallback)"))
        ops = kdir / "ops.py"
        if ops.exists() and not _has_interpret_kwarg(ops):
            violations.append(common.Violation(
                CHECKER, f"{rel}/ops.py", 1, kdir.name, "no-interpret-path",
                f"{rel}/ops.py has no `interpret` fallback keyword — the "
                "kernel cannot run (or be conformance-tested) off-TPU"))
    return violations
