"""DLAF005 — every compiled program has a name.

``jax.jit`` names a program's module after the function it wraps: a
``functools.partial`` becomes ``jit__unknown`` and a ``lambda`` becomes
``jit__lambda_``.  Profiler traces, compile logs and the program-load
counter (``obs.program_loads``) then cannot tell the programs apart — the
SBR kernel and ``transpose`` once shared one label.  Library code names a
program with ``plan.jit(op, fun, ...)`` (``jit_<op>``) or jits a ``def``
whose name says what it does.

Flagged: ``jax.jit(partial(...))`` / ``jax.jit(functools.partial(...))`` and
``jax.jit(lambda ...)`` (also through ``from jax import jit``) in files under
``dlaf_tpu/``.  ``@partial(jax.jit, ...)`` on a ``def`` keeps the def's name
and is not flagged.
"""
from __future__ import annotations

import ast

from dlaf_tpu.analysis.engine import Finding
from dlaf_tpu.analysis.project import dotted_name

RULE = "DLAF005"
SUMMARY = "jax.jit of a partial or a lambda: an unnamed program"

JIT_NAMES = frozenset({"jax.jit", "jit"})
PARTIAL_NAMES = frozenset({"partial", "functools.partial"})


def in_scope(file) -> bool:
    return file.rel.replace("\\", "/").split("/")[0] == "dlaf_tpu"


def _unnamed(arg) -> str | None:
    """What makes the jitted callable unnamed, or None."""
    if isinstance(arg, ast.Lambda):
        return "a lambda"
    if isinstance(arg, ast.Call) and dotted_name(arg.func) in PARTIAL_NAMES:
        return "a partial"
    return None


def _symbol(tree, line: int) -> str:
    """The innermost def around ``line`` (for a stable baseline identity)."""
    best = ""
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) and \
                node.lineno <= line <= (node.end_lineno or node.lineno):
            best = node.name
    return best


def check(project):
    findings = []
    for file in project.files:
        if not in_scope(file):
            continue
        for node in ast.walk(file.tree):
            if not (isinstance(node, ast.Call) and node.args
                    and dotted_name(node.func) in JIT_NAMES):
                continue
            what = _unnamed(node.args[0])
            if what is None:
                continue
            findings.append(Finding(
                rule=RULE, path=file.rel, line=node.lineno, col=node.col_offset,
                symbol=_symbol(file.tree, node.lineno),
                message=f"jax.jit of {what} compiles an unnamed program — use "
                        f"plan.jit(op, fun, ...) to name it jit_<op>",
            ))
    return findings
