"""R3 — host side-effects inside jit/shard_map/pallas bodies.

A jitted function body runs ONCE, at trace time. ``print`` prints a
tracer once and never again; ``time.time()`` stamps compilation, not
execution; mutating a global records the trace-time value forever; a
``np.*`` op on a traced value either crashes (TracerArrayConversion)
or silently constant-folds host data into the program. All four read
as working code in a quick local test (the first call does execute
them) and rot into wrong numbers in production.

Detected jit contexts (syntactic):

- ``@jax.jit`` / ``@jit`` / ``@partial(jax.jit, ...)`` decorators;
- local defs passed to ``jax.jit(f)``, ``shard_map(f, ...)``, or as the kernel of ``pl.pallas_call(f, ..)``.

Inside those bodies the rule flags ``print(...)``, ``time.*()`` calls,
``global``-declared assignment, and ``np.* (traced-param)`` calls —
the numpy check requires a direct function parameter as an argument
to keep static-shape numpy math (``np.prod(shape)``) legal.
``jax.debug.*`` and the ``*_callback`` APIs are the sanctioned
escape hatches and are never flagged.
"""

from __future__ import annotations

import ast
from typing import Iterable, List, Optional, Set, Tuple

from tpunet.analysis.core import (Finding, Project, Rule, SourceFile,
                                  call_name, dotted)

_TIME_CALLS = {"time", "perf_counter", "monotonic", "process_time",
               "sleep", "time_ns", "perf_counter_ns"}
_ALLOWED_PREFIXES = ("jax.debug.",)
_ALLOWED_SUBSTR = ("callback",)
_JIT_WRAP_SUFFIXES = ("jit", "pjit")
_FN_WRAPPERS = ("shard_map", "pallas_call")


def _is_jit_decorator(dec: ast.AST) -> bool:
    if isinstance(dec, (ast.Name, ast.Attribute)):
        return dotted(dec).rsplit(".", 1)[-1] in _JIT_WRAP_SUFFIXES
    if isinstance(dec, ast.Call):
        name = call_name(dec)
        if name.rsplit(".", 1)[-1] in _JIT_WRAP_SUFFIXES:
            return True
        if name.rsplit(".", 1)[-1] == "partial" and dec.args:
            inner = dec.args[0]
            if isinstance(inner, (ast.Name, ast.Attribute)):
                return (dotted(inner).rsplit(".", 1)[-1]
                        in _JIT_WRAP_SUFFIXES)
    return False


def _wrapped_local_defs(tree: ast.AST) -> Set[str]:
    """Names of local functions passed to jit/shard_map/pallas_call
    anywhere in the module."""
    wrapped: Set[str] = set()
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call) \
                or not isinstance(node.func, (ast.Name, ast.Attribute)):
            continue
        last = call_name(node).rsplit(".", 1)[-1]
        if last in _JIT_WRAP_SUFFIXES or last in _FN_WRAPPERS:
            if node.args and isinstance(node.args[0], ast.Name):
                wrapped.add(node.args[0].id)
    return wrapped


class JitEffectsRule(Rule):
    id = "R3"
    name = "jit-host-side-effects"
    doc = ("print/time.*/global mutation/numpy-on-traced-values inside "
           "jit, shard_map, or pallas kernel bodies (trace-time-only "
           "execution)")

    def run(self, project: Project) -> List[Finding]:
        findings: List[Finding] = []
        for src in project.files():
            if src.tree is None:
                continue
            wrapped = _wrapped_local_defs(src.tree)
            for node in ast.walk(src.tree):
                if not isinstance(node, (ast.FunctionDef,
                                         ast.AsyncFunctionDef)):
                    continue
                jitted = (node.name in wrapped
                          or any(_is_jit_decorator(d)
                                 for d in node.decorator_list))
                if jitted:
                    findings.extend(self._check_body(src, node))
        return findings

    # ------------------------------------------------------------------

    def _check_body(self, src: SourceFile,
                    fn: ast.FunctionDef) -> List[Finding]:
        findings: List[Finding] = []
        params: Set[str] = {a.arg for a in (
            fn.args.posonlyargs + fn.args.args + fn.args.kwonlyargs)}
        global_names: Set[str] = set()
        for node in ast.walk(fn):
            if isinstance(node, ast.Global):
                global_names.update(node.names)

        def emit(line: int, kind: str, detail: str, message: str,
                 hint: str) -> None:
            findings.append(Finding(
                rule="R3", path=src.rel, line=line, message=message,
                hint=hint, key=f"{fn.name}:{kind}:{detail}"))

        for node in ast.walk(fn):
            if isinstance(node, (ast.Assign, ast.AugAssign)):
                targets: Iterable[ast.AST] = (
                    node.targets if isinstance(node, ast.Assign)
                    else [node.target])
                for t in targets:
                    if isinstance(t, ast.Name) and t.id in global_names:
                        emit(node.lineno, "global", t.id,
                             f"jitted '{fn.name}' mutates global "
                             f"'{t.id}' — the mutation happens once at "
                             "trace time, never per step",
                             "return the value (or use jax.debug."
                             "callback for host-side accounting)")
            if not isinstance(node, ast.Call):
                continue
            name = call_name(node)
            if not name:
                continue
            if name.startswith(_ALLOWED_PREFIXES) \
                    or any(s in name for s in _ALLOWED_SUBSTR):
                continue
            last = name.rsplit(".", 1)[-1]
            root = name.split(".", 1)[0]
            if name == "print":
                emit(node.lineno, "print", str(node.lineno),
                     f"print() inside jitted '{fn.name}' executes at "
                     "trace time only (and prints a tracer)",
                     "use jax.debug.print for per-execution output")
            elif root == "time" and last in _TIME_CALLS:
                emit(node.lineno, "time", last,
                     f"time.{last}() inside jitted '{fn.name}' stamps "
                     "trace time, not step time",
                     "time around the jitted call on the host (the obs "
                     "Timer), not inside it")
            elif root in ("np", "numpy"):
                traced = [a for a in node.args
                          if isinstance(a, ast.Name) and a.id in params]
                if traced:
                    emit(node.lineno, "numpy", f"{last}:{traced[0].id}",
                         f"np.{last}({traced[0].id}) inside jitted "
                         f"'{fn.name}' applies a host numpy op to a "
                         "traced value — TracerArrayConversionError at "
                         "best, silent trace-time constant-folding at "
                         "worst",
                         f"use jnp.{last} (or move the numpy math "
                         "outside the jitted body)")
        return findings
