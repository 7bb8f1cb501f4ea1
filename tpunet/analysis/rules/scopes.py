"""R2 — named-scope coverage for the kernels in ``tpunet/ops/``.

Byte/phase attribution (``tpunet/obs/hlo_bytes.py``) classifies HLO
instructions by the framework ``op_name`` — and a custom_vjp'd Pallas
kernel has nothing classifiable in its op_name unless the code wraps
it in a ``tpunet_*`` named scope: the kernel lowers to a custom call
(no convolution/dot opcode) and a custom_vjp backward carries no
``transpose(`` autodiff marker. PR 6 burned three review passes
rediscovering this per kernel; this rule makes it structural:

1. every ``pl.pallas_call`` in ``tpunet/ops/`` must sit under a
   ``tpunet_*`` named scope — lexically, or via a wrapper function
   whose every in-module call site is scoped (the depthwise layout);
2. every ``defvjp``-registered fwd/bwd body must be *scope-bearing*:
   contain a tpunet scope or (transitively, through in-module calls)
   reach one (the flash layout, where the scope lives inside the
   shared kernel-invocation helpers);
3. every ``tpunet_*`` scope string used in ``tpunet/ops/`` must be a
   ``<prefix>_fwd`` / ``<prefix>_bwd`` of ``hlo_bytes.KERNEL_SCOPES``
   — the actual marker table attribution matches on — so a renamed or
   invented scope fails the tree instead of silently bucketing into
   ``elementwise``.

The cross-check imports the live table, not a copy: adding a kernel
means adding its scope prefix to ``KERNEL_SCOPES`` (with its fwd/bwd
byte categories) in the same change, or R2 says so.
"""

from __future__ import annotations

import ast
import re
from typing import Dict, List, Optional, Set, Tuple

from tpunet.analysis.core import (Finding, Project, Rule, SourceFile,
                                  call_name, const_str, dotted)
from tpunet.obs.hlo_bytes import KERNEL_SCOPES

_OPS_PATH_RE = re.compile(r"(^|/)ops/[^/]+\.py$")

#: Assignments whose value wraps a function without renaming its body:
#: ``X = sharded(F, ...)`` (the mesh split of tpunet/ops/partition.py;
#: ``custom_partitioning(F, ...)`` is the same shape and stays
#: recognized) / ``X = functools.partial(F, ..)``
_ALIAS_WRAPPERS = ("sharded", "custom_partitioning", "partial")


def _valid_scope_names() -> Set[str]:
    return {f"{p}_{d}" for p in KERNEL_SCOPES for d in ("fwd", "bwd")}


class _FileScopes(ast.NodeVisitor):
    """Per-file collection pass: function defs, named-scope contexts,
    call sites, pallas_call sites, defvjp registrations, aliases."""

    def __init__(self) -> None:
        self.funcs: Dict[str, ast.AST] = {}
        self.func_stack: List[str] = []
        self.scope_stack: List[str] = []
        # fn -> scope names lexically opened inside its body
        self.scopes_in: Dict[str, Set[str]] = {}
        # callee -> [(caller or '' for module level, scoped bool)]
        self.call_sites: Dict[str, List[Tuple[str, bool]]] = {}
        # caller -> set of in-module callees
        self.calls_out: Dict[str, Set[str]] = {}
        # (line, enclosing fn, scoped bool) per pallas_call
        self.pallas: List[Tuple[int, str, bool]] = []
        # (primal name, fwd name, bwd name, line)
        self.vjp: List[Tuple[str, str, str, int]] = []
        self.aliases: Dict[str, str] = {}
        self.scope_strings: List[Tuple[str, int]] = []

    # -- helpers -------------------------------------------------------

    def _cur_fn(self) -> str:
        return self.func_stack[-1] if self.func_stack else ""

    def _record_call(self, callee: str, scoped: bool) -> None:
        self.call_sites.setdefault(callee, []).append(
            (self._cur_fn(), scoped))
        if self._cur_fn():
            self.calls_out.setdefault(self._cur_fn(), set()).add(callee)

    # -- visitors ------------------------------------------------------

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        self.funcs[node.name] = node
        self.func_stack.append(node.name)
        outer_scopes = self.scope_stack
        self.scope_stack = []   # scopes do not cross function bodies
        self.generic_visit(node)
        self.scope_stack = outer_scopes
        self.func_stack.pop()

    visit_AsyncFunctionDef = visit_FunctionDef  # type: ignore[assignment]

    def visit_With(self, node: ast.With) -> None:
        opened: List[str] = []
        for item in node.items:
            if isinstance(item.context_expr, ast.Call):
                name = call_name(item.context_expr)
                if name.endswith("named_scope") and item.context_expr.args:
                    scope = const_str(item.context_expr.args[0])
                    if scope is not None:
                        opened.append(scope)
                        self.scope_strings.append(
                            (scope, item.context_expr.lineno))
                        if self._cur_fn():
                            self.scopes_in.setdefault(
                                self._cur_fn(), set()).add(scope)
        self.scope_stack.extend(opened)
        self.generic_visit(node)
        for _ in opened:
            self.scope_stack.pop()

    def visit_Assign(self, node: ast.Assign) -> None:
        if (isinstance(node.value, ast.Call)
                and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)):
            callee = call_name(node.value)
            last = callee.rsplit(".", 1)[-1]
            if last in _ALIAS_WRAPPERS and node.value.args:
                wrapped = node.value.args[0]
                if isinstance(wrapped, ast.Name):
                    self.aliases[node.targets[0].id] = wrapped.id
        self.generic_visit(node)

    def _under_tpunet_scope(self) -> bool:
        return any(s.startswith("tpunet_") for s in self.scope_stack)

    def visit_Call(self, node: ast.Call) -> None:
        # Only direct Name/Attribute callees: ``pl.pallas_call(f, ..)
        # (*args)`` is two Call nodes whose dotted names both fold to
        # pallas_call — count the inner one only.
        if not isinstance(node.func, (ast.Name, ast.Attribute)):
            self.generic_visit(node)
            return
        name = call_name(node)
        scoped = self._under_tpunet_scope()
        if name.endswith("pallas_call"):
            self.pallas.append((node.lineno, self._cur_fn(), scoped))
        elif name.endswith(".defvjp"):
            primal = name.rsplit(".", 1)[0]
            if len(node.args) >= 2 \
                    and isinstance(node.args[0], ast.Name) \
                    and isinstance(node.args[1], ast.Name):
                self.vjp.append((primal, node.args[0].id,
                                 node.args[1].id, node.lineno))
        elif isinstance(node.func, ast.Name):
            self._record_call(node.func.id, scoped)
        self.generic_visit(node)


class ScopeRule(Rule):
    id = "R2"
    name = "named-scope-coverage"
    doc = ("every Pallas kernel call and custom_vjp fwd/bwd body in "
           "tpunet/ops/ sits under a tpunet_* named scope known to "
           "hlo_bytes.KERNEL_SCOPES")

    def run(self, project: Project) -> List[Finding]:
        findings: List[Finding] = []
        for src in project.files():
            if src.tree is None or not _OPS_PATH_RE.search(src.rel):
                continue
            findings.extend(self._check_file(src))
        return findings

    # ------------------------------------------------------------------

    def _check_file(self, src: SourceFile) -> List[Finding]:
        collect = _FileScopes()
        assert src.tree is not None
        collect.visit(src.tree)
        findings: List[Finding] = []

        def resolve(name: str) -> str:
            seen: Set[str] = set()
            while name in collect.aliases and name not in seen:
                seen.add(name)
                name = collect.aliases[name]
            return name

        # Fold aliased call sites onto the wrapped function: a call to
        # ``_partitioned`` IS a call to ``_pallas_forward``.
        call_sites: Dict[str, List[Tuple[str, bool]]] = {}
        for callee, sites in collect.call_sites.items():
            call_sites.setdefault(resolve(callee), []).extend(sites)

        # scope-bearing: body opens a tpunet scope, or transitively
        # calls (in-module) a scope-bearing function.
        bearing: Set[str] = {
            fn for fn, scopes in collect.scopes_in.items()
            if any(s.startswith("tpunet_") for s in scopes)}
        changed = True
        while changed:
            changed = False
            for caller, callees in collect.calls_out.items():
                if caller in bearing:
                    continue
                if any(resolve(c) in bearing for c in callees):
                    bearing.add(caller)
                    changed = True

        # covered: every COUNTED in-module call site is scoped, or sits
        # inside a covered caller (and at least one counted site
        # exists — an uncalled function has no scoped context to
        # inherit). Call sites inside functions that are themselves
        # never called in-module (callbacks handed to a partitioner)
        # are NOT counted — they execute under the partitioned op's trace
        # context, which is the scoped call we already track through
        # the alias; custom_vjp fwd/bwd are invoked by jax machinery
        # and DO count as live callers.
        vjp_fns = {name for _, fwd, bwd, _ in collect.vjp
                   for name in (fwd, bwd)}

        def counted(sites: List[Tuple[str, bool]]
                    ) -> List[Tuple[str, bool]]:
            return [(caller, scoped) for caller, scoped in sites
                    if caller == "" or caller in vjp_fns
                    or call_sites.get(caller)]

        covered: Set[str] = set()
        changed = True
        while changed:
            changed = False
            for fn in collect.funcs:
                if fn in covered:
                    continue
                sites = counted(call_sites.get(fn, []))
                if sites and all(
                        scoped or (caller and caller in covered)
                        for caller, scoped in sites):
                    covered.add(fn)
                    changed = True

        for line, enclosing, scoped in collect.pallas:
            if scoped or (enclosing and enclosing in covered):
                continue
            findings.append(Finding(
                rule="R2", path=src.rel, line=line,
                message=(f"pl.pallas_call in '{enclosing or '<module>'}' "
                         "is not under a tpunet_* named scope (directly "
                         "or via its call sites) — its custom call will "
                         "attribute to 'elementwise' and its backward "
                         "to the fwd phase in hlo_bytes breakdowns"),
                hint=("wrap the kernel invocation in with jax.named_"
                      "scope(\"tpunet_<kernel>_fwd\") (or _bwd) and "
                      "register the prefix in hlo_bytes.KERNEL_SCOPES"),
                key=f"pallas:{enclosing or '<module>'}"))

        for primal, fwd, bwd, line in collect.vjp:
            for role, fn_name in (("fwd", fwd), ("bwd", bwd)):
                fn = collect.funcs.get(fn_name)
                if fn is None:
                    continue
                if fn_name in bearing or fn_name in covered:
                    continue
                findings.append(Finding(
                    rule="R2", path=src.rel,
                    line=getattr(fn, "lineno", line),
                    message=(f"custom_vjp {role} '{fn_name}' (defvjp of "
                             f"'{primal}') contains no tpunet_* named "
                             "scope — a custom_vjp body carries no "
                             "transpose( marker, so without the scope "
                             "its ops misattribute (PR-6 class)"),
                    hint=("wrap the body: with jax.named_scope("
                          f"\"tpunet_<kernel>_{role}\"): ... (prefix "
                          "must exist in hlo_bytes.KERNEL_SCOPES)"),
                    key=f"vjp:{primal}:{role}:{fn_name}"))

        valid = _valid_scope_names()
        for scope, line in collect.scope_strings:
            if scope.startswith("tpunet_") and scope not in valid:
                findings.append(Finding(
                    rule="R2", path=src.rel, line=line,
                    message=(f"named scope '{scope}' is not in hlo_bytes"
                             ".KERNEL_SCOPES (expected <prefix>_fwd/"
                             "_bwd with a registered prefix) — byte/"
                             "phase attribution will not classify it"),
                    hint=("add the prefix to KERNEL_SCOPES with its "
                          "fwd/bwd byte categories, or use an existing "
                          "marker"),
                    key=f"marker:{scope}"))
        return findings
