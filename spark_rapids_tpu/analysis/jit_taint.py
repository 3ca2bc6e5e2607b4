"""Interprocedural jit host-sync taint (tpu-lint 2.0).

Replaces PR 6's file-list heuristic (`host-sync-in-jit` only looked at
`io/parquet_device.py` and `ops/` and only at functions jitted *in the
same module*). The dataflow engine's call graph makes the real property
checkable: **any function reachable from a `jax.jit`-ed callable** that
performs a host synchronization — `np.asarray` / `np.array` /
`jax.device_get` / `.item()` / `.block_until_ready()` — is flagged,
wherever it lives. A host sync inside a traced region either fails
tracing outright or (through `callback`-style escapes) stalls the
dispatch stream on every call of the program.

Roots are found package-wide:

- decorator form: ``@jax.jit`` / ``@jit`` / ``@partial(jax.jit, ...)``;
- call form: ``jax.jit(fn)`` / ``jit(self._method, ...)`` — at module
  level, class level, or inside a function (the repo's dominant idiom:
  ``self._jit_single = jax.jit(self._single_pass)``, nested
  ``fn = jax.jit(build)``).

Propagation uses the project call graph (bounded depth); each finding
carries the root and the call chain so the reader can judge the path.
"""
from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from .dataflow import FuncInfo, Project, call_name

__all__ = ["analyze_jit_taint"]

_HOST_SYNC_CALLS = {"np.asarray", "np.array", "numpy.asarray",
                    "numpy.array", "jax.device_get", "device_get"}
_HOST_SYNC_METHODS = {"block_until_ready", "item"}
_MAX_DEPTH = 6


def _own_calls(f: FuncInfo) -> List[ast.Call]:
    """Calls lexically in f, excluding nested function bodies (those
    are their own FuncInfo and taint separately if reachable)."""
    out: List[ast.Call] = []
    stack = list(ast.iter_child_nodes(f.node))
    while stack:
        n = stack.pop()
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                          ast.Lambda)):
            continue
        if isinstance(n, ast.Call):
            out.append(n)
        stack.extend(ast.iter_child_nodes(n))
    return out


def _is_jit_name(name: str) -> bool:
    return name in ("jax.jit", "jit", "pjit", "jax.pjit")


def _resolve_jit_arg(arg, project: Project,
                     caller: Optional[FuncInfo],
                     rel: str) -> List[FuncInfo]:
    """The function a jit argument names, in `caller`'s scope (or at
    module level of `rel` when caller is None)."""
    if isinstance(arg, ast.Name):
        if caller is not None:
            nested = (f"{caller.rel}::{caller.qual}"
                      f".<locals>.{arg.id}")
            if nested in project.functions:
                return [project.functions[nested]]
        return [f for f in project.by_name.get(arg.id, [])
                if f.rel == rel and f.cls is None
                and "<locals>" not in f.qual] \
            or ([f for f in project.by_name.get(arg.id, [])
                 if f.rel == rel])
    if isinstance(arg, ast.Attribute) \
            and isinstance(arg.value, ast.Name):
        if arg.value.id in ("self", "cls") and caller is not None \
                and caller.cls:
            return [f for f in project.by_name.get(arg.attr, [])
                    if f.cls == caller.cls and f.rel == caller.rel]
        return [f for f in project.by_name.get(arg.attr, [])
                if f.rel == rel]
    return []


def _jit_roots(project: Project) -> List[Tuple[FuncInfo, int]]:
    roots: Dict[str, Tuple[FuncInfo, int]] = {}

    def add(infos, line):
        for info in infos:
            roots.setdefault(info.key, (info, line))

    # decorator form
    for f in project.functions.values():
        for d in f.node.decorator_list:
            if isinstance(d, (ast.Name, ast.Attribute)) \
                    and _is_jit_name(call_name(ast.Call(
                        func=d, args=[], keywords=[]))):
                add([f], f.node.lineno)
            elif isinstance(d, ast.Call):
                dn = call_name(d)
                if _is_jit_name(dn):
                    add([f], f.node.lineno)
                elif dn.rsplit(".", 1)[-1] == "partial" and any(
                        isinstance(a, (ast.Name, ast.Attribute))
                        and _is_jit_name(call_name(ast.Call(
                            func=a, args=[], keywords=[])))
                        for a in d.args):
                    add([f], f.node.lineno)

    # call form inside functions
    for f in project.functions.values():
        for call in _own_calls(f):
            if _is_jit_name(call_name(call)) and call.args:
                add(_resolve_jit_arg(call.args[0], project, f, f.rel),
                    call.lineno)

    # call form at module / class level (outside any function)
    for path, tree in project.parsed:
        rel = project._rel(path)
        stack: List[ast.AST] = list(ast.iter_child_nodes(tree))
        while stack:
            n = stack.pop()
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.Lambda)):
                continue
            if isinstance(n, ast.Call) and _is_jit_name(call_name(n)) \
                    and n.args:
                add(_resolve_jit_arg(n.args[0], project, None, rel),
                    n.lineno)
            stack.extend(ast.iter_child_nodes(n))
    return list(roots.values())


def _host_syncs(f: FuncInfo) -> List[Tuple[int, str]]:
    out = []
    for call in _own_calls(f):
        name = call_name(call)
        tail = name.rsplit(".", 1)[-1]
        if name in _HOST_SYNC_CALLS:
            out.append((call.lineno, name))
        elif tail in _HOST_SYNC_METHODS and not call.args:
            out.append((call.lineno, f".{tail}()"))
    return out


def analyze_jit_taint(project: Project) -> List[Dict]:
    findings: List[Dict] = []
    seen: Set[Tuple[str, int]] = set()
    for root, root_line in sorted(_jit_roots(project),
                                  key=lambda r: r[0].key):
        # BFS through the call graph from the jitted root
        frontier: List[Tuple[FuncInfo, Tuple[str, ...]]] = [
            (root, (root.qual,))]
        visited: Set[str] = {root.key}
        while frontier:
            f, chain = frontier.pop(0)
            for line, what in _host_syncs(f):
                key = (f.key, line)
                if key in seen:
                    continue
                seen.add(key)
                via = "" if len(chain) == 1 \
                    else f" (reached via {' -> '.join(chain)})"
                findings.append({
                    "rule": "host-sync-in-jit", "path": f.rel,
                    "line": line,
                    "message": f"{what} inside {f.qual!r}, which is "
                               f"jitted at {root.rel}:{root_line}"
                               f"{via}: a host sync in a traced "
                               "region fails tracing or stalls the "
                               "dispatch stream"})
            if len(chain) >= _MAX_DEPTH:
                continue
            for call in _own_calls(f):
                for callee in project.resolve_call(call, f):
                    if callee.key not in visited:
                        visited.add(callee.key)
                        frontier.append(
                            (callee, chain + (callee.qual,)))
    return findings
