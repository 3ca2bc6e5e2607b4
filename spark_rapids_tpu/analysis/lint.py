"""tpu-lint 2.0: AST rules + interprocedural dataflow analyses.

Every rule is distilled from a bug class this repo has actually
shipped (see CHANGES.md: the window.py f-string SyntaxError,
`time.time()` duration math, dead conf keys, the ledger leaks PR 4/5
satellites patched by hand) or from the invariants its threaded
runtime depends on. The engine is `ast`-exact — no regex over source
text — and reports file:line findings with a machine-readable JSON
form (`tools/tpu_lint.py --json`, ``schema: 2``); tier-1 gates on zero
unallowlisted, unbaselined violations (tests/test_lint.py).

Statement rules (this module)
-----------------------------
- ``wallclock-duration``      — ``time.time()`` (directly or via a
  local assigned from it) used in a subtraction: durations must use
  ``time.monotonic()`` so an NTP step cannot produce negative or
  spurious intervals. Wall stamps stored as event timestamps are fine.
- ``unregistered-conf-key``   — a ``.get("spark....")`` string-literal
  conf read whose key no ``register(...)`` call in the package
  declares: the read silently returns None forever (the AST-exact form
  of `tools/api_validation.py::validate_configs`, which delegates to
  this module's `conf_key_report`).
- ``blocking-call-in-thread`` — ``time.sleep``, zero-argument
  ``.result()`` or zero-argument ``.join()`` in the thread-heavy
  modules (`cluster.py`, `pipeline.py`, `shuffle/host.py`): an
  unbounded block on a worker/feeder thread is how the runtime wedges
  with no heartbeat to blame.
- ``exit-without-flush``      — ``os._exit(...)`` in a function with
  no preceding flush call: the flight recorder's crash-forensics
  guarantee depends on the ring reaching disk before the process dies.

Dataflow analyses (analysis/dataflow.py engine; path-sensitive over a
basic-block CFG with exception edges, interprocedural via call-graph
summaries)
----------
- ``lock-order-cycle`` / ``lock-order-inversion`` /
  ``blocking-under-lock`` — analysis/locks.py: the package lock-
  ordering graph (locks held across helper calls included), checked
  for cycles and against the declared hierarchy
  (`locks.LOCK_HIERARCHY`, which the runtime watchdog in
  analysis/lockwatch.py verifies against real executions), plus
  blocking calls (sleep / unbounded result()/join()/wait() / file I/O
  / device syncs) while any lock is held.
- ``ledger-leak-path``        — analysis/ledger.py: every
  ``DeviceMemoryManager.register`` / ``transient_reservation`` site
  must release, hand off, or store its reservation on ALL CFG paths
  including exception edges (the PR 4/5 hand-patched bug class).
- ``host-sync-in-jit``        — analysis/jit_taint.py: taint
  propagation from every ``jax.jit``-ed callable through the call
  graph; any reachable function performing ``np.asarray`` /
  ``jax.device_get`` / ``.item()`` / ``.block_until_ready()`` is
  flagged wherever it lives (replaces the old two-module file-list
  heuristic).
- ``unlocked-shared-mutation`` — ported onto the lock dataflow: an
  attribute mutated with a lock held somewhere in its class must not
  be mutated (plain or augmented assignment) on a path holding no
  lock. The old AST-pattern rule only saw ``with self._lock:`` blocks,
  so ``acquire()``-style critical sections (SpillableBatch) never
  guarded anything and ``self.x += 1`` outside them was invisible.

Allowlist syntax
----------------
An intentional violation carries an inline comment on the flagged line
or the line directly above::

    time.sleep(poll_s)  # tpu-lint: allow[blocking-call-in-thread] rendezvous poll

``allow[rule-a,rule-b]`` allowlists several rules at once; the text
after the bracket is the REQUIRED reason (an empty reason keeps the
violation fatal). Allowlisted findings stay in the JSON report with
``allowlisted: true`` so the suppression surface is auditable.

Baseline ratchet
----------------
``tools/tpu_lint.py --baseline tools/tpu_lint_baseline.json`` marks
findings whose fingerprint (rule + path + digit-normalized message —
stable across line drift) appears in the checked-in baseline as
``baselined: true`` and fails only on NEW findings. Regenerate with
``--write-baseline`` after deliberately accepting a finding.
"""
from __future__ import annotations

import ast
import dataclasses
import hashlib
import os
import re
from typing import Dict, Iterable, List, Optional, Set, Tuple

__all__ = ["LintFinding", "lint_paths", "lint_package",
           "conf_key_report", "registered_conf_keys", "package_dir",
           "LINT_SCHEMA", "ALL_RULES", "finding_fingerprint",
           "load_baseline", "default_baseline_path"]

#: JSON report schema version (`tools/check_obs_output.py
#: --lint-report` validates against it). v1 = PR 6 statement rules;
#: v2 = dataflow rules + baseline/fingerprint fields.
LINT_SCHEMA = 2

ALL_RULES = (
    "wallclock-duration", "unregistered-conf-key",
    "blocking-call-in-thread", "exit-without-flush",
    "lock-order-cycle", "lock-order-inversion", "blocking-under-lock",
    "ledger-leak-path", "host-sync-in-jit", "unlocked-shared-mutation",
    "syntax-error",
)


@dataclasses.dataclass
class LintFinding:
    rule: str
    path: str
    line: int
    message: str
    allowlisted: bool = False
    allow_reason: str = ""
    baselined: bool = False
    fingerprint: str = ""

    def to_dict(self) -> Dict:
        return dataclasses.asdict(self)


def finding_fingerprint(rule: str, path: str, message: str) -> str:
    """Stable id for the baseline ratchet: line numbers drift with
    every edit, so the message is digit-normalized and the line is
    excluded."""
    norm = re.sub(r"\d+", "N", message)
    return hashlib.sha1(
        f"{rule}|{path}|{norm}".encode()).hexdigest()[:12]


def default_baseline_path() -> str:
    return os.path.join(os.path.dirname(package_dir()), "tools",
                        "tpu_lint_baseline.json")


def load_baseline(path: Optional[str] = None) -> Dict[str, int]:
    """{fingerprint: accepted count} from a baseline file; empty when
    the file is missing (nothing is baselined then)."""
    import json
    path = path or default_baseline_path()
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        return {}
    return {fp: int(meta.get("count", 1))
            for fp, meta in (doc.get("findings") or {}).items()}


def package_dir() -> str:
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _iter_py_files(paths: Iterable[str]) -> List[str]:
    out = []
    for p in paths:
        if os.path.isdir(p):
            for root, dirs, files in os.walk(p):
                dirs[:] = [d for d in dirs if d != "__pycache__"]
                out.extend(os.path.join(root, f) for f in sorted(files)
                           if f.endswith(".py"))
        elif p.endswith(".py"):
            out.append(p)
    return out


def _call_name(node: ast.Call) -> str:
    """Dotted name of a call target ('time.time', 'os._exit', 'x.join');
    only the trailing segments that are plain attributes/names."""
    parts = []
    f = node.func
    while isinstance(f, ast.Attribute):
        parts.append(f.attr)
        f = f.value
    if isinstance(f, ast.Name):
        parts.append(f.id)
    return ".".join(reversed(parts))


def _is_time_time(node: ast.AST) -> bool:
    return (isinstance(node, ast.Call)
            and _call_name(node) in ("time.time",))


# --- rule implementations -----------------------------------------------------

def _rule_wallclock_duration(tree, path, add):
    """time.time() (or a local assigned from it) in a subtraction."""

    class V(ast.NodeVisitor):
        def __init__(self):
            self.wall_names: Set[str] = set()

        def _scoped(self, node):
            saved = self.wall_names
            self.wall_names = set(saved)
            self.generic_visit(node)
            self.wall_names = saved

        visit_FunctionDef = visit_AsyncFunctionDef = _scoped

        def visit_Assign(self, node):
            if _is_time_time(node.value):
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        self.wall_names.add(t.id)
            self.generic_visit(node)

        def _is_wall(self, n):
            return _is_time_time(n) or (
                isinstance(n, ast.Name) and n.id in self.wall_names)

        def visit_BinOp(self, node):
            if isinstance(node.op, ast.Sub) and (
                    self._is_wall(node.left) or self._is_wall(node.right)):
                add("wallclock-duration", node.lineno,
                    "duration computed from time.time(); use "
                    "time.monotonic() (wall clock steps under NTP)")
            self.generic_visit(node)

    V().visit(tree)


def _rule_unregistered_conf_key(tree, path, add, registered: Set[str]):
    """.get("spark....") literal reads must name a registered key."""
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "get" and node.args):
            continue
        arg = node.args[0]
        if isinstance(arg, ast.Constant) and isinstance(arg.value, str) \
                and arg.value.startswith("spark.") \
                and arg.value not in registered:
            add("unregistered-conf-key", node.lineno,
                f"conf key {arg.value!r} is read here but never "
                "registered in the config registry (the read returns "
                "None/default forever)")


_THREAD_MODULES = ("cluster.py", "pipeline.py", os.path.join("shuffle",
                                                             "host.py"))


def _rule_blocking_call(tree, path, add):
    if not any(path.endswith(m) for m in _THREAD_MODULES):
        return
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        name = _call_name(node)
        tail = name.rsplit(".", 1)[-1]
        if name in ("time.sleep", "sleep"):
            add("blocking-call-in-thread", node.lineno,
                "time.sleep in a thread-heavy module: prefer "
                "Event.wait(timeout) so shutdown can interrupt")
        elif tail in ("result", "join") and not node.args \
                and not node.keywords and name not in ("os.path.join",):
            add("blocking-call-in-thread", node.lineno,
                f"unbounded .{tail}() blocks this thread forever if "
                "the other side wedged; pass a timeout and handle it")


def _rule_exit_without_flush(tree, path, add):
    for fn in ast.walk(tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        flush_line = None
        for node in ast.walk(fn):
            if isinstance(node, ast.Call) \
                    and "flush" in _call_name(node).lower():
                flush_line = min(flush_line or node.lineno, node.lineno)
        for node in ast.walk(fn):
            if isinstance(node, ast.Call) \
                    and _call_name(node) == "os._exit":
                if flush_line is None or flush_line > node.lineno:
                    add("exit-without-flush", node.lineno,
                        "os._exit without a preceding recorder/ring "
                        "flush in this function: the crash leaves no "
                        "forensics behind")


# --- allowlist ----------------------------------------------------------------

_ALLOW_RE = re.compile(
    r"#\s*tpu-lint:\s*allow\[([a-z0-9_,\- ]+)\]\s*(.*)")


def _allow_for(lines: List[str], lineno: int) -> Dict[str, str]:
    """{rule: reason} allowlisted at this line: a trailing comment on
    the line itself, or a comment-ONLY line directly above. A trailing
    allow on the previous code line does NOT carry over — it blessed
    that line, not this one."""
    out: Dict[str, str] = {}

    def collect(ln):
        if 1 <= ln <= len(lines):
            m = _ALLOW_RE.search(lines[ln - 1])
            if m:
                reason = m.group(2).strip().rstrip("#").strip()
                for rule in m.group(1).split(","):
                    out.setdefault(rule.strip(), reason)

    collect(lineno)
    if lineno >= 2 and lines[lineno - 2].lstrip().startswith("#"):
        collect(lineno - 1)
    return out


# --- conf-key registry (AST-exact) --------------------------------------------

def _parse_files(files: List[str]) -> List[Tuple[str, ast.AST]]:
    out = []
    for path in files:
        try:
            out.append((path, ast.parse(open(path).read())))
        except SyntaxError:
            continue
    return out


def registered_conf_keys(
        parsed: Optional[List[Tuple[str, ast.AST]]] = None) -> Set[str]:
    """Every key a `register("...")` call declares, package-wide (the
    registry spans config.py, memory.py, obs/, tools/event_log.py).
    Accepts pre-parsed (path, tree) pairs so callers that already
    parsed the package do not pay a second ast.parse sweep."""
    if parsed is None:
        parsed = _parse_files(_iter_py_files([package_dir()]))
    keys: Set[str] = set()
    for _path, tree in parsed:
        for node in ast.walk(tree):
            if isinstance(node, ast.Call) \
                    and _call_name(node).rsplit(".", 1)[-1] == "register" \
                    and node.args \
                    and isinstance(node.args[0], ast.Constant) \
                    and isinstance(node.args[0].value, str):
                keys.add(node.args[0].value)
    return keys


def conf_key_report(pkg: Optional[str] = None) -> Dict[str, List[str]]:
    """AST-exact dead/unregistered conf audit (what
    `tools/api_validation.py::validate_configs` delegates to):

    - an entry is CONSUMED when the name its `register(...)` result is
      bound to is referenced anywhere outside that assignment, or its
      literal key is passed as a call argument outside register();
    - a read is UNREGISTERED when `.get("spark....")` names a key no
      register() call declares.
    """
    pkg = pkg or package_dir()
    registered: Dict[str, str] = {}     # key -> bound name
    entry_names: Set[str] = set()
    name_refs: Dict[str, int] = {}
    key_arg_refs: Dict[str, int] = {}
    unregistered: List[Tuple[str, str, int]] = []

    parsed = _parse_files(_iter_py_files([pkg]))
    for path, tree in parsed:
        for node in ast.walk(tree):
            if isinstance(node, ast.Assign) \
                    and isinstance(node.value, ast.Call) \
                    and _call_name(node.value).rsplit(".", 1)[-1] == \
                    "register" \
                    and node.value.args \
                    and isinstance(node.value.args[0], ast.Constant):
                key = node.value.args[0].value
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        registered[key] = t.id
                        entry_names.add(t.id)
    for path, tree in parsed:
        for node in ast.walk(tree):
            if isinstance(node, ast.Name) and node.id in entry_names \
                    and isinstance(node.ctx, ast.Load):
                name_refs[node.id] = name_refs.get(node.id, 0) + 1
            elif isinstance(node, ast.Attribute) \
                    and node.attr in entry_names:
                name_refs[node.attr] = name_refs.get(node.attr, 0) + 1
            elif isinstance(node, ast.Call):
                is_register = _call_name(node).rsplit(".", 1)[-1] == \
                    "register"
                for a in list(node.args) + [k.value for k in node.keywords]:
                    if isinstance(a, ast.Constant) \
                            and isinstance(a.value, str) \
                            and a.value in registered and not is_register:
                        key_arg_refs[a.value] = \
                            key_arg_refs.get(a.value, 0) + 1
                if isinstance(node.func, ast.Attribute) \
                        and node.func.attr == "get" and node.args:
                    a = node.args[0]
                    if isinstance(a, ast.Constant) \
                            and isinstance(a.value, str) \
                            and a.value.startswith("spark.") \
                            and a.value not in registered:
                        unregistered.append((a.value, path, node.lineno))
    unused = sorted(
        key for key, name in registered.items()
        if name_refs.get(name, 0) == 0 and key_arg_refs.get(key, 0) == 0)
    return {
        "checked": sorted(registered),
        "unused": unused,
        "unregistered_reads": [
            {"key": k, "path": os.path.relpath(p, pkg), "line": ln}
            for k, p, ln in unregistered],
    }


# --- engine -------------------------------------------------------------------

def lint_paths(paths: Optional[List[str]] = None,
               baseline: Optional[Dict[str, int]] = None) -> Dict:
    """Run every rule — the statement rules above plus the dataflow
    analyses (locks / ledger / jit taint) — over `paths` (default: the
    installed package). Returns {"schema": 2, "findings": [...],
    "violations": N, ...} with allowlisted and baselined findings
    included but not counted as violations."""
    pkg = package_dir()
    files = _iter_py_files(paths or [pkg])
    findings: List[LintFinding] = []
    parsed: List[Tuple[str, ast.AST, str]] = []
    lines_by_rel: Dict[str, List[str]] = {}
    for path in files:
        try:
            src = open(path).read()
            parsed.append((path, ast.parse(src), src))
        except SyntaxError as e:
            findings.append(LintFinding(
                "syntax-error",
                os.path.relpath(path, pkg)
                if path.startswith(pkg + os.sep) else path,
                e.lineno or 0, str(e)))
    # when the lint target IS the package, its parse also serves the
    # conf-key registry sweep (no second ast.parse over ~80 files);
    # arbitrary targets still check against the package registry
    if paths is None or paths == [pkg]:
        registered = registered_conf_keys(
            [(p, t) for p, t, _ in parsed])
    else:
        registered = registered_conf_keys()

    def mk_add(rel, lines):
        def add(rule, lineno, message):
            allows = _allow_for(lines, lineno)
            reason = allows.get(rule, "")
            findings.append(LintFinding(
                rule, rel, lineno, message,
                allowlisted=bool(reason), allow_reason=reason))
        return add

    # display paths: package files report relative to the package
    # (stable fingerprints); out-of-tree targets keep the path as
    # given (absolute), like v1 did — a machine-dependent relpath
    # would both read badly and break fingerprint sharing
    display = {}
    for path, tree, src in parsed:
        lines = src.splitlines()
        disp = os.path.relpath(path, pkg) \
            if path.startswith(pkg + os.sep) else path
        display[os.path.relpath(path, pkg)] = (disp, lines)
        lines_by_rel[disp] = lines
        add = mk_add(disp, lines)
        _rule_wallclock_duration(tree, path, add)
        _rule_unregistered_conf_key(tree, path, add, registered)
        _rule_blocking_call(tree, path, add)
        _rule_exit_without_flush(tree, path, add)

    # package-level dataflow analyses over the same parsed trees
    from .dataflow import Project
    from .jit_taint import analyze_jit_taint
    from .ledger import analyze_ledger
    from .locks import analyze_locks
    project = Project([(p, t) for p, t, _ in parsed], root=pkg)
    for f in (analyze_locks(project) + analyze_ledger(project)
              + analyze_jit_taint(project)):
        disp, lines = display.get(f["path"], (f["path"], []))
        mk_add(disp, lines)(f["rule"], f["line"], f["message"])

    baseline = dict(baseline or {})
    for f in findings:
        f.fingerprint = finding_fingerprint(f.rule, f.path, f.message)
        if not f.allowlisted and baseline.get(f.fingerprint, 0) > 0:
            baseline[f.fingerprint] -= 1
            f.baselined = True
    return {
        "schema": LINT_SCHEMA,
        "rules": list(ALL_RULES),
        "findings": [f.to_dict() for f in findings],
        "violations": sum(1 for f in findings
                          if not f.allowlisted and not f.baselined),
        "allowlisted": sum(1 for f in findings if f.allowlisted),
        "baselined": sum(1 for f in findings if f.baselined),
        "files": len(files),
    }


def lint_package(baseline: Optional[Dict[str, int]] = None) -> Dict:
    return lint_paths([package_dir()], baseline=baseline)
