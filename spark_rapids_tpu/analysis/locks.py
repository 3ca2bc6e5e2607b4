"""Lock-order & blocking-under-lock analysis (tpu-lint 2.0).

Built on the dataflow engine (analysis/dataflow.py): the fact is the
ordered tuple of locks the current thread holds, propagated through the
CFG (``with`` blocks, explicit ``.acquire()``/``.release()`` pairs,
early returns, exception edges) and **through helper calls** via
call-graph summaries — a lock held in ``register()`` while
``_evict_to_fit`` → ``spill()`` acquires another is an edge in the
package lock-ordering graph even though no single function shows both.

Three rule families come out of one solved lattice:

- ``lock-order-cycle``      — the package-wide lock-ordering graph
  (edge a→b = b acquired while a held, directly or through calls) has
  a cycle: a potential deadlock. Try-acquires (``acquire(blocking=
  False)`` or a non-literal blocking argument — the ledger's
  best-effort spill protocol) hold the lock but add **no** incoming
  edge: a try-acquire cannot complete a hold-and-wait cycle.
- ``lock-order-inversion``  — an edge that contradicts the DECLARED
  package hierarchy (:data:`LOCK_HIERARCHY`, the same table the
  runtime watchdog in lockwatch.py enforces against real executions).
- ``blocking-under-lock``   — ``time.sleep``, zero-argument
  ``.result()``/``.join()``/``.wait()``, file I/O (``open``,
  ``os.replace``/``rename``/``link``, ``pa.OSFile``,
  ``shutil.rmtree``), or a device sync (``block_until_ready``,
  ``device_get``) while at least one lock is held — directly or
  inside any resolvable callee. ``Condition.wait`` on the held
  condition's *own* lock is exempt (wait releases it).

plus the dataflow port of PR 6's ``unlocked-shared-mutation``: an
attribute mutated with a lock held somewhere in its class must not be
mutated (plain or **augmented** assignment — the old rule's false
negative) on any path where no lock is held. Lock-held-ness here is the
solved fact, so ``.acquire()``-style critical sections (SpillableBatch)
and mutations after an early ``release()`` are finally visible.
"""
from __future__ import annotations

import ast
import dataclasses
from typing import Dict, List, Optional, Sequence, Set, Tuple

from .dataflow import (Analysis, FuncInfo, LoopIter, Project, WithEnter,
                       WithExit, call_name, fixpoint_summaries, solve,
                       stmt_calls)

__all__ = ["LOCK_HIERARCHY", "lock_level", "collect_locks",
           "analyze_locks", "lock_graph"]


# --- the declared package lock hierarchy -------------------------------------
#
# Levels ascend in acquisition order: a thread holding a level-N lock
# may only block-acquire locks with level > N. The static analysis
# checks every graph edge against this table; the runtime watchdog
# (analysis/lockwatch.py) checks every REAL acquisition in
# watchdog-enabled test runs against the same table — static analysis
# proposes the order, the watchdog verifies it against reality.
# ``runtime`` is (file basename, class-or-None, function-or-None)
# matching the lock's creation site (lockwatch matches most-specific
# first). README.md ("Lock-order appendix") renders this table.

@dataclasses.dataclass(frozen=True)
class LockLevel:
    pattern: str   # fnmatch over the static lock id
    level: int
    runtime: Tuple[str, Optional[str], Optional[str]]
    desc: str


LOCK_HIERARCHY: Tuple[LockLevel, ...] = (
    LockLevel("*exchange.py::_SHARED_LOCK_INIT", 10,
              ("exchange.py", None, "<module>"),
              "guards lazy creation of per-exchange shared locks"),
    LockLevel("TpuShuffleExchangeExec._shared_lock", 12,
              ("exchange.py", "TpuShuffleExchangeExec", None),
              "one materialize per shared exchange; held across "
              "materialize() and therefore above every lock below"),
    LockLevel("DeviceMemoryManager._shared_lock", 15,
              # class-body creation: the frame is named after the class
              ("memory.py", None, "DeviceMemoryManager"),
              "process-level manager cache; held across __init__ "
              "(which publishes gauges and flight events)"),
    LockLevel("*ici.py::_LOCAL_LOCK", 15,
              ("ici.py", None, "<module>"),
              "the process's one ICI transport per device set "
              "(local_transport); held across the transport's __init__, "
              "which takes no lock"),
    LockLevel("HostShuffleTransport._lock", 20,
              ("host.py", "HostShuffleTransport", "__init__"),
              "shuffle bookkeeping (futures/manifests/stats)"),
    LockLevel("LocalShuffleTransport._lock", 20,
              ("transport.py", "LocalShuffleTransport", None),
              "in-process shuffle store bookkeeping"),
    LockLevel("IciShuffleTransport._lock", 20,
              ("ici.py", "IciShuffleTransport", None),
              "collective-transport bookkeeping"),
    LockLevel("FairAdmissionController._cv", 28,
              ("lifecycle.py", "FairAdmissionController", "__init__"),
              "fair-admission queues/grants; the cancellation token's "
              "lock (34) and the observability leaves are acquired "
              "under it (token poll / queue-depth gauge), never the "
              "reverse"),
    LockLevel("_WeightedWindow._cv", 30,
              ("pipeline.py", "_WeightedWindow", None),
              "pipelined-map admission window; polls the cancellation "
              "token (34) while waiting"),
    LockLevel("*parquet_device.py::_JIT_LOCK", 30,
              ("parquet_device.py", None, "<module>"),
              "fused-decode jit arena cache"),
    LockLevel("*scan.py::*.ilock", 30,
              ("scan.py", None, None),
              "scan feeder in-flight set (releases ledger entries "
              "under it on the early-close path)"),
    LockLevel("*host.py::*.ilock", 30,
              ("host.py", "HostShuffleTransport", "read_partition"),
              "shuffle-read feeder in-flight set"),
    LockLevel("CancellationToken._lock", 34,
              ("lifecycle.py", "CancellationToken", "__init__"),
              "classify-once cancellation flag; leaf-ish — only the "
              "metrics/flight leaves sit below it"),
    LockLevel("SpillableBatch._state_lock", 40,
              ("memory.py", "SpillableBatch", None),
              "per-batch tier transitions; acquires the ledger lock "
              "inside (eviction paths only ever TRY-acquire it)"),
    LockLevel("*memory.py::_SWEEP_LOCK", 45,
              ("memory.py", None, "<module>"),
              "orphan-spill-sweep once-per-root guard: held only "
              "around the swept-roots set check (the sweep's IO runs "
              "outside it); acquired during manager construction, so "
              "it sits above the manager-cache lock (15) and below "
              "the ledger"),
    LockLevel("DeviceMemoryManager._lock", 50,
              ("memory.py", "DeviceMemoryManager", "__init__"),
              "the byte ledger + catalog; leaf-ish: nothing below it "
              "but observability"),
    LockLevel("Tracer._lock", 60,
              ("tracer.py", "Tracer", None),
              "span buffer"),
    LockLevel("*warehouse.py::_seg_lock", 65,
              ("warehouse.py", None, "<module>"),
              "telemetry-warehouse segment writer: one sealed rewrite "
              "per QUERY, held across the file IO and the retention "
              "prune; taken at query exit with nothing else held, "
              "above only the flight/metric leaves"),
    LockLevel("FlightRecorder._lock", 70,
              ("recorder.py", "FlightRecorder", None),
              "flight-recorder ring"),
    LockLevel("OpMetricsCollector._times_lock", 75,
              ("opmetrics.py", "OpMetricsCollector", None),
              "deferred stage-time result buffer (appended by the "
              "process-wide stage-timer thread, drained at finalize); "
              "held only around list swap/append, above everything "
              "but the metric leaves"),
    LockLevel("*recorder.py::*", 70,
              ("recorder.py", None, None),
              "incident sequence guard"),
    LockLevel("_Family._lock", 80,
              ("metrics.py", "_Family", None),
              "per-metric series map"),
    LockLevel("MetricsRegistry._lock", 80,
              ("metrics.py", "MetricsRegistry", None),
              "metrics registry"),
    LockLevel("*metrics.py::*", 85,
              ("metrics.py", None, None),
              "metric update + /metrics HTTP guards (taken under the "
              "series-map lock); absolute leaf tier"),
    LockLevel("*lockwatch.py::*", 90,
              ("lockwatch.py", None, None),
              "the watchdog's own inversion-list guard; held only "
              "around list appends/copies, below everything"),
)


def lock_level(static_id: str) -> Optional[int]:
    import fnmatch
    for entry in LOCK_HIERARCHY:
        if fnmatch.fnmatchcase(static_id, entry.pattern):
            return entry.level
    return None


# --- lock registry -----------------------------------------------------------

_LOCK_CTORS = {"Lock", "RLock", "Condition"}
_LOCKISH_CTORS = _LOCK_CTORS | {"Semaphore", "BoundedSemaphore"}


@dataclasses.dataclass(frozen=True)
class LockDecl:
    lock_id: str      # "Class.attr" | "rel.py::name" | "rel.py::fn.name"
    kind: str         # Lock | RLock | Condition
    rel: str
    line: int


def _ctor_kind(node: ast.AST) -> Optional[str]:
    """'Lock'/'RLock'/'Condition' when node is a threading lock ctor."""
    if not isinstance(node, ast.Call):
        return None
    name = call_name(node)
    tail = name.rsplit(".", 1)[-1]
    if tail not in _LOCK_CTORS:
        return None
    head = name.split(".")[0]
    if head in ("threading", "_threading", tail):
        return tail
    return None


def collect_locks(project: Project) -> Dict[str, LockDecl]:
    """Every threading.Lock/RLock/Condition creation site, package-wide,
    keyed by lock id. Attributes key by owning class; module globals
    and function locals key by module path (locals also by function)."""
    out: Dict[str, LockDecl] = {}

    def add(lock_id, kind, rel, line):
        out.setdefault(lock_id, LockDecl(lock_id, kind, rel, line))

    for path, tree in project.parsed:
        rel = project._rel(path)

        def visit(node, cls: Optional[str], fn: Optional[str]):
            for child in ast.iter_child_nodes(node):
                if isinstance(child, ast.ClassDef):
                    visit(child, child.name, None)
                    continue
                if isinstance(child, (ast.FunctionDef,
                                      ast.AsyncFunctionDef)):
                    visit(child, cls, child.name)
                    continue
                if isinstance(child, (ast.Assign, ast.AnnAssign)):
                    kind = _ctor_kind(getattr(child, "value", None))
                    if kind:
                        targets = child.targets \
                            if isinstance(child, ast.Assign) \
                            else [child.target]
                        for t in targets:
                            if isinstance(t, ast.Attribute) \
                                    and isinstance(t.value, ast.Name) \
                                    and t.value.id == "self" and cls:
                                add(f"{cls}.{t.attr}", kind, rel,
                                    child.lineno)
                            elif isinstance(t, ast.Name):
                                if cls and fn is None:
                                    add(f"{cls}.{t.id}", kind, rel,
                                        child.lineno)
                                elif fn:
                                    add(f"{rel}::{fn}.{t.id}", kind,
                                        rel, child.lineno)
                                else:
                                    add(f"{rel}::{t.id}", kind, rel,
                                        child.lineno)
                visit(child, cls, fn)

        visit(tree, None, None)
    return out


class _LockResolver:
    """Map a lock-reference expression to a registry lock id."""

    def __init__(self, project: Project, registry: Dict[str, LockDecl]):
        self.project = project
        self.registry = registry
        # attr name -> owning classes (for unique-attr fallback)
        self.attr_owners: Dict[str, List[str]] = {}
        for lock_id in registry:
            if "::" not in lock_id and "." in lock_id:
                cls, attr = lock_id.split(".", 1)
                self.attr_owners.setdefault(attr, []).append(cls)

    def resolve(self, expr: ast.AST,
                caller: FuncInfo) -> Optional[str]:
        # self.X / cls.X
        if isinstance(expr, ast.Attribute):
            base = expr.value
            if isinstance(base, ast.Name):
                if base.id in ("self", "cls") and caller.cls:
                    lid = f"{caller.cls}.{expr.attr}"
                    if lid in self.registry:
                        return lid
                # ClassName._shared_lock
                lid = f"{base.id}.{expr.attr}"
                if lid in self.registry:
                    return lid
                # local with a known class (ctor assignment/annotation)
                cls = self.project._local_ctor_class(base.id, caller)
                if cls:
                    lid = f"{cls}.{expr.attr}"
                    if lid in self.registry:
                        return lid
            elif isinstance(base, ast.Attribute) \
                    and isinstance(base.value, ast.Name) \
                    and base.value.id == "self" and caller.cls:
                cls = self.project.attr_types.get(
                    caller.cls, {}).get(base.attr)
                if cls:
                    lid = f"{cls}.{expr.attr}"
                    if lid in self.registry:
                        return lid
            # unique attribute name anywhere in the package
            owners = self.attr_owners.get(
                getattr(expr, "attr", None), [])
            if len(owners) == 1:
                return f"{owners[0]}.{expr.attr}"
            return None
        if isinstance(expr, ast.Name):
            for lid in (f"{caller.rel}::{caller.name}.{expr.id}",
                        f"{caller.rel}::{expr.id}"):
                if lid in self.registry:
                    return lid
            # nested function referencing the enclosing function's local
            if "<locals>" in caller.qual:
                outer = caller.qual.split(
                    ".<locals>.")[0].split(".")[-1]
                lid = f"{caller.rel}::{outer}.{expr.id}"
                if lid in self.registry:
                    return lid
        return None


# --- blocking primitives -----------------------------------------------------

_BLOCKING_CALLS = {"time.sleep", "sleep", "os.replace", "os.rename",
                   "os.link", "os.unlink", "os.makedirs", "open",
                   "shutil.rmtree", "pa.OSFile", "jax.device_get",
                   "device_get", "subprocess.run"}
_BLOCKING_0ARG_METHODS = {"result", "join", "wait"}
_BLOCKING_METHODS = {"block_until_ready"}


def _blocking_reason(call: ast.Call,
                     held_cv: Optional[str] = None,
                     resolver: Optional[_LockResolver] = None,
                     caller: Optional[FuncInfo] = None) -> Optional[str]:
    """Why this call blocks, or None. `held_cv`: when the receiver of a
    0-arg .wait() is a held Condition, the wait RELEASES it (not a
    block under that lock)."""
    name = call_name(call)
    tail = name.rsplit(".", 1)[-1]
    if name in _BLOCKING_CALLS:
        return name
    if tail in _BLOCKING_METHODS:
        return f".{tail}()"
    if tail in _BLOCKING_0ARG_METHODS and not call.args \
            and not call.keywords and name != "os.path.join":
        if tail == "wait" and resolver is not None and caller is not None \
                and isinstance(call.func, ast.Attribute):
            lid = resolver.resolve(call.func.value, caller)
            if lid is not None and lid == held_cv:
                return None  # cv.wait() releases the held cv lock
        return f"unbounded .{tail}()"
    return None


def _acquire_is_blocking(call: ast.Call) -> bool:
    """acquire() blocks unless blocking=False / blocking=<non-literal>
    (best-effort try-acquire protocols) or a literal False first arg."""
    for kw in call.keywords:
        if kw.arg == "blocking":
            return isinstance(kw.value, ast.Constant) \
                and kw.value.value is True
    if call.args:
        a = call.args[0]
        if isinstance(a, ast.Constant):
            return a.value is True or isinstance(a.value, (int, float))
        return False  # non-literal: treat as try-acquire
    return True


# --- summaries ---------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LockSummary:
    acquires: frozenset = frozenset()   # lock ids block-acquired inside
    blocking: Tuple = ()                # (reason, rel, line) or ()

    def __or__(self, other):
        return LockSummary(self.acquires | other.acquires,
                           self.blocking or other.blocking)


def _function_summaries(project: Project, resolver: _LockResolver,
                        funcs: Sequence[FuncInfo]) -> Dict[str, LockSummary]:
    def compute(f: FuncInfo, summaries) -> LockSummary:
        acq: Set[str] = set()
        blocking: Tuple = ()
        for node in ast.walk(f.node):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and node is not f.node:
                continue  # nested defs summarize separately
            if isinstance(node, ast.With):
                for item in node.items:
                    lid = resolver.resolve(item.context_expr, f)
                    if lid:
                        acq.add(lid)
            if not isinstance(node, ast.Call):
                continue
            tail = call_name(node).rsplit(".", 1)[-1]
            if tail == "acquire" and isinstance(node.func, ast.Attribute):
                lid = resolver.resolve(node.func.value, f)
                if lid and _acquire_is_blocking(node):
                    acq.add(lid)
                continue
            why = _blocking_reason(node)
            if why and not blocking:
                blocking = (why, f.rel, node.lineno)
            for callee in project.resolve_call(node, f):
                s = summaries.get(callee.key)
                if s:
                    acq |= s.acquires
                    if s.blocking and not blocking:
                        blocking = s.blocking
        return LockSummary(frozenset(acq), blocking)

    return fixpoint_summaries(project, funcs, compute,
                              initial=LockSummary)


# --- the dataflow analysis ---------------------------------------------------

class _HeldLocks(Analysis):
    """Fact: ordered tuple of (lock_id, blocking) currently held."""

    def __init__(self, func: FuncInfo, project: Project,
                 resolver: _LockResolver,
                 summaries: Dict[str, LockSummary], sink):
        self.f = func
        self.project = project
        self.resolver = resolver
        self.summaries = summaries
        self.sink = sink  # collects edges / findings / mutations

    def initial(self):
        return ()

    def join(self, a, b):
        if a == b:
            return a
        out = list(a)
        for item in b:
            if item not in out:
                out.append(item)
        return tuple(out)

    # -- helpers ----------------------------------------------------------

    def _held_ids(self, fact) -> Tuple[str, ...]:
        return tuple(lid for lid, _ in fact)

    def _acquire(self, fact, lid: str, blocking: bool, line: int):
        decl = self.resolver.registry.get(lid)
        reentrant = decl is not None and decl.kind in ("RLock",
                                                       "Condition")
        if any(h == lid for h, _ in fact):
            if not reentrant and blocking:
                # a non-reentrant lock re-acquired while held:
                # self-deadlock — a 1-cycle in the order graph
                self.sink.edge(lid, lid, self.f, line)
            return fact
        if blocking:
            for h, _ in fact:
                self.sink.edge(h, lid, self.f, line)
        return fact + ((lid, blocking),)

    def _release(self, fact, lid: str):
        return tuple((h, b) for h, b in fact if h != lid)

    def _held_condition(self, fact) -> Optional[str]:
        for lid, _ in fact:
            decl = self.resolver.registry.get(lid)
            if decl is not None and decl.kind == "Condition":
                return lid
        return None

    # -- transfer ---------------------------------------------------------

    def transfer(self, stmt, fact):
        if isinstance(stmt, WithEnter):
            lid = self.resolver.resolve(stmt.node.context_expr, self.f)
            if lid:
                return self._acquire(fact, lid, True, stmt.lineno)
            # `with lock.acquire():` style never occurs; but the ctx
            # expr may contain calls worth scanning (e.g. tempfile)
            return self._scan_calls(stmt, fact)
        if isinstance(stmt, WithExit):
            lid = self.resolver.resolve(stmt.node.context_expr, self.f)
            if lid:
                return self._release(fact, lid)
            return fact
        if isinstance(stmt, LoopIter):
            return fact
        node = getattr(stmt, "node", stmt)
        # record self-attribute mutations with the current held set
        if isinstance(node, (ast.Assign, ast.AugAssign)):
            targets = node.targets if isinstance(node, ast.Assign) \
                else [node.target]
            flat = []
            for t in targets:
                flat.extend(t.elts if isinstance(t, ast.Tuple) else [t])
            for t in flat:
                if isinstance(t, ast.Attribute) \
                        and isinstance(t.value, ast.Name) \
                        and t.value.id == "self":
                    self.sink.mutation(self.f, t.attr,
                                       self._held_ids(fact),
                                       node.lineno)
        return self._scan_calls(stmt, fact)

    def _scan_calls(self, stmt, fact):
        held_cv = self._held_condition(fact)
        for call in stmt_calls(stmt):
            tail = call_name(call).rsplit(".", 1)[-1]
            if tail in ("acquire", "release") \
                    and isinstance(call.func, ast.Attribute):
                lid = self.resolver.resolve(call.func.value, self.f)
                if lid:
                    if tail == "acquire":
                        fact = self._acquire(
                            fact, lid, _acquire_is_blocking(call),
                            call.lineno)
                    else:
                        fact = self._release(fact, lid)
                    continue
            if not fact:
                continue
            why = _blocking_reason(call, held_cv, self.resolver, self.f)
            if why:
                self.sink.blocking(self.f, why, self._held_ids(fact),
                                   call.lineno)
                continue
            for callee in self.project.resolve_call(call, self.f):
                s = self.summaries.get(callee.key)
                if s is None:
                    continue
                for acquired in sorted(s.acquires):
                    for h, _ in fact:
                        if h != acquired:
                            self.sink.edge(h, acquired, self.f,
                                           call.lineno,
                                           via=callee.qual)
                if s.blocking:
                    why, rel, line = s.blocking
                    self.sink.blocking(
                        self.f, f"{why} (via {callee.qual} at "
                        f"{rel}:{line})", self._held_ids(fact),
                        call.lineno)
        return fact


class _Sink:
    def __init__(self):
        # (a, b) -> first (rel, line, func, via)
        self.edges: Dict[Tuple[str, str], Tuple] = {}
        self.blockings: List[Tuple] = []
        self.mutations: List[Tuple] = []
        self._seen_block: Set[Tuple] = set()

    def edge(self, a, b, f: FuncInfo, line, via: str = ""):
        self.edges.setdefault((a, b), (f.rel, line, f.qual, via))

    def blocking(self, f: FuncInfo, why, held, line):
        key = (f.key, line, why)
        if key not in self._seen_block:
            self._seen_block.add(key)
            self.blockings.append((f, why, held, line))

    def mutation(self, f: FuncInfo, attr, held, line):
        self.mutations.append((f, attr, held, line))


# --- public entry points -----------------------------------------------------

def lock_graph(project: Project) -> Dict:
    """Solve the package and return the raw lock-ordering graph:
    {"locks": {...}, "edges": [{"from", "to", "site", "via"}],
    "cycles": [[lock ids]]}. `tpu_lint --lock-graph` renders this."""
    registry = collect_locks(project)
    resolver = _LockResolver(project, registry)
    funcs = list(project.functions.values())
    summaries = _function_summaries(project, resolver, funcs)
    sink = _Sink()
    for f in funcs:
        solve(project.cfg(f), _HeldLocks(f, project, resolver,
                                         summaries, sink))
    cycles = _find_cycles(sink.edges)
    return {
        "locks": {lid: {"kind": d.kind, "site": f"{d.rel}:{d.line}",
                        "level": lock_level(lid)}
                  for lid, d in sorted(registry.items())},
        "edges": [{"from": a, "to": b, "site": f"{rel}:{line}",
                   "func": qual, "via": via}
                  for (a, b), (rel, line, qual, via)
                  in sorted(sink.edges.items())],
        "cycles": cycles,
        "_sink": sink,
        "_registry": registry,
    }


def _find_cycles(edges: Dict[Tuple[str, str], Tuple]) -> List[List[str]]:
    """Strongly connected components with >1 node, plus self-loops."""
    graph: Dict[str, Set[str]] = {}
    for a, b in edges:
        graph.setdefault(a, set()).add(b)
        graph.setdefault(b, set())
    index: Dict[str, int] = {}
    low: Dict[str, int] = {}
    on: Set[str] = set()
    stack: List[str] = []
    out: List[List[str]] = []
    counter = [0]

    def strongconnect(v):  # iterative Tarjan
        work = [(v, iter(sorted(graph[v])))]
        index[v] = low[v] = counter[0]
        counter[0] += 1
        stack.append(v)
        on.add(v)
        while work:
            node, it = work[-1]
            advanced = False
            for w in it:
                if w not in index:
                    index[w] = low[w] = counter[0]
                    counter[0] += 1
                    stack.append(w)
                    on.add(w)
                    work.append((w, iter(sorted(graph[w]))))
                    advanced = True
                    break
                elif w in on:
                    low[node] = min(low[node], index[w])
            if advanced:
                continue
            work.pop()
            if work:
                parent = work[-1][0]
                low[parent] = min(low[parent], low[node])
            if low[node] == index[node]:
                comp = []
                while True:
                    w = stack.pop()
                    on.discard(w)
                    comp.append(w)
                    if w == node:
                        break
                if len(comp) > 1 or node in graph.get(node, ()):
                    out.append(sorted(comp))

    for v in sorted(graph):
        if v not in index:
            strongconnect(v)
    return out


def analyze_locks(project: Project) -> List[Dict]:
    """Findings for lint: lock-order-cycle, lock-order-inversion,
    blocking-under-lock, unlocked-shared-mutation."""
    g = lock_graph(project)
    sink: _Sink = g["_sink"]
    registry: Dict[str, LockDecl] = g["_registry"]
    findings: List[Dict] = []

    for cycle in g["cycles"]:
        # anchor the finding at the first edge inside the cycle
        site = None
        path = []
        cset = set(cycle)
        for (a, b), (rel, line, qual, via) in sorted(sink.edges.items()):
            if a in cset and b in cset:
                if site is None:
                    site = (rel, line)
                path.append(f"{a}->{b} at {rel}:{line}"
                            + (f" via {via}" if via else ""))
        rel, line = site or (registry[cycle[0]].rel,
                             registry[cycle[0]].line)
        findings.append({
            "rule": "lock-order-cycle", "path": rel, "line": line,
            "message": "potential deadlock: lock-ordering cycle "
                       f"[{' -> '.join(cycle + [cycle[0]])}]; "
                       + "; ".join(path)})

    for (a, b), (rel, line, qual, via) in sorted(sink.edges.items()):
        la, lb = lock_level(a), lock_level(b)
        if la is not None and lb is not None and la > lb:
            findings.append({
                "rule": "lock-order-inversion", "path": rel,
                "line": line,
                "message": f"{b} (level {lb}) acquired while holding "
                           f"{a} (level {la}) in {qual}"
                           + (f" via {via}" if via else "")
                           + "; the declared hierarchy "
                           "(analysis/locks.py::LOCK_HIERARCHY) orders "
                           "them the other way"})

    for f, why, held, line in sink.blockings:
        findings.append({
            "rule": "blocking-under-lock", "path": f.rel, "line": line,
            "message": f"{why} while holding "
                       f"[{', '.join(held)}] in {f.qual}: a blocked "
                       "holder starves every other thread contending "
                       "for the lock"})

    findings.extend(_unlocked_mutations(project, sink))
    return findings


def _unlocked_mutations(project: Project, sink: _Sink) -> List[Dict]:
    """Port of the PR 6 rule onto the solved lock facts: an attribute
    mutated with a lock held somewhere in its class must not be mutated
    lock-free elsewhere (outside __init__). Catches acquire()-style
    sections and augmented assignments the AST-pattern rule missed."""
    by_cls: Dict[Tuple[str, str], List[Tuple]] = {}
    for f, attr, held, line in sink.mutations:
        if f.cls is None:
            continue
        by_cls.setdefault((f.rel, f.cls), []).append(
            (f, attr, held, line))
    out: List[Dict] = []
    for (rel, cls), muts in sorted(by_cls.items()):
        guarded: Dict[str, str] = {}
        for f, attr, held, line in muts:
            if held and f.name != "__init__":
                guarded.setdefault(attr, held[0])
        for f, attr, held, line in muts:
            if attr in guarded and not held and f.name != "__init__":
                out.append({
                    "rule": "unlocked-shared-mutation", "path": rel,
                    "line": line,
                    "message": f"self.{attr} is mutated under "
                               f"{guarded[attr]} elsewhere in {cls} "
                               f"but assigned in {f.qual} on a path "
                               "holding no lock"})
    return out
