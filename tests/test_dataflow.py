"""tpu-lint 2.0 dataflow engine: CFG construction specimens, worklist
convergence, call-graph summary propagation, per-analysis seeded-defect
fixtures, and the runtime lock-order watchdog (ISSUE 10)."""
import ast
import os

import pytest

from spark_rapids_tpu.analysis.dataflow import (Analysis, CFG,
                                                BranchTest, LoopIter,
                                                Project, WithEnter,
                                                WithExit, solve)
from spark_rapids_tpu.analysis import lockwatch
from spark_rapids_tpu.analysis.jit_taint import analyze_jit_taint
from spark_rapids_tpu.analysis.ledger import analyze_ledger
from spark_rapids_tpu.analysis.locks import (LOCK_HIERARCHY,
                                             analyze_locks,
                                             collect_locks, lock_graph,
                                             lock_level)


def _cfg(src):
    return CFG(ast.parse(src).body[0])


def _project(src, name="mod.py"):
    return Project([(os.path.join("/tmp/dfproj", name),
                     ast.parse(src))])


def _rules(findings):
    return sorted({f["rule"] for f in findings})


# --- CFG construction specimens ---------------------------------------------


class _Trace(Analysis):
    """Records which statement kinds flow to which exits — enough to
    assert structural properties without a real lattice."""

    def initial(self):
        return frozenset()

    def join(self, a, b):
        return a | b

    def transfer(self, stmt, fact):
        if isinstance(stmt, WithExit):
            return fact | {("exit", stmt.lineno)}
        if isinstance(stmt, (WithEnter, LoopIter, BranchTest)):
            return fact
        node = getattr(stmt, "node", stmt)
        if isinstance(node, ast.Expr) and isinstance(node.value,
                                                     ast.Call):
            names = [n.id for n in ast.walk(node.value)
                     if isinstance(n, ast.Name)]
            return fact | {("call", names[0] if names else "?")}
        return fact


def test_cfg_try_finally_runs_on_all_exits():
    cfg = _cfg(
        "def f(cond):\n"
        "    try:\n"
        "        if cond:\n"
        "            return 1\n"
        "        work()\n"
        "    finally:\n"
        "        cleanup()\n"
        "    return 2\n")
    facts = solve(cfg, _Trace())
    # cleanup() reaches the normal exit (early return AND fallthrough)
    assert ("call", "cleanup") in facts[cfg.exit]
    # and the exceptional exit (work() raising)
    assert ("call", "cleanup") in facts[cfg.raise_exit]


def test_cfg_with_exit_on_exception_edge():
    cfg = _cfg(
        "def f(lock):\n"
        "    with lock:\n"
        "        work()\n")
    facts = solve(cfg, _Trace())
    # __exit__ runs before the exception propagates out
    assert any(k == "exit" for k, _ in facts[cfg.raise_exit])
    assert any(k == "exit" for k, _ in facts[cfg.exit])


def test_cfg_break_unwinds_with():
    cfg = _cfg(
        "def f(lock, items):\n"
        "    for x in items:\n"
        "        with lock:\n"
        "            if x:\n"
        "                break\n"
        "    return 0\n")
    facts = solve(cfg, _Trace())
    # the break path still ran the with-exit before leaving the loop
    assert any(k == "exit" for k, _ in facts[cfg.exit])


def test_cfg_nested_loops_and_unreachable_code():
    cfg = _cfg(
        "def f(rows):\n"
        "    total = 0\n"
        "    for r in rows:\n"
        "        for c in r:\n"
        "            if c:\n"
        "                continue\n"
        "            total += 1\n"
        "    return total\n")
    facts = solve(cfg, _Trace())
    assert cfg.exit in facts  # converged, exit reachable


def test_solver_converges_on_loop():
    """A genuinely growing fact across a back edge must reach a
    fixpoint, not oscillate."""

    class Accum(Analysis):
        def initial(self):
            return frozenset()

        def join(self, a, b):
            return a | b

        def transfer(self, stmt, fact):
            node = getattr(stmt, "node", stmt)
            if isinstance(node, ast.Assign) \
                    and isinstance(node.targets[0], ast.Name):
                return fact | {node.targets[0].id}
            return fact

    cfg = _cfg(
        "def f(n):\n"
        "    i = 0\n"
        "    while i < n:\n"
        "        a = work()\n"
        "        b = work()\n"
        "        i = i + 1\n"
        "    return i\n")
    facts = solve(cfg, Accum())
    assert {"i", "a", "b"} <= facts[cfg.exit]


# --- call-graph summaries ----------------------------------------------------


def test_lock_summary_flows_through_helper_calls():
    """A lock acquired two helpers deep creates an order edge from the
    caller's held lock — the one-level summary pass at fixpoint."""
    src = (
        "import threading\n"
        "class W:\n"
        "    def __init__(self):\n"
        "        self._outer = threading.Lock()\n"
        "        self._inner = threading.Lock()\n"
        "    def deep(self):\n"
        "        with self._inner:\n"
        "            pass\n"
        "    def mid(self):\n"
        "        self.deep()\n"
        "    def top(self):\n"
        "        with self._outer:\n"
        "            self.mid()\n")
    g = lock_graph(_project(src))
    edges = {(e["from"], e["to"]) for e in g["edges"]}
    assert ("W._outer", "W._inner") in edges
    assert g["cycles"] == []


def test_allocator_summary_two_levels():
    """register() behind two helper returns still creates an
    obligation at the outer call site."""
    src = (
        "def build(mm, b):\n"
        "    sb = mm.register(b)\n"
        "    return sb\n"
        "def acquire(mm, b):\n"
        "    return build(mm, b)\n"
        "def use(mm, b, risky):\n"
        "    sb = acquire(mm, b)\n"
        "    risky()\n")  # never released, never escapes
    out = analyze_ledger(_project(src))
    # flagged on the normal AND the exception exit
    assert _rules(out) == ["ledger-leak-path"] and len(out) == 2
    assert all("use" in f["message"] for f in out)


# --- seeded-defect fixtures per analysis -------------------------------------


def test_seeded_lock_order_cycle():
    src = (
        "import threading\n"
        "class A:\n"
        "    def __init__(self):\n"
        "        self._alock = threading.Lock()\n"
        "        self._block = threading.Lock()\n"
        "    def ab(self):\n"
        "        with self._alock:\n"
        "            with self._block:\n"
        "                pass\n"
        "    def ba(self):\n"
        "        with self._block:\n"
        "            with self._alock:\n"
        "                pass\n")
    out = analyze_locks(_project(src))
    assert _rules(out) == ["lock-order-cycle"]
    assert "A._alock" in out[0]["message"] \
        and "A._block" in out[0]["message"]
    # consistent order in both methods: no cycle
    clean = src.replace(
        "with self._block:\n            with self._alock:",
        "with self._alock:\n            with self._block:")
    assert analyze_locks(_project(clean)) == []


def test_seeded_lock_order_inversion_against_hierarchy():
    """Class/attr names matching the declared hierarchy patterns are
    checked against it even without a cycle."""
    src = (
        "import threading\n"
        "class DeviceMemoryManager:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.RLock()\n"
        "class SpillableBatch:\n"
        "    def __init__(self, mgr: 'DeviceMemoryManager'):\n"
        "        self._state_lock = threading.RLock()\n"
        "        self._mgr = mgr\n"
        "    def bad(self):\n"
        "        with self._mgr._lock:\n"
        "            with self._state_lock:\n"
        "                pass\n")
    out = analyze_locks(_project(src))
    assert "lock-order-inversion" in _rules(out)
    inv = [f for f in out if f["rule"] == "lock-order-inversion"][0]
    assert "level 50" in inv["message"] and "level 40" in inv["message"]


def test_seeded_blocking_under_lock_direct_and_via_helper():
    src = (
        "import threading, time\n"
        "class W:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "    def direct(self, fut):\n"
        "        with self._lock:\n"
        "            fut.result()\n"
        "    def slow(self):\n"
        "        time.sleep(1)\n"
        "    def indirect(self):\n"
        "        with self._lock:\n"
        "            self.slow()\n")
    out = analyze_locks(_project(src))
    blocking = [f for f in out if f["rule"] == "blocking-under-lock"]
    assert len(blocking) == 2
    assert any("via W.slow" in f["message"] for f in blocking)
    # a try-acquired lock does not make the same calls findings-free —
    # but bounded calls do
    clean = src.replace("fut.result()", "fut.result(timeout=5)") \
               .replace("time.sleep(1)", "pass")
    assert [f for f in analyze_locks(_project(clean))
            if f["rule"] == "blocking-under-lock"] == []


def test_seeded_condition_wait_on_own_lock_is_exempt():
    src = (
        "import threading\n"
        "class W:\n"
        "    def __init__(self):\n"
        "        self._cv = threading.Condition()\n"
        "    def waiter(self):\n"
        "        with self._cv:\n"
        "            while True:\n"
        "                self._cv.wait()\n")
    assert [f for f in analyze_locks(_project(src))
            if f["rule"] == "blocking-under-lock"] == []


def test_seeded_unlocked_mutation_acquire_style_augassign():
    """The PR 6 rule's false negative: acquire()/release() critical
    sections guarded nothing, so `self.x += 1` outside was invisible.
    The dataflow port sees lock-held-ness as a fact."""
    src = (
        "import threading\n"
        "class Store:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self.x = 0\n"
        "    def f(self):\n"
        "        self._lock.acquire()\n"
        "        self.x += 1\n"
        "        self._lock.release()\n"
        "    def g(self):\n"
        "        self.x += 1\n")
    out = analyze_locks(_project(src))
    muts = [f for f in out if f["rule"] == "unlocked-shared-mutation"]
    assert [f["line"] for f in muts] == [11]
    # mutation after an early release() on the same path is caught too
    src2 = src.replace(
        "    def g(self):\n        self.x += 1\n",
        "")
    src2 += "    def h(self):\n" \
            "        self._lock.acquire()\n" \
            "        self._lock.release()\n" \
            "        self.x += 1\n"
    out2 = analyze_locks(_project(src2))
    assert [f["rule"] for f in out2] == ["unlocked-shared-mutation"]


def test_seeded_ledger_leak_and_fixed_variant():
    leaky = (
        "def f(mm, items, risky):\n"
        "    sbs = []\n"
        "    for b in items:\n"
        "        sbs.append(mm.register(b))\n"
        "    risky()\n"
        "    for sb in sbs:\n"
        "        sb.release()\n")
    out = analyze_ledger(_project(leaky))
    assert _rules(out) == ["ledger-leak-path"]
    assert "exception path" in out[0]["message"]
    fixed = (
        "def f(mm, items, risky):\n"
        "    sbs = []\n"
        "    try:\n"
        "        for b in items:\n"
        "            sbs.append(mm.register(b))\n"
        "        risky()\n"
        "    except BaseException:\n"
        "        for sb in sbs:\n"
        "            sb.release()\n"
        "        raise\n"
        "    for sb in sbs:\n"
        "        sb.release()\n")
    assert analyze_ledger(_project(fixed)) == []


def test_seeded_ledger_comprehension_and_discard():
    src = (
        "def f(mm, batches):\n"
        "    sbs = [mm.register(b) for b in batches]\n"
        "    for sb in sbs:\n"
        "        sb.release()\n"
        "def g(mm, b):\n"
        "    mm.register(b)\n")
    out = analyze_ledger(_project(src))
    msgs = sorted(f["message"][:20] for f in out)
    assert len(out) == 2
    assert any("comprehension" in f["message"] for f in out)
    assert any("discarded" in f["message"] for f in out), msgs


def test_seeded_ledger_ownership_transfers_are_clean():
    src = (
        "def ret(mm, b):\n"
        "    sb = mm.register(b)\n"
        "    return sb\n"
        "class H:\n"
        "    def store(self, mm, b):\n"
        "        self._sb = mm.register(b)\n"
        "def closure(mm, b):\n"
        "    sb = mm.register(b)\n"
        "    def replay():\n"
        "        sb.release()\n"
        "    return replay\n"
        "def handoff(mm, b, inflight):\n"
        "    sb = mm.register(b)\n"
        "    inflight.add(sb)\n")
    assert analyze_ledger(_project(src)) == []


def test_seeded_transient_reservation_forms():
    src = (
        "def good(mm, n):\n"
        "    with mm.transient_reservation(n):\n"
        "        work()\n"
        "def assigned(mm, n):\n"
        "    charge = mm.transient_reservation(n)\n"
        "    with charge:\n"
        "        work()\n"
        "def bad(mm, n):\n"
        "    mm.transient_reservation(n)\n"
        "    work()\n")
    out = analyze_ledger(_project(src))
    assert len(out) == 1
    assert "never releases" in out[0]["message"]


def test_seeded_jit_taint_interprocedural():
    src = (
        "import jax\n"
        "import numpy as np\n"
        "def helper2(x):\n"
        "    return np.asarray(x)\n"
        "def helper(x):\n"
        "    return helper2(x) + 1\n"
        "@jax.jit\n"
        "def kernel(x):\n"
        "    return helper(x)\n"
        "def host_only(x):\n"
        "    return np.asarray(x)\n")  # unreachable from jit: clean
    out = analyze_jit_taint(_project(src))
    assert [f["line"] for f in out] == [4]
    assert "kernel -> helper -> helper2" in out[0]["message"]


def test_seeded_jit_taint_method_and_module_forms():
    src = (
        "import jax\n"
        "class K:\n"
        "    def run(self, b):\n"
        "        self._jit = jax.jit(self._impl)\n"
        "        return self._jit(b)\n"
        "    def _impl(self, b):\n"
        "        return b.item()\n"
        "def decode(blob):\n"
        "    return blob.block_until_ready()\n"
        "fn = jax.jit(decode)\n")
    out = analyze_jit_taint(_project(src))
    assert sorted(f["line"] for f in out) == [7, 9]


# --- package-wide invariants -------------------------------------------------


@pytest.fixture(scope="module")
def package_project():
    from spark_rapids_tpu.analysis.lint import (_iter_py_files,
                                                package_dir)
    pkg = package_dir()
    parsed = []
    for p in _iter_py_files([pkg]):
        try:
            parsed.append((p, ast.parse(open(p).read())))
        except SyntaxError:
            continue
    return Project(parsed, root=pkg)


def test_package_lock_graph_has_no_cycles_and_all_levels_declared(
        package_project):
    """The acceptance gate: the package lock graph is cycle-free, every
    edge ascends the declared hierarchy, and every lock the registry
    finds maps to a declared level (no unexplained locks)."""
    g = lock_graph(package_project)
    assert g["cycles"] == []
    unleveled = [lid for lid, meta in g["locks"].items()
                 if meta["level"] is None]
    assert unleveled == [], unleveled
    for e in g["edges"]:
        la, lb = lock_level(e["from"]), lock_level(e["to"])
        assert la is not None and lb is not None
        assert la <= lb, e


def test_package_lock_registry_matches_known_locks(package_project):
    reg = collect_locks(package_project)
    for expected in ("DeviceMemoryManager._lock",
                     "SpillableBatch._state_lock",
                     "HostShuffleTransport._lock",
                     "_WeightedWindow._cv"):
        assert expected in reg, sorted(reg)


# --- runtime lock-order watchdog ---------------------------------------------


@pytest.mark.skipif(not lockwatch.env_enabled(),
                    reason="needs RAPIDS_TPU_LOCKWATCH=1 (conftest "
                           "bootstrap)")
def test_import_time_singleton_locks_are_watched():
    """The conftest bootstrap installs the watchdog BEFORE the package
    imports, so module-level singleton locks created at import time
    (flight recorder, metrics guards) are watched proxies that resolve
    their declared hierarchy level lazily."""
    assert lockwatch.installed()
    from spark_rapids_tpu.obs import metrics
    from spark_rapids_tpu.obs.recorder import RECORDER
    for lk, want in ((RECORDER._lock, 70),
                     (metrics._update_lock, 85)):
        assert type(lk).__name__ == "_WatchedLock", type(lk)
        with lk:
            pass
        lk._resolve()
        assert lk._level == want, (lk._label, lk._level)


@pytest.fixture
def watchdog():
    """Install (if not already via RAPIDS_TPU_LOCKWATCH), snapshot the
    inversion count, and restore state afterwards."""
    was_installed = lockwatch.installed()
    if not was_installed:
        lockwatch.install()
    before = len(lockwatch.report()["inversions"])
    yield lockwatch
    # drop only what this test added, keep session-level evidence
    with lockwatch._state_lock:
        del lockwatch._inversions[before:]
    if not was_installed:
        lockwatch.uninstall()


def _mem_pair():
    from spark_rapids_tpu.config import RapidsConf
    from spark_rapids_tpu.memory import (DeviceMemoryManager,
                                         SpillableBatch)

    class FakeBatch:
        schema = None

        def device_size_bytes(self):
            return 128

    mgr = DeviceMemoryManager(RapidsConf(
        {"spark.rapids.memory.device.budgetBytes": str(1 << 30)}))
    return mgr, SpillableBatch(mgr, FakeBatch())


def test_watchdog_levels_and_inversion(watchdog):
    mgr, sb = _mem_pair()
    # hierarchy levels resolve lazily (locks can be created before the
    # package finishes importing under the conftest bootstrap)
    mgr._lock._resolve()
    sb._state_lock._resolve()
    assert mgr._lock._level == 50
    assert sb._state_lock._level == 40
    base = len(watchdog.report()["inversions"])
    with sb._state_lock:      # 40 then 50: declared order
        with mgr._lock:
            pass
    assert len(watchdog.report()["inversions"]) == base
    with mgr._lock:           # 50 then 40: inversion
        with sb._state_lock:
            pass
    rep = watchdog.report()
    assert len(rep["inversions"]) == base + 1
    inv = rep["inversions"][-1]
    assert "SpillableBatch._state_lock" in inv["why"]
    assert any("DeviceMemoryManager._lock" in h for h in inv["held"])


def test_watchdog_try_acquire_and_reentrancy_exempt(watchdog):
    mgr, sb = _mem_pair()
    base = len(watchdog.report()["inversions"])
    with mgr._lock:
        got = sb._state_lock.acquire(blocking=False)  # try: exempt
        if got:
            sb._state_lock.release()
        with mgr._lock:  # RLock reentrancy: exempt
            pass
    assert len(watchdog.report()["inversions"]) == base


def test_watchdog_self_deadlock_on_plain_lock(watchdog):
    import threading
    lk = threading.Lock()  # watched (factory is patched)
    base = len(watchdog.report()["inversions"])
    lk.acquire()
    try:
        got = lk.acquire(blocking=False)  # try-acquire: no record
        assert not got
        assert len(watchdog.report()["inversions"]) == base
        # a BLOCKING re-acquire would hang: the check records the
        # self-deadlock BEFORE blocking, so probe via a short timeout
        got = lk.acquire(True, 0.01)
        assert not got
    finally:
        lk.release()
    rep = watchdog.report()
    assert len(rep["inversions"]) == base + 1
    assert "self-deadlock" in rep["inversions"][-1]["why"]


def test_watchdog_condition_machinery_stays_healthy(watchdog):
    import queue
    import threading as th
    q = queue.Queue(maxsize=1)

    def worker():
        for i in range(50):
            q.put(i)

    t = th.Thread(target=worker)
    t.start()
    got = [q.get(timeout=5) for _ in range(50)]
    t.join(5)
    assert got == list(range(50))
    from spark_rapids_tpu.pipeline import pipelined_map
    assert list(pipelined_map(lambda x: x * 2, range(8), threads=2,
                              window=2, weigher=lambda x: 1,
                              max_weight=2)) == [0, 2, 4, 6, 8, 10,
                                                 12, 14]


def test_watchdog_report_of_a_scan_passes_the_checker_until_an_inversion(
        watchdog, tmp_path):
    """A parquet query through the scan's threads, the upload tunnel
    and the memory manager, run under the watchdog: the written report
    is one ``check_obs_output.py --lockwatch`` accepts (installed,
    acquisitions checked, zero inversions); a seeded inversion is one
    it refuses by name."""
    import pyarrow as pa
    import pyarrow.parquet as pq
    from asserts import obs_checker
    from spark_rapids_tpu.session import TpuSession
    data = str(tmp_path / "t.parquet")
    pq.write_table(pa.table({"k": [i % 7 for i in range(4096)],
                             "v": list(range(4096))}),
                   data, row_group_size=512)
    s = TpuSession()
    s.register_table("t", s.read_parquet(data))
    base = len(watchdog.report()["inversions"])
    got = s.sql("SELECT k, SUM(v) AS sv FROM t GROUP BY k").collect()
    assert got.num_rows == 7
    assert len(watchdog.report()["inversions"]) == base
    path = watchdog.write_report(str(tmp_path / "lw.json"))
    if base == 0:
        assert obs_checker().check_lockwatch(path) == []
    mgr, sb = _mem_pair()
    with mgr._lock:
        with sb._state_lock:
            pass
    errors = obs_checker().check_lockwatch(
        watchdog.write_report(str(tmp_path / "lw2.json")))
    assert any("INVERSION" in e and "SpillableBatch._state_lock" in e
               for e in errors), errors


def test_watchdog_report_and_assert_clean(watchdog, tmp_path):
    mgr, sb = _mem_pair()
    path = str(tmp_path / "lw.json")
    out = watchdog.write_report(path)
    assert out == path
    import json
    doc = json.load(open(path))
    assert doc["installed"] is True
    assert doc["counts"]["checked"] >= 0
    base = len(watchdog.report()["inversions"])
    if base == 0:
        watchdog.assert_clean()
    with mgr._lock:
        with sb._state_lock:
            pass
    with pytest.raises(AssertionError):
        watchdog.assert_clean()
