"""Runtime lock-order watchdog: the dynamic half of the lock analysis.

Static analysis (analysis/locks.py) proposes the package lock
hierarchy (:data:`~spark_rapids_tpu.analysis.locks.LOCK_HIERARCHY`);
this watchdog verifies it against *reality*: in watchdog-enabled runs
(``RAPIDS_TPU_LOCKWATCH=1`` — tier-1 via tests/conftest.py, cluster
workers via ``cluster._main``) every
``threading.Lock`` / ``RLock`` / ``Condition`` the process creates is
replaced by a recording proxy. Each *blocking* acquisition checks the
calling thread's shadow stack: holding a lock of level N while
block-acquiring one of level <= N is an **inversion** — the dynamic
witness of a potential deadlock the static edge graph may have missed
(locks reached through C extensions, getattr indirection, or code the
resolver could not follow).

No ``threading.settrace`` / ``sys.settrace``: the proxies are plain
objects, so the overhead is one dict-free Python call per acquire and
zero when not installed. Design points:

- Lock identity = creation site (file basename, ``self``'s class if
  constructing inside a method, code name), matched against each
  hierarchy entry's ``runtime`` tuple, most-specific entry first.
  Locks created by stdlib/jax internals match nothing → level None →
  tracked for the held stack but never flagged (and never flag
  others).
- Try-acquires (``blocking=False``) skip the inversion check — they
  cannot complete a hold-and-wait cycle (the ledger's best-effort
  spill protocol depends on this exemption, same as the static rule).
- Re-acquiring a held RLock is reentrant (counted); re-acquiring a
  held non-reentrant Lock on the same thread is recorded as a
  self-deadlock inversion *before* the call would hang.
- ``Condition`` proxies deliberately hide ``_release_save`` /
  ``_acquire_restore`` / ``_is_owned`` so ``wait()`` releases and
  re-acquires through the tracked ``release()``/``acquire()`` path —
  the shadow stack stays truthful across waits.
- Inversions are recorded, not raised: a watchdog must never change
  the program it observes. ``report()`` / ``write_report()`` expose
  them; conftest fails the session on a non-empty list, and
  ``check_obs_output.py --lockwatch`` validates a written report.

Crash caveat: a worker that dies via ``os._exit`` (chaos) loses its
report — the driver-side run still covers the shared-memory paths.
"""
from __future__ import annotations

import json
import os
import sys
import threading
from typing import Dict, List, Optional, Tuple

# NOTE: top-level imports are stdlib-only ON PURPOSE. The watchdog
# must be installable BEFORE the package imports (tests/conftest.py
# bootstraps this file by path and pre-registers it in sys.modules),
# so the module-/class-level singleton locks created DURING package
# import (exchange._SHARED_LOCK_INIT, DeviceMemoryManager._shared_lock,
# the flight-recorder and metrics guards, _JIT_LOCK) are watched too.
# The declared hierarchy is resolved lazily at check time instead.

__all__ = ["install", "uninstall", "installed", "report", "reset",
           "write_report", "env_enabled", "assert_clean",
           "ENV_FLAG", "ENV_OUT"]

ENV_FLAG = "RAPIDS_TPU_LOCKWATCH"
ENV_OUT = "RAPIDS_TPU_LOCKWATCH_OUT"

_real: Dict[str, object] = {}
_tls = threading.local()
_state_lock = threading.Lock()
_inversions: List[Dict] = []
_counts = {"created": 0, "checked": 0, "acquired": 0}
_MAX_INVERSIONS = 200


def env_enabled() -> bool:
    return os.environ.get(ENV_FLAG, "") not in ("", "0", "false")


def _hierarchy():
    """The declared levels (analysis/locks.py), or None while the
    package is still importing — locks created that early resolve
    their level lazily on a later check."""
    try:
        from spark_rapids_tpu.analysis.locks import LOCK_HIERARCHY
    except Exception:  # noqa: BLE001 — mid-package-import bootstrap
        return None
    return LOCK_HIERARCHY


def _creation_site() -> Tuple[str, Optional[str], Optional[str], int]:
    f = sys._getframe(1)
    here = os.path.basename(__file__)
    while f is not None:
        base = os.path.basename(f.f_code.co_filename)
        if base not in (here, "threading.py"):
            break
        f = f.f_back
    if f is None:  # pragma: no cover - interpreter internals
        return "?", None, None, 0
    cls = None
    slf = f.f_locals.get("self")
    if slf is not None:
        cls = type(slf).__name__
    elif isinstance(f.f_locals.get("cls"), type):
        cls = f.f_locals["cls"].__name__
    return (os.path.basename(f.f_code.co_filename), cls,
            f.f_code.co_name, f.f_lineno)


def _stack() -> List:
    s = getattr(_tls, "stack", None)
    if s is None:
        s = _tls.stack = []
    return s


class _WatchedLock:
    """Proxy around a real lock primitive with shadow-stack tracking."""

    def __init__(self, inner, reentrant: bool):
        file, cls, fn, line = _creation_site()
        self._inner = inner
        self._reentrant = reentrant
        self._site_key = (file, cls, fn)
        self._level: Optional[int] = None
        self._label = f"{file}:{cls or ''}:{fn or ''}"
        self._resolved = False
        self._site = f"{file}:{line} in {cls + '.' if cls else ''}{fn}"
        _counts["created"] += 1

    # -- tracking ---------------------------------------------------------

    def _resolve(self):
        """Lazy hierarchy lookup: locks created before the package
        finished importing resolve on their first checked acquire."""
        if self._resolved:
            return
        hierarchy = _hierarchy()
        if hierarchy is None:
            return  # package still importing; retry next check
        file, cls, fn = self._site_key
        for entry in hierarchy:
            efile, ecls, efn = entry.runtime
            if efile != file:
                continue
            if ecls is not None and ecls != cls:
                continue
            if efn is not None and efn != fn:
                continue
            self._level = entry.level
            self._label = entry.pattern
            break
        self._resolved = True

    def _check(self):
        """Record an inversion BEFORE the acquire can block on it."""
        _counts["checked"] += 1
        self._resolve()
        stack = _stack()
        for held, _ in stack:
            held._resolve()
        for held, count in stack:
            if held is self:
                if not self._reentrant:
                    self._record(stack, "self-deadlock: non-reentrant "
                                        "lock re-acquired while held")
                return
        if self._level is None:
            return
        worst = None
        for held, _ in stack:
            if held._level is not None and held._level >= self._level \
                    and held is not self:
                worst = held
        if worst is not None:
            self._record(stack,
                         f"{self._label} (level {self._level}) "
                         f"block-acquired while holding "
                         f"{worst._label} (level {worst._level})")

    def _record(self, stack, why: str):
        caller = sys._getframe(2)
        here = os.path.basename(__file__)
        while caller is not None and os.path.basename(
                caller.f_code.co_filename) == here:
            caller = caller.f_back
        site = "?" if caller is None else (
            f"{os.path.basename(caller.f_code.co_filename)}:"
            f"{caller.f_lineno} in {caller.f_code.co_name}")
        with _state_lock:
            if len(_inversions) < _MAX_INVERSIONS:
                _inversions.append({
                    "thread": threading.current_thread().name,
                    "why": why,
                    "acquiring": self._label,
                    "acquiring_site": site,
                    "held": [f"{h._label}(level={h._level})"
                             for h, _ in stack],
                })

    def _push(self):
        stack = _stack()
        for i, (held, count) in enumerate(stack):
            if held is self:
                stack[i] = (held, count + 1)
                return
        stack.append((self, 1))
        _counts["acquired"] += 1

    def _pop(self):
        stack = _stack()
        for i in range(len(stack) - 1, -1, -1):
            held, count = stack[i]
            if held is self:
                if count > 1:
                    stack[i] = (held, count - 1)
                else:
                    del stack[i]
                return

    # -- lock protocol ----------------------------------------------------

    def acquire(self, blocking: bool = True, timeout: float = -1):
        if blocking:
            self._check()
        got = self._inner.acquire(blocking, timeout) \
            if blocking else self._inner.acquire(False)
        if got:
            self._push()
        return got

    def release(self):
        self._inner.release()
        self._pop()

    def __enter__(self):
        self.acquire()
        return self

    def __exit__(self, *exc):
        self.release()
        return False

    def locked(self):
        fn = getattr(self._inner, "locked", None)
        return fn() if fn is not None else False

    # -- Condition support -------------------------------------------------
    #
    # Implemented HERE (not delegated raw to the inner lock) so that
    # Condition.wait()'s release/re-acquire keeps the shadow stack
    # truthful: the full recursion count is dropped on wait and
    # restored on wake. Delegating would bypass the tracking; hiding
    # them would break RLock-backed conditions (the acquire(False)
    # ownership probe succeeds reentrantly and notify() then refuses).

    def _is_owned(self):
        inner = self._inner
        if hasattr(inner, "_is_owned"):
            return inner._is_owned()
        if inner.acquire(False):  # plain lock: probe the inner directly
            inner.release()
            return False
        return True

    def _release_save(self):
        stack = _stack()
        count = 0
        for i in range(len(stack) - 1, -1, -1):
            if stack[i][0] is self:
                count = stack[i][1]
                del stack[i]
                break
        inner = self._inner
        if hasattr(inner, "_release_save"):
            return (inner._release_save(), count)
        inner.release()
        return (None, count)

    def _acquire_restore(self, saved):
        state, count = saved
        inner = self._inner
        if state is not None and hasattr(inner, "_acquire_restore"):
            inner._acquire_restore(state)
        else:
            inner.acquire()
        if count:
            _stack().append((self, count))

    def __getattr__(self, name):
        return getattr(self._inner, name)


def _lock_factory():
    return _WatchedLock(_real["Lock"](), reentrant=False)


def _rlock_factory():
    return _WatchedLock(_real["RLock"](), reentrant=True)


def _condition_factory(lock=None):
    if lock is None:
        lock = _WatchedLock(_real["RLock"](), reentrant=True)
    return _real["Condition"](lock)


def install() -> None:
    """Replace threading.Lock/RLock/Condition with recording proxies.
    Idempotent; existing lock objects are untouched (only locks created
    AFTER install are watched)."""
    if _real:
        return
    _real["Lock"] = threading.Lock
    _real["RLock"] = threading.RLock
    _real["Condition"] = threading.Condition
    threading.Lock = _lock_factory
    threading.RLock = _rlock_factory
    threading.Condition = _condition_factory


def uninstall() -> None:
    if not _real:
        return
    threading.Lock = _real.pop("Lock")
    threading.RLock = _real.pop("RLock")
    threading.Condition = _real.pop("Condition")


def installed() -> bool:
    return bool(_real)


def reset() -> None:
    with _state_lock:
        _inversions.clear()
    _counts.update(created=0, checked=0, acquired=0)


def report() -> Dict:
    with _state_lock:
        inv = list(_inversions)
    return {"installed": installed(), "counts": dict(_counts),
            "inversions": inv}


def assert_clean() -> None:
    rep = report()
    if rep["inversions"]:
        lines = [f"- {i['why']} at {i['acquiring_site']} "
                 f"(held: {i['held']})" for i in rep["inversions"]]
        raise AssertionError(
            f"lock-order watchdog recorded "
            f"{len(rep['inversions'])} inversion(s):\n"
            + "\n".join(lines))


def write_report(path: Optional[str] = None) -> Optional[str]:
    """Dump the report JSON to `path` (default: $RAPIDS_TPU_LOCKWATCH_OUT;
    no-op when neither is set). Returns the path written."""
    path = path or os.environ.get(ENV_OUT)
    if not path:
        return None
    doc = report()
    doc["pid"] = os.getpid()
    tmp = f"{path}.tmp{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=2)
    os.replace(tmp, path)
    return path
