"""The program's own spans laid over the chip's idle time.

The engine writes every span of its tracer to the profiler's host plane
as well (``spark_rapids_tpu/obs/tracer.py``): events named ``spark:...``
on the thread that did the work, on the clock of the device operations.
This file reads them from the run's own trace — the newest
``.xplane.pb`` under ``.bench_cache/benchmark/trace/*/``, where
``run.py`` puts it after clearing the cell's directory — and gives, over
the traced window (the harness's ``plan`` / ``collect`` spans, as
``trace_reduce.reduce_profile`` takes it), per span name: how many, their
summed seconds, the sum of each numeric argument, and the **idle seconds
it owns**.

Ownership: every instant inside the window at which no operation runs on
the chip is shared equally among the distinct names of the WORKING spans
open at it on any thread. The spans that contain or wait (WAITING) own an
instant only where no working span is open — else the consumer's wait,
which starts later than the feeder's work it waits for, would take every
gap — and then the most specific of them takes it whole (the wait before
the operator before the query). What no span of the program covers is
``UNOWNED``. So the owned seconds of all names and ``UNOWNED`` add up to
the idle seconds of the window, and nothing is counted twice. With
several chips each chip's idle time is shared out on its own and the
chips are averaged, as ``reduce_profile`` averages their busy seconds.

Imports ``trace_reduce``'s helpers and nothing of the engine. A trace
with no device operation, or with no ``spark:`` event (a program from
before the spans existed), gives ``None`` and every reader of it leaves
its metric out. Checked on a hand-written trace and on one recorded on a
TPU v5e by ``tests/benchmark_harness/test_bench_span_reduce.py``.
"""
from __future__ import annotations

import glob
import os

import numpy as np

from trace_reduce import (DEVICE_PLANE, HOST_PLANE, OPS_LINE, _events,
                          _union)

PREFIX = "spark:"
#: spans inside which the host does the query's work
WORKING = ("spark:scan.read", "spark:scan.assemble",
           "spark:scan.arena_wait", "spark:scan.upload",
           "spark:scan.dispatch", "spark:admit", "spark:download",
           "spark:finish")
#: spans that contain or wait for the work, most specific first
WAITING = ("spark:scan.wait", "spark:op", "spark:query")
#: the scan's host stages, for ``host_overlap``
SCAN_STAGES = ("spark:scan.read", "spark:scan.assemble",
               "spark:scan.upload", "spark:scan.dispatch")
UNOWNED = "(no span)"
HARNESS_SPANS = ("plan", "collect")

TRACE_ROOT = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), ".bench_cache", "benchmark", "trace")
_MEMO: dict = {}


def newest_xplane(root: str | None = None) -> str | None:
    found = glob.glob(os.path.join(root or TRACE_ROOT, "*", "plugins",
                                   "profile", "*", "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


class _Busy:
    """One chip's merged busy intervals inside the window, and the idle
    seconds of any stretch of it."""

    def __init__(self, starts, ends, w0, w1):
        cs, ce = np.clip(starts, w0, w1), np.clip(ends, w0, w1)
        keep = ce > cs
        self.us, self.ue = _union(cs[keep], ce[keep])
        self.cum = np.concatenate(([0.0], np.cumsum(self.ue - self.us)))

    def busy_before(self, t):
        """Busy nanoseconds of the window before each ``t``."""
        t = np.asarray(t, float)
        if not len(self.us):
            return np.zeros_like(t)
        i = np.searchsorted(self.us, t, side="right")
        last = np.maximum(i - 1, 0)
        beyond = np.where(i > 0, np.maximum(self.ue[last] - t, 0.0), 0.0)
        return self.cum[i] - beyond

    def idle(self, a, b):
        """Idle nanoseconds of each ``[a, b]``."""
        a, b = np.asarray(a, float), np.asarray(b, float)
        return (b - a) - (self.busy_before(b) - self.busy_before(a))


def _program_spans(profile):
    """Every ``spark:`` event of every host line: parallel lists of
    name, start, end (ns), numeric arguments, and the line's name."""
    names, starts, ends, args, threads = [], [], [], [], []
    for pl in profile.planes:
        if pl.name != HOST_PLANE:
            continue
        for ln in pl.lines:
            for e in ln.events:
                if not e.name.startswith(PREFIX):
                    continue
                names.append(e.name)
                starts.append(e.start_ns)
                ends.append(e.start_ns + e.duration_ns)
                args.append({k: v for k, v in e.stats
                             if isinstance(v, (int, float))
                             and not isinstance(v, bool)})
                threads.append(ln.name)
    return (names, np.asarray(starts, float), np.asarray(ends, float),
            args, threads)


def _window(profile):
    lo = hi = None
    for pl in profile.planes:
        if pl.name != HOST_PLANE:
            continue
        for ln in pl.lines:
            for n, s, d in zip(*_events(ln)):
                if n in HARNESS_SPANS:
                    lo = s if lo is None else min(lo, s)
                    hi = s + d if hi is None else max(hi, s + d)
    return lo, hi


def _owners(open_names):
    """Who owns an idle instant at which these names are open."""
    working = [n for n in WORKING if n in open_names]
    if working:
        return working
    for n in WAITING:
        if n in open_names:
            return [n]
    return [UNOWNED]


def reduce_spans(profile) -> dict | None:
    chips = []
    for pl in profile.planes:
        if not DEVICE_PLANE.match(pl.name):
            continue
        for ln in pl.lines:
            if ln.name == OPS_LINE:
                _, s, d = _events(ln)
                if len(s):
                    chips.append((s, s + d))
    names, starts, ends, args, threads = _program_spans(profile)
    if not chips or not names:
        return None
    w0, w1 = _window(profile)
    if w0 is None:
        w0 = min(float(s.min()) for s, _ in chips)
        w1 = max(float(e.max()) for _, e in chips)
    if w1 <= w0:
        return None
    cs, ce = np.clip(starts, w0, w1), np.clip(ends, w0, w1)
    inside = ce > cs
    spans = {}
    for i in np.flatnonzero(inside):
        rec = spans.setdefault(names[i], {
            "count": 0, "seconds": 0.0, "idle_owned_s": 0.0, "args": {},
            "threads": set()})
        rec["count"] += 1
        rec["seconds"] += (ce[i] - cs[i]) / 1e9
        rec["threads"].add(threads[i])
        for k, v in args[i].items():
            rec["args"][k] = rec["args"].get(k, 0) + v
    busy = [_Busy(s, e, w0, w1) for s, e in chips]
    # between two neighbouring span boundaries the set of open spans is
    # constant: share that stretch's idle time out among its owners
    names_in = np.asarray(names, object)[inside]
    s_in, e_in = cs[inside], ce[inside]
    edges = np.unique(np.concatenate(([w0, w1], s_in, e_in)))
    owned = {}
    for a, b in zip(edges[:-1], edges[1:]):
        mid = (a + b) / 2.0
        open_names = set(names_in[(s_in <= mid) & (e_in > mid)])
        idle_ns = float(np.mean([c.idle(a, b) for c in busy]))
        if idle_ns <= 0:
            continue
        owners = _owners(open_names)
        for n in owners:
            owned[n] = owned.get(n, 0.0) + idle_ns / len(owners) / 1e9
    for n, v in owned.items():
        if n != UNOWNED:
            spans[n]["idle_owned_s"] = v
    for rec in spans.values():
        rec["threads"] = sorted(rec["threads"])
    # the scan's host stages, any thread: how much of them ran while
    # the chip was busy
    stage = np.isin(names_in, SCAN_STAGES)
    us, ue = _union(s_in[stage], e_in[stage])
    union_ns = float((ue - us).sum())
    under_busy_ns = union_ns - float(np.mean(
        [c.idle(us, ue).sum() for c in busy])) if len(us) else 0.0
    busy_s = float(np.mean([c.cum[-1] for c in busy])) / 1e9
    window_s = (w1 - w0) / 1e9
    return {"window_s": window_s, "busy_s": busy_s,
            "idle_s": window_s - busy_s, "spans": spans,
            "idle_unowned_s": owned.get(UNOWNED, 0.0),
            "idle_by_working_s": sum(owned.get(n, 0.0) for n in WORKING),
            "scan_host": {"union_s": union_ns / 1e9,
                          "under_busy_s": under_busy_ns / 1e9}}


def spans_of(reading) -> dict | None:
    """The reduction of this run's trace, or ``None`` where the harness
    found no device operation in it (a CPU rehearsal) or the program
    wrote no span. Parsed once per process."""
    if not reading.get("trace"):
        return None
    path = newest_xplane()
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _MEMO:
        from jax.profiler import ProfileData
        _MEMO.clear()
        _MEMO[key] = reduce_spans(ProfileData.from_file(path))
    return _MEMO[key]


def idle_owned_s(reading, name: str):
    """Idle seconds per traced query owned by the span ``name``
    (0 where the trace has the program's spans but none of this name)."""
    r = spans_of(reading)
    if r is None:
        return None
    return r["spans"].get(name, {}).get("idle_owned_s", 0.0)
