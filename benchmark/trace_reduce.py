"""From a profiler trace (``.xplane.pb``) to the numbers the benchmark
prints: device-busy seconds, the traced window, the device operations
that took most time and the longest idle gaps by what the host was doing.

Reads the file with nothing but JAX (``jax.profiler.ProfileData``).
A TPU trace has one plane per chip, ``/device:TPU:<n>``, whose line
``XLA Ops`` holds one event per executed HLO operation (start and
duration in nanoseconds; the event's name is the operation's whole HLO
line) and whose line ``XLA Modules`` holds one event per program run;
host threads are lines of ``/host:CPU`` and the harness's own
``jax.profiler.TraceAnnotation`` spans land there. An operation is named
``<XLA module>/<HLO op name>`` (``jit_build/while.42``): no shapes, no
fingerprint, so two operations never share a name and a name survives
an edit that leaves the operation in place. Checked on a hand-written
trace and on a small trace recorded on a TPU v5e by
``tests/benchmark_harness/test_bench_trace_reduce.py``.
"""
from __future__ import annotations

import glob
import os
import re

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
TOP = 10


def find_xplane(trace_dir: str) -> str | None:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def _events(line):
    names, starts, durs = [], [], []
    for e in line.events:
        names.append(e.name)
        starts.append(e.start_ns)
        durs.append(e.duration_ns)
    return names, np.asarray(starts, float), np.asarray(durs, float)


def op_name(hlo_line: str) -> str:
    """``%while.42 = (u32[]{:T(128)}, ...) while(...)`` -> ``while.42``."""
    return hlo_line.split(" = ", 1)[0].strip().lstrip("%")


def module_name(event_name: str) -> str:
    """``jit_build(9386983176490101500)`` -> ``jit_build``."""
    return re.sub(r"\(\d+\)$", "", event_name.strip())


def _modules_of(plane, starts):
    """The name of the program run that holds each operation's start
    (``?`` where the trace shows none)."""
    lines = [ln for ln in plane.lines if ln.name == MODULES_LINE]
    out = np.full(len(starts), "?", object)
    if not lines:
        return out
    names, m_starts, m_durs = _events(lines[0])
    if len(m_starts) == 0:
        return out
    order = np.argsort(m_starts, kind="stable")
    m_starts, m_ends = m_starts[order], (m_starts + m_durs)[order]
    names = [module_name(names[i]) for i in order]
    # half a microsecond of slack: both lines round picoseconds apart
    at = np.searchsorted(m_starts, starts + 500.0, side="right") - 1
    inside = (at >= 0) & (starts <= m_ends[np.maximum(at, 0)] + 500.0)
    for i in np.flatnonzero(inside):
        out[i] = names[at[i]]
    return out


def _union(starts, ends):
    """Merged ``(starts, ends)`` of possibly overlapping intervals."""
    if len(starts) == 0:
        return starts, ends
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], np.maximum.accumulate(ends[order])
    new = np.ones(len(s), bool)
    new[1:] = s[1:] > e[:-1]
    first = np.flatnonzero(new)
    last = np.append(first[1:] - 1, len(s) - 1)
    return s[first], e[last]


def reduce_profile(profile, spans=("plan", "collect")) -> dict | None:
    """``None`` when the trace holds no device plane with an operation
    (a CPU run): the caller then prints no device metric."""
    host = [(ln.name,) + _events(ln) for pl in profile.planes
            if pl.name == HOST_PLANE for ln in pl.lines]
    # the traced window: first start to last end of the harness's spans
    w0 = w1 = None
    mine = []
    for _, names, starts, durs in host:
        for n, s, d in zip(names, starts, durs):
            if n in spans:
                mine.append((n, s, s + d))
    if mine:
        w0 = min(s for _, s, _ in mine)
        w1 = max(e for _, _, e in mine)
    busy, op_seconds, gaps_by_label = [], {}, {}
    for pl in profile.planes:
        if not DEVICE_PLANE.match(pl.name):
            continue
        lines = [ln for ln in pl.lines if ln.name == OPS_LINE]
        if not lines:
            continue
        names, starts, durs = _events(lines[0])
        if len(starts) == 0:
            continue
        names = [f"{m}/{op_name(n)}"
                 for m, n in zip(_modules_of(pl, starts), names)]
        if w0 is None:
            w0, w1 = float(starts.min()), float((starts + durs).max())
        cs = np.clip(starts, w0, w1)
        ce = np.clip(starts + durs, w0, w1)
        keep = ce > cs
        for n, d in zip(np.asarray(names, object)[keep], (ce - cs)[keep]):
            op_seconds[n] = op_seconds.get(n, 0.0) + d / 1e9
        us, ue = _union(cs[keep], ce[keep])
        busy.append(float((ue - us).sum()) / 1e9)
        # idle gaps on this chip, the window's head and tail included
        gs = np.concatenate(([w0], ue))
        ge = np.concatenate((us, [w1]))
        longest = np.argsort(ge - gs)[::-1][:200]
        for i in longest:
            if ge[i] <= gs[i]:
                continue
            label = _host_label(host, mine, (gs[i] + ge[i]) / 2.0)
            gaps_by_label[label] = gaps_by_label.get(label, 0.0) \
                + (ge[i] - gs[i]) / 1e9
    if not busy or w1 is None or w1 <= w0:
        return None
    top = lambda d: [[k, v] for k, v in sorted(  # noqa: E731
        d.items(), key=lambda kv: -kv[1])[:TOP]]
    return {"busy_s": float(np.mean(busy)), "window_s": (w1 - w0) / 1e9,
            "chips": len(busy), "device_ops": top(op_seconds),
            "idle_gaps": top(gaps_by_label)}


def _host_label(host, mine, t) -> str:
    """What the host was doing at ``t``: the harness's span that covers
    it, then the shortest host event on any thread that covers it."""
    span = next((n for n, s, e in mine if s <= t <= e), "outside")
    best, best_d = None, None
    for _, names, starts, durs in host:
        if len(starts) == 0:
            continue
        hit = np.flatnonzero((starts <= t) & (starts + durs >= t)
                             & (durs > 0))
        for i in hit:
            if any(names[i] == n for n, _, _ in mine):
                continue
            if best_d is None or durs[i] < best_d:
                best, best_d = names[i], durs[i]
    return f"{span}: {best}" if best else span


def reduce_trace(trace_dir: str, spans=("plan", "collect")) -> dict | None:
    from jax.profiler import ProfileData
    path = find_xplane(trace_dir)
    if path is None:
        return None
    return reduce_profile(ProfileData.from_file(path), spans)
