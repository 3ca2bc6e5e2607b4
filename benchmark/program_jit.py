"""The host's re-jit inside a traced query, from the engine's own
``spark:program`` spans.

The engine opens one ``spark:program`` span around each call of a named
program (``spark_rapids_tpu/programs.py``), under the span of the plan
node or scan stage that made the call, with the arguments ``program``
(the registry name), ``miss`` (1 where the call traced the function
anew, else 0), ``trace_s``, ``lower_s``, ``compile_s`` (the seconds of
jax's three compile phases on that thread, the persistent-cache fetch
inside ``compile_s``) and ``cache_hits``. ``mesh_busy.idle_under`` picks
spans by name only; this file picks the spans that MISSED and gives the
seconds, averaged over the chips, in which a chip ran nothing while one
of them was open on any thread (the union of their intervals: two
members missing at once count once), and the same by program and parent
for PERF.md. Both over the traced window (the harness's ``plan`` /
``collect`` spans), from the run's newest ``.xplane.pb``; nothing of the
engine is imported. A trace with no device plane gives ``None``.

    python3 benchmark/program_jit.py [<file>.xplane.pb]

prints that table for the newest trace (or the file given), one JSON
line a program and parent, the largest idle first.
"""
from __future__ import annotations

import json
import sys

import numpy as np

import mesh_busy
import span_reduce
from trace_reduce import HOST_PLANE, _union

SPAN = "spark:program"
PHASES = ("trace_s", "lower_s", "compile_s")


def _idle(chips, starts, ends, w0, w1) -> float:
    """Idle seconds, mean over ``chips``, under the union of the spans."""
    cs, ce = np.clip(starts, w0, w1), np.clip(ends, w0, w1)
    us, ue = _union(cs[ce > cs], ce[ce > cs])
    if not len(us):
        return 0.0
    return float(np.mean([b.idle(us, ue).sum() for _, b in chips])) / 1e9


def miss_idle_s(profile) -> float | None:
    """Seconds of the traced window, averaged over the chips, in which a
    chip ran nothing while a ``spark:program`` span with ``miss`` >= 1
    was open on any thread; 0.0 where no span missed."""
    chips, w0, w1 = mesh_busy._chips(profile, *span_reduce._window(profile))
    if not chips:
        return None
    names, starts, ends, args, _ = span_reduce._program_spans(profile)
    pick = np.asarray([n == SPAN and a.get("miss", 0) >= 1
                       for n, a in zip(names, args)], bool)
    return _idle(chips, starts[pick], ends[pick], w0, w1)


def _spans(profile):
    """Every ``spark:`` event of the host plane: name, start, end and
    ALL its arguments (``span_reduce`` keeps the numbers only, and a
    program's name and parent are strings)."""
    out = []
    for pl in profile.planes:
        if pl.name != HOST_PLANE:
            continue
        for ln in pl.lines:
            for e in ln.events:
                if e.name.startswith(span_reduce.PREFIX):
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return out


def by_program(profile) -> list | None:
    """Per program and parent (the parent span's name, and its ``op``
    where it is an operator's): calls, misses, the three phases' seconds,
    cache hits and the idle seconds under that row's missing calls,
    the largest idle first."""
    chips, w0, w1 = mesh_busy._chips(profile, *span_reduce._window(profile))
    if not chips:
        return None
    spans = _spans(profile)
    parent_of = {a.get("span"): (n, a.get("op")) for n, _, _, a in spans}
    rows, missed = {}, {}
    for n, s, e, a in spans:
        if n != SPAN or min(e, w1) <= max(s, w0):
            continue
        pname, op = parent_of.get(a.get("parent"), ("(none)", None))
        key = (a.get("program"), pname + (f" {op}" if op else ""))
        row = rows.setdefault(key, dict.fromkeys(
            ("calls", "misses", "cache_hits") + PHASES, 0))
        row["calls"] += 1
        row["misses"] += int(a.get("miss", 0))
        row["cache_hits"] += int(a.get("cache_hits", 0))
        for k in PHASES:
            row[k] += float(a.get(k, 0.0))
        if a.get("miss", 0):
            missed.setdefault(key, []).append((s, e))
    out = []
    for key, row in rows.items():
        iv = np.asarray(missed.get(key, []), float).reshape(-1, 2)
        out.append(dict(program=key[0], parent=key[1], **row,
                        idle_s=_idle(chips, iv[:, 0], iv[:, 1], w0, w1)))
    return sorted(out, key=lambda r: -r["idle_s"])


def main(argv) -> int:
    from jax.profiler import ProfileData
    path = argv[0] if argv else span_reduce.newest_xplane()
    if path is None:
        print("no trace", file=sys.stderr)
        return 1
    rows = by_program(ProfileData.from_file(path))
    for row in rows or ():
        print(json.dumps(row))
    return 0 if rows is not None else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
