"""Device-busy seconds of the traced query by XLA module.

``trace_reduce.reduce_profile`` keeps only the ten longest operations;
the readers of ``exec.join_busy_s`` / ``exec.agg_busy_s`` /
``exec.sort_busy_s`` need every operation of a FAMILY of programs
(``jit_join_*``, ``jit_agg_*``, ``jit_sort_*``: the names of
``spark_rapids_tpu/programs.py``), so this file sums by module itself,
with ``trace_reduce``'s functions: inside the traced window (the
harness's ``plan`` / ``collect`` spans) the union of the intervals of
the operations each module ran, averaged over the chips. It reads the
run's newest ``.xplane.pb`` as ``span_reduce`` does and imports nothing
of the engine. A CPU rehearsal (no device plane) and a program whose
modules carry no name of the family (the joins before they were named)
give ``None``, and the reader leaves its metric out.
"""
from __future__ import annotations

import os

import numpy as np

import span_reduce
from trace_reduce import DEVICE_PLANE, OPS_LINE, _events, _modules_of, _union

_MEMO: dict = {}


def by_module(profile) -> dict | None:
    """``{module: busy seconds}`` over the traced window, or ``None``
    where no chip ran an operation."""
    w0, w1 = span_reduce._window(profile)
    per_chip = []
    for pl in profile.planes:
        if not DEVICE_PLANE.match(pl.name):
            continue
        lines = [ln for ln in pl.lines if ln.name == OPS_LINE]
        if not lines:
            continue
        _, starts, durs = _events(lines[0])
        if len(starts) == 0:
            continue
        lo = float(starts.min()) if w0 is None else w0
        hi = float((starts + durs).max()) if w1 is None else w1
        cs, ce = np.clip(starts, lo, hi), np.clip(starts + durs, lo, hi)
        modules = _modules_of(pl, starts)
        busy = {}
        for m in set(modules):
            pick = (modules == m) & (ce > cs)
            us, ue = _union(cs[pick], ce[pick])
            busy[m] = float((ue - us).sum()) / 1e9
        per_chip.append(busy)
    if not per_chip:
        return None
    names = set().union(*per_chip)
    return {m: float(np.mean([c.get(m, 0.0) for c in per_chip]))
            for m in names}


def family_busy_s(reading, prefix: str):
    """Busy seconds of the traced query in the modules whose name
    starts with ``prefix``; ``None`` where there is none to read."""
    if not reading.get("trace"):
        return None
    path = span_reduce.newest_xplane()
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _MEMO:
        from jax.profiler import ProfileData
        _MEMO.clear()
        _MEMO[key] = by_module(ProfileData.from_file(path))
    modules = _MEMO[key]
    if not modules:
        return None
    mine = [v for m, v in modules.items() if m.startswith(prefix)]
    return sum(mine) if mine else None
