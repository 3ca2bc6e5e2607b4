"""A traced query over several chips: each chip's own busy seconds, and
the idle seconds that lie under spans of given names.

``trace_reduce`` / ``span_reduce`` / ``module_busy`` AVERAGE over the
device planes (``/device:TPU:<n>``); the readers of a cell on four chips
also need the chips one by one (how many worked, how evenly) and the idle
time inside the exchange's spans, which ``span_reduce`` shares out among
its own fixed lists of names only. Both are taken over the traced window
(the harness's ``plan`` / ``collect`` spans) with ``span_reduce``'s
functions, from the run's newest ``.xplane.pb``; nothing of the engine is
imported. A trace with no device plane (a CPU rehearsal) gives ``None``
and the reader leaves its metric out.
"""
from __future__ import annotations

import os

import numpy as np

import span_reduce
from trace_reduce import DEVICE_PLANE, OPS_LINE, _events, _union

_MEMO: dict = {}


def _chips(profile, w0, w1):
    """``(plane name, _Busy)`` of every device plane that has an ``XLA
    Ops`` line, over the window (the whole trace where it has none)."""
    planes = []
    for pl in profile.planes:
        if not DEVICE_PLANE.match(pl.name):
            continue
        for ln in pl.lines:
            if ln.name == OPS_LINE:
                _, s, d = _events(ln)
                planes.append((pl.name, s, s + d))
    if w0 is None and any(len(s) for _, s, _ in planes):
        w0 = min(float(s.min()) for _, s, _ in planes if len(s))
        w1 = max(float(e.max()) for _, _, e in planes if len(e))
    if w0 is None:
        return [], None, None
    return [(name, span_reduce._Busy(s, e, w0, w1))
            for name, s, e in planes], w0, w1


def per_chip_busy_s(profile) -> dict | None:
    """``{plane: busy seconds in the traced window}``, a chip that ran
    nothing in it at 0.0; ``None`` where the trace has no device plane."""
    chips, _, _ = _chips(profile, *span_reduce._window(profile))
    if not chips:
        return None
    return {name: float(b.cum[-1]) / 1e9 for name, b in chips}


def idle_under(profile, names) -> float | None:
    """Seconds of the traced window, averaged over the chips, in which a
    chip ran nothing while a span of one of ``names`` was open on any
    thread (the union of their intervals: two members waiting at once
    count once). 0.0 where the trace has no such span."""
    chips, w0, w1 = _chips(profile, *span_reduce._window(profile))
    if not chips:
        return None
    sp_names, starts, ends, _, _ = span_reduce._program_spans(profile)
    pick = np.isin(np.asarray(sp_names, object), list(names)) \
        if sp_names else np.zeros(0, bool)
    cs, ce = np.clip(starts[pick], w0, w1), np.clip(ends[pick], w0, w1)
    us, ue = _union(cs[ce > cs], ce[ce > cs])
    if not len(us):
        return 0.0
    return float(np.mean([b.idle(us, ue).sum() for _, b in chips])) / 1e9


def of(reading):
    """The run's newest trace, parsed once per process; ``None`` where
    the harness found no device operation in it."""
    if not reading.get("trace"):
        return None
    path = span_reduce.newest_xplane()
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _MEMO:
        from jax.profiler import ProfileData
        _MEMO.clear()
        _MEMO[key] = ProfileData.from_file(path)
    return _MEMO[key]
