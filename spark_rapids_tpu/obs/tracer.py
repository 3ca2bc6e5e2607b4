"""Span-based tracer with cross-process stitching.

One ``Tracer`` per query (driver side) or per task attempt (worker
side). Spans carry a ``trace_id`` shared by every process that worked on
the query and a ``parent_id`` linking them into one tree:

    query q1                                (driver, pid 0)
      stage map s1                          (driver)
        q1s1m0.a0  [attempt, failed]        (driver bookkeeping span)
          task q1s1m0 a0                    (worker 0, pid 1)
            Project#3 / shuffle_write ...   (worker operator spans)
        q1s1m0.a1  [attempt, ok]            (driver)
          task q1s1m0 a1                    (worker 1, pid 2)
            ...

Driver-side spans are recorded live through a thread-local parent stack
(``span()`` context manager); scheduler attempt spans are emitted
retroactively (``emit``) because their extent is only known at harvest
time; worker spans travel back through the filesystem rendezvous (a
``.spans`` JSON file committed next to the task's ``.ok``/``.err``
marker) and are ``absorb``-ed into the driver tracer, which writes one
Chrome ``trace_event`` JSON per query (loadable in chrome://tracing or
https://ui.perfetto.dev).

Wall-clock ``time.time()`` stamps span starts (cross-process
comparable on one host / shared filesystem); ``time.perf_counter()``
measures durations so a clock step cannot produce negative spans.

**One span, two sinks.** A live span also holds a
``jax.profiler.TraceAnnotation`` open for its whole extent, so the same
span lies on the JAX profiler's host plane, on the clock of the device
operations, whenever a profiler session runs (XProf shows the program's
spans beside the chip's; ``benchmark/span_reduce.py`` lays them over the
chip's idle gaps). There its name is ``spark:<kind>`` (``spark:query``,
``spark:op``, ``spark:scan.read``) and it carries ``query`` (``q`` +
the tracer's ``trace_id``), ``span`` and ``parent`` (``s`` + the ids of
the store; the letter keeps the profiler from reading ``0.10`` as the
number 0.1) and the span's own arguments. TraceMe's wire form fences
arguments with ``#`` and separates them with ``,``: both are replaced in
names and string values (a node label ``Project#op3`` goes in as
``Project:op3``).
A tracer is live when ``spark.rapids.trace.dir`` is set OR a profiler
session is running when it is made (``tracer_from_conf``); with only the
profiler to feed it writes no file.
"""
from __future__ import annotations

import contextlib
import ctypes
import json
import os
import sys
import threading
import time
import uuid
from typing import Dict, List, Optional

from jax.profiler import TraceAnnotation

from ..config import register
from .recorder import RECORDER as _FLIGHT, prune_oldest

__all__ = ["TRACE_DIR", "TRACE_MAX_SPANS", "TRACE_MAX_FILES", "Span",
           "Tracer", "NULL_TRACER", "tracer_from_conf", "spans_to_chrome",
           "load_chrome_trace", "PROFILER_PREFIX", "StageClock", "LIVE"]

#: every name this program writes to the profiler starts with it, so a
#: reader tells the program's spans from the runtime's events by name
PROFILER_PREFIX = "spark:"
_WIRE_SAFE = str.maketrans({"#": ":", ",": ";"})


_OS_NAMED = threading.local()


class _Live(threading.local):
    """The tracer of this thread's innermost open live span, ``None``
    outside every one (and always under the null tracer): how
    ``programs.named_jit``'s callable tells, in one attribute read,
    whether a call of a named program opens a ``spark:program`` span."""

    tracer = None


LIVE = _Live()


def _name_os_thread() -> None:
    """Give the calling thread's Python name to the operating system
    too, once (Linux ``PR_SET_NAME``, 15 bytes): the JAX profiler names
    a host line after the OS thread when it first sees it, and CPython
    before 3.14 leaves every thread it starts under its creator's name,
    so without this all of a query's threads read ``python`` in a
    profile. Called by a live span as it opens, never by the disabled
    tracer: an untraced query starts its threads as it always did. The
    main thread keeps its name (it is the process's); does nothing
    where it cannot."""
    if getattr(_OS_NAMED, "done", False):
        return
    _OS_NAMED.done = True
    me = threading.current_thread()
    if me is threading.main_thread() or not sys.platform.startswith("linux"):
        return
    try:
        prctl = ctypes.CDLL(None).prctl
    except (OSError, AttributeError):
        return
    prctl.argtypes = [ctypes.c_int, ctypes.c_char_p, ctypes.c_ulong,
                      ctypes.c_ulong, ctypes.c_ulong]
    prctl.restype = ctypes.c_int
    prctl(15, me.name.encode()[:15], 0, 0, 0)


def _wire(args: Dict) -> Dict:
    """Arguments as TraceMe can carry them (module docstring)."""
    return {k: v.translate(_WIRE_SAFE) if isinstance(v, str) else v
            for k, v in args.items()}

TRACE_DIR = register(
    "spark.rapids.trace.dir", "",
    "When set, every query records query/stage/operator spans (driver "
    "AND process-cluster workers, stitched via a propagated trace "
    "context) and writes one Chrome trace_event JSON under this "
    "directory — open it in chrome://tracing or Perfetto. The same "
    "spans also go to the JAX profiler's host plane (named spark:...) "
    "while a profiler session runs, with or without this directory. "
    "With neither, the tracer is a shared no-op.")
TRACE_MAX_SPANS = register(
    "spark.rapids.trace.maxSpans", 100_000,
    "Per-tracer span buffer bound; spans past it are dropped and "
    "counted (trace JSON metadata reports dropped_spans) so a "
    "pathological query cannot exhaust driver memory.")
TRACE_MAX_FILES = register(
    "spark.rapids.trace.maxFiles", 200,
    "On-disk retention for spark.rapids.trace.dir and "
    "spark.rapids.eventLog.dir: at write time the oldest files beyond "
    "this count are pruned (atomic unlinks), so a long-lived session "
    "cannot accumulate trace/event JSONs without bound.")


class Span:
    """One closed span; plain data, serialized as a dict."""

    __slots__ = ("name", "cat", "span_id", "parent_id", "ts", "dur",
                 "pid", "args")

    def __init__(self, name: str, cat: str, span_id: str,
                 parent_id: Optional[str], ts: float, dur: float,
                 pid: int, args: Optional[Dict] = None):
        self.name = name
        self.cat = cat
        self.span_id = span_id
        self.parent_id = parent_id
        self.ts = ts            # wall-clock start, seconds since epoch
        self.dur = dur          # seconds
        self.pid = pid          # 0 = driver, worker K = K + 1
        self.args = args or {}

    def to_dict(self) -> Dict:
        return {"name": self.name, "cat": self.cat, "span_id": self.span_id,
                "parent_id": self.parent_id, "ts": self.ts,
                "dur": self.dur, "pid": self.pid, "args": self.args}

    @staticmethod
    def from_dict(d: Dict) -> "Span":
        return Span(d["name"], d.get("cat", "default"), d["span_id"],
                    d.get("parent_id"), d["ts"], d["dur"],
                    d.get("pid", 0), d.get("args") or {})


class _LiveSpan:
    """Context manager for an in-flight span; exposes ``span_id`` so
    callers can hand it to children in other processes (or threads),
    and ``dur`` (seconds) once it has closed."""

    __slots__ = ("_tracer", "name", "cat", "span_id", "parent_id",
                 "args", "kind", "dur", "_ts", "_t0", "_ann", "_outer")

    def __init__(self, tracer: "Tracer", name: str, cat: str,
                 parent_id: Optional[str], args: Optional[Dict],
                 kind: Optional[str]):
        self._tracer = tracer
        self.name = name
        self.cat = cat
        self.span_id = tracer._next_id()
        self.parent_id = parent_id
        self.args = args
        self.kind = kind or name
        self.dur = 0.0

    def set(self, **args) -> None:
        """Arguments known only once the work is under way (bytes read,
        rows staged); allowed until the span closes."""
        self.args = dict(self.args or {}, **args)
        self._ann.set_metadata(**_wire(args))

    def __enter__(self) -> "_LiveSpan":
        _name_os_thread()
        stack = self._tracer._stack()
        if self.parent_id is None and stack:
            self.parent_id = stack[-1]
        stack.append(self.span_id)
        ids = {"query": "q" + self._tracer.trace_id,
               "span": "s" + self.span_id}
        if self.parent_id is not None:
            ids["parent"] = "s" + self.parent_id
        self._ann = TraceAnnotation(
            PROFILER_PREFIX + self.kind.translate(_WIRE_SAFE),
            **ids, **_wire(self.args or {}))
        self._outer = LIVE.tracer
        LIVE.tracer = self._tracer
        self._ann.__enter__()
        # the two clocks read back to back: a child's ts + dur then
        # stays inside its parent's
        self._ts = time.time()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.dur = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        LIVE.tracer = self._outer
        stack = self._tracer._stack()
        if stack and stack[-1] == self.span_id:
            stack.pop()
        self._tracer._record(Span(self.name, self.cat, self.span_id,
                                  self.parent_id, self._ts, self.dur,
                                  self._tracer.pid, self.args))
        return False


class _NullSpan:
    """Shared no-op span: the cost of tracing when disabled."""

    __slots__ = ()
    span_id = None
    dur = 0.0

    def set(self, **args):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


class _Stopwatch:
    """The disabled span of a stage whose seconds feed a counter
    (``span(..., timed=True)``): one clock pair, nothing recorded."""

    __slots__ = ("dur", "_t0")
    span_id = None

    def set(self, **args):
        pass

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.dur = time.perf_counter() - self._t0
        return False


_NULL_SPAN = _NullSpan()


class Tracer:
    """Bounded span collector for one process's share of a trace."""

    enabled = True

    def __init__(self, trace_id: Optional[str] = None, pid: int = 0,
                 max_spans: int = 100_000, id_prefix: str = "",
                 max_files: int = 200):
        self.trace_id = trace_id or uuid.uuid4().hex[:16]
        self.pid = pid
        # span-id namespace: workers prefix their ids with the attempt
        # key so two attempts on one worker (fresh Tracer each) can't
        # mint colliding ids into the same stitched trace
        self.id_prefix = id_prefix
        self.max_spans = max_spans
        self.max_files = max_files
        self.spans: List[Span] = []
        self.dropped = 0
        self._seq = 0
        self._lock = threading.Lock()
        self._local = threading.local()

    # --- recording --------------------------------------------------------

    def _stack(self) -> List[str]:
        s = getattr(self._local, "stack", None)
        if s is None:
            s = self._local.stack = []
        return s

    def _next_id(self) -> str:
        with self._lock:
            self._seq += 1
            return f"{self.id_prefix}{self.pid}.{self._seq}"

    def _record(self, span: Span) -> None:
        with self._lock:
            if len(self.spans) >= self.max_spans:
                self.dropped += 1
            else:
                self.spans.append(span)
        # flight-recorder tap: span closures join the always-on ring
        # (the recorder also gets events from tracer-free paths, so it
        # works with tracing disabled; this tap only ADDS detail)
        _FLIGHT.record_span(span)

    def span(self, name: str, cat: str = "default",
             parent_id: Optional[str] = None,
             args: Optional[Dict] = None, kind: Optional[str] = None,
             timed: bool = False) -> _LiveSpan:
        """Live span context manager; nests via a thread-local stack
        unless ``parent_id`` pins it explicitly (a span opened in
        another process or on another thread than its cause). ``kind``
        is its name on the profiler where ``name`` is an instance label
        (operator spans: ``kind="op"``). ``timed`` asks for ``dur`` even
        from the disabled tracer: the stage's seconds feed a counter
        and this is the one place they are taken."""
        return _LiveSpan(self, name, cat, parent_id, args, kind)

    def current_span_id(self) -> Optional[str]:
        """This thread's innermost open span — the parent a
        retroactively ``emit``-ed span should nest under."""
        s = self._stack()
        return s[-1] if s else None

    def emit(self, name: str, cat: str, ts: float, dur: float,
             span_id: Optional[str] = None,
             parent_id: Optional[str] = None, pid: Optional[int] = None,
             args: Optional[Dict] = None) -> str:
        """Retroactive span whose extent is already known (scheduler
        attempt timelines). Deterministic ``span_id``s let other
        processes parent onto a span before it is emitted."""
        sid = span_id or self._next_id()
        self._record(Span(name, cat, sid, parent_id, ts, dur,
                          self.pid if pid is None else pid, args))
        return sid

    def absorb(self, span_dicts: List[Dict]) -> None:
        """Merge spans another process serialized (worker .spans files)."""
        for d in span_dicts:
            try:
                self._record(Span.from_dict(d))
            except (KeyError, TypeError):
                continue  # torn/alien entry: skip, keep the trace

    # --- export -----------------------------------------------------------

    def drain(self) -> List[Dict]:
        with self._lock:
            out = [s.to_dict() for s in self.spans]
        return out

    def summary(self) -> Dict:
        """Compact rollup for event-log embedding: span counts and total
        duration per category."""
        by_cat: Dict[str, Dict[str, float]] = {}
        with self._lock:
            for s in self.spans:
                c = by_cat.setdefault(s.cat, {"spans": 0, "total_s": 0.0})
                c["spans"] += 1
                c["total_s"] = round(c["total_s"] + s.dur, 6)
            n = len(self.spans)
        return {"trace_id": self.trace_id, "spans": n,
                "dropped": self.dropped, "by_cat": by_cat}

    def write_chrome(self, base_dir: str,
                     name: Optional[str] = None) -> str:
        """Write one Chrome trace_event JSON; returns its path. The
        write is atomic (tmp + rename) so readers never see a torn
        trace. No directory (a tracer that only feeds the profiler):
        no file, and ``""``."""
        if not base_dir:
            return ""
        os.makedirs(base_dir, exist_ok=True)
        fname = name or f"trace-{self.trace_id}.json"
        path = os.path.join(base_dir, fname)
        doc = spans_to_chrome(self.drain(), self.trace_id, self.dropped)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(doc, f)
        os.replace(tmp, path)
        # write-time retention: oldest traces beyond maxFiles pruned so
        # a long-lived session cannot grow the dir without bound
        prune_oldest(base_dir, self.max_files, prefix="trace-",
                     suffix=".json")
        return path


def spans_to_chrome(span_dicts: List[Dict], trace_id: str,
                    dropped: int = 0) -> Dict:
    """Chrome trace_event JSON object format: complete ('X') events in
    microseconds, normalized to the trace's earliest span, one 'process'
    per execution role (driver / worker K) named via 'M' metadata
    events. span/parent/trace ids ride in args — the linkage the
    stitching tests and the critical-path miner consume."""
    events = []
    t0 = min((d["ts"] for d in span_dicts), default=0.0)
    pids = set()
    for d in span_dicts:
        pids.add(d.get("pid", 0))
        events.append({
            "name": d["name"], "cat": d.get("cat", "default"), "ph": "X",
            "ts": round((d["ts"] - t0) * 1e6, 3),
            "dur": round(d["dur"] * 1e6, 3),
            "pid": d.get("pid", 0), "tid": 0,
            "args": dict(d.get("args") or {}, span_id=d["span_id"],
                         parent_id=d.get("parent_id"), trace_id=trace_id),
        })
    for pid in sorted(pids):
        events.append({
            "name": "process_name", "ph": "M", "pid": pid, "tid": 0,
            "args": {"name": "driver" if pid == 0
                     else f"worker {pid - 1}"}})
    return {"traceEvents": events, "displayTimeUnit": "ms",
            "otherData": {"trace_id": trace_id, "dropped_spans": dropped,
                          "epoch_origin_s": t0}}


def load_chrome_trace(path: str) -> List[Dict]:
    """Back-convert a written trace to span dicts (seconds), for the
    critical-path miner and tests."""
    with open(path) as f:
        doc = json.load(f)
    t0 = float(doc.get("otherData", {}).get("epoch_origin_s", 0.0))
    out = []
    for ev in doc.get("traceEvents", []):
        if ev.get("ph") != "X":
            continue
        args = dict(ev.get("args") or {})
        out.append({"name": ev["name"], "cat": ev.get("cat", "default"),
                    "span_id": args.pop("span_id", None),
                    "parent_id": args.pop("parent_id", None),
                    "ts": t0 + float(ev["ts"]) / 1e6,
                    "dur": float(ev["dur"]) / 1e6,
                    "pid": ev.get("pid", 0), "args": args})
    return out


class _NullTracer:
    """The disabled path: every call is a no-op and ``span()`` returns
    one shared context manager — no allocation on hot paths (a
    ``timed`` span is a bare stopwatch: its caller needs the seconds)."""

    enabled = False
    trace_id = ""
    pid = 0
    spans: List[Span] = []
    dropped = 0

    def span(self, name, cat="default", parent_id=None, args=None,
             kind=None, timed=False):
        return _Stopwatch() if timed else _NULL_SPAN

    def current_span_id(self):
        return None

    def emit(self, *a, **kw):
        return None

    def absorb(self, span_dicts):
        pass

    def drain(self):
        return []

    def summary(self):
        return {}

    def write_chrome(self, base_dir, name=None):
        return ""


NULL_TRACER = _NullTracer()


class StageClock:
    """The stages of one unit of pipelined work (one scan batch on a
    feeder thread), each timed at ONE site: ``stage(name)`` is the span
    ``<prefix>.<name>`` under ``parent_id`` (the cause waits on another
    thread, so the thread-local stack cannot supply it) and adds the
    span's own seconds to ``seconds[name]``. The consumer folds
    ``seconds`` into the operator's counters when the unit is handed
    over, so a counter is the summed duration of its spans and is only
    ever written on the consumer's thread. Under the null tracer a
    stage is a bare stopwatch."""

    __slots__ = ("_tracer", "_prefix", "_parent_id", "seconds")

    def __init__(self, tracer=None, prefix: str = "stage",
                 parent_id: Optional[str] = None):
        self._tracer = tracer or NULL_TRACER
        self._prefix = prefix
        self._parent_id = parent_id
        self.seconds: Dict[str, float] = {}

    @contextlib.contextmanager
    def stage(self, name: str, **args):
        with self._tracer.span(f"{self._prefix}.{name}", cat=self._prefix,
                               parent_id=self._parent_id, args=args,
                               timed=True) as sp:
            yield sp
        self.seconds[name] = self.seconds.get(name, 0.0) + sp.dur


def tracer_from_conf(conf, pid: int = 0, trace_id: Optional[str] = None):
    """A live Tracer when ``spark.rapids.trace.dir`` is set or a JAX
    profiler session is running now (its host plane is the second sink
    of every span), else the shared null tracer."""
    if not conf.get(TRACE_DIR) and not TraceAnnotation.is_enabled():
        return NULL_TRACER
    return Tracer(trace_id=trace_id, pid=pid,
                  max_spans=conf.get(TRACE_MAX_SPANS),
                  max_files=conf.get(TRACE_MAX_FILES))
