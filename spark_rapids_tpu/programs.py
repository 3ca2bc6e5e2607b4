"""Names for the XLA programs the engine compiles.

``jax.jit`` names a program after the Python function it is handed, so
a closure called ``build`` or ``composed``, a ``lambda`` or a
``functools.partial`` reaches the profiler's device plane as
``jit_build``, ``jit_composed``, ``jit__lambda_`` or ``jit__unknown``:
names that say nothing of layer or stage and are shared by unrelated
programs. ``named_jit`` is ``jax.jit`` under a name from the registry
below: ``<layer>_<stage>``, no id that changes between queries or
processes. The device operations of a trace then read
``jit_scan_decode_chain/while.42`` (``benchmark/trace_reduce.py``), and
the span that dispatches a program carries the same name
(``spark:scan.dispatch``, argument ``program``). The name is part of the
module and so of the persistent compile cache's key; the HLO is not
touched. The jit sites not yet named are listed in ROADMAP.md (S0);
``tests/test_obs.py`` fails when a query on the benchmark's path
compiles a program whose name is not in the registry.

**Where the re-jit is paid.** ``named_jit`` hands back a ``NamedProgram``:
the ``jax.jit`` object, called through one thread-local read. Where the
calling thread is inside a live span of a query's tracer
(``obs/tracer.py::LIVE``), the call is a span ``spark:program`` under
that span (the ``op`` that names the plan node, ``scan.dispatch``,
``gang.member``), with the arguments ``program`` (the registry name),
``miss`` (1 if the call traced the function, else 0: an int, since the
benchmark's reducer drops bools), ``trace_s``, ``lower_s``,
``compile_s`` (this thread's seconds of jax's three compile phases while
the span was open; ``compile_s`` wraps the persistent-cache fetch) and
``cache_hits``. A named program called inside another's span, or inside
any trace, is part of that trace and opens none. One ``jax.monitoring``
listener, registered at import, feeds the open span of its thread and,
tracing on or off, the registry's ``rapids_jit_*`` families
(``obs/metrics.py``). It fires only on a cache miss.
"""
from __future__ import annotations

import threading

from .obs.metrics import (JIT_CACHE_HITS, JIT_HOST_SECONDS, JIT_PHASES,
                          JIT_TRACES)
from .obs.tracer import LIVE

__all__ = ["PROGRAM_NAMES", "EAGER_OPS", "named_jit", "module_name",
           "NamedProgram"]

PROGRAM_NAMES = frozenset((
    "scan_decode",        # io/parquet_device.py: fused decode of a row group
    "scan_decode_chain",  # ... with the fused operator chain spliced in
    "scan_chain",         # io/scan.py: the chain alone (no device column)
    "fused_stage",        # exec/base.py: one fused operator chain per batch
    "agg_final",          # exec/aggregate.py: the final aggregation
    "concat_batches",     # ops/concat.py: exact and capacity-bounded concat
    "compact_selection",  # ops/gather.py: a lazy selection made a prefix
    # exec/joins.py, per build side: key duplication + string lengths,
    # the sorted build keys of the unique-build probe, the duplicate flag
    "join_build_analysis",
    "join_build_probe",
    "join_build_dup",
    "join_probe",         # ... per stream batch, unique build: one program
    "join_count",         # ... staged path: matches, total, string bytes
    "join_indices",       # ... nested loop: output indices of the pairs
    "join_gather",        # ... staged path: indices + gather
    "join_pairs",         # ... nested loop: pair gather + condition
    "agg_partial",        # exec/aggregate.py: per-batch partial (unfused)
    "agg_merge",          # ... merge of partials past the batch size
    "agg_single",         # ... single-pass (collect_*, exact percentile)
    "sort_batch",         # exec/sort.py: sort (and truncate) one batch
    "sort_merge",         # ... one round of the out-of-core run merge
    # shuffle/ici.py: the SPMD all-to-all of an exchange epoch (the one
    # program that spans the mesh), the broadcast's all-gather, an
    # epoch's sizing reduction, and the landed ragged rebuilds (strings
    # from flat payloads; arrays and broadcast strings from matrices)
    "exchange_all_to_all",
    "exchange_all_gather",
    "exchange_caps",
    "exchange_strings",
    "exchange_ragged",
))

#: single-primitive programs JAX compiles for EAGER ``jnp`` calls on the
#: host path of a join / exchange / limit query (a lazy batch's live-row
#: count for the operator metrics, the limit's clamp, the exchange
#: view's partition mask): each well under a millisecond on the chip,
#: none the engine's own ``jax.jit`` site. Listed so that the registry
#: test can tell them from an unnamed closure; folding them into named
#: programs is ROADMAP S0's.
EAGER_OPS = frozenset((
    "iota", "less", "bitwise_and", "convert_element_type", "_reduce_sum",
    "add", "subtract", "clip", "broadcast_in_dim", "equal",
))


def module_name(name: str) -> str:
    """The XLA module's name, as device traces and compile logs have it."""
    return "jit_" + name


_PHASES = dict(zip(("/jax/core/compile/jaxpr_trace_duration",
                    "/jax/core/compile/jaxpr_to_mlir_module_duration",
                    "/jax/core/compile/backend_compile_duration"),
                   JIT_PHASES))
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


class _Compiling(threading.local):
    """This thread's share of jax's compile events."""

    call = None  # the _Call of the open spark:program span
    depth = 0    # phases under way: a jit traced while another is traced
    #              or lowered (a scan's body) is part of that phase
    hits = 0     # persistent-cache hits not yet claimed by their compile


_HERE = _Compiling()


class _Call:
    """What one ``spark:program`` span saw of its thread's compile."""

    __slots__ = ("miss", "trace_s", "lower_s", "compile_s", "cache_hits")

    def __init__(self):
        self.miss = self.cache_hits = 0
        self.trace_s = self.lower_s = self.compile_s = 0.0

    def args(self) -> dict:
        return {k: getattr(self, k) for k in self.__slots__}


def _program(fun_name: str) -> str:
    """``jit(fused_stage)`` (lowering, compile) -> ``fused_stage``."""
    if fun_name.startswith("jit(") and fun_name.endswith(")"):
        return fun_name[4:-1]
    return fun_name


def _on_start(event, value, **_):
    if event in _PHASES:
        _HERE.depth += 1


def _on_duration(event, secs, fun_name="", **_):
    phase = _PHASES.get(event)
    if phase is None:
        return
    here = _HERE
    here.depth = max(here.depth - 1, 0)
    if here.depth:  # inside another phase: its seconds are that one's
        return
    name = _program(fun_name)
    call = here.call
    if phase == "trace":
        JIT_TRACES.labels(name).inc()
        if call is not None:
            call.miss = 1
    elif phase == "compile" and here.hits:
        hits, here.hits = here.hits, 0
        JIT_CACHE_HITS.labels(name).inc(hits)
        if call is not None:
            call.cache_hits += hits
    JIT_HOST_SECONDS.labels(name, phase).inc(secs)
    if call is not None:
        setattr(call, phase + "_s", getattr(call, phase + "_s") + secs)


def _on_event(event, **_):
    if event == _CACHE_HIT:
        _HERE.hits += 1


def _listen() -> None:
    import jax.monitoring as monitoring
    monitoring.register_scalar_listener(_on_start)
    monitoring.register_event_duration_secs_listener(_on_duration)
    monitoring.register_event_listener(_on_event)


_listen()


class NamedProgram:
    """What ``named_jit`` returns: the ``jax.jit`` object (its name, HLO
    and cache key untouched), called through one thread-local read, and
    in a ``spark:program`` span where the thread is inside a live span
    (module docstring). ``lower``, ``trace``, ``eval_shape``,
    ``_cache_size`` and ``clear_cache`` are the jit object's."""

    __slots__ = ("name", "jitted")

    def __init__(self, name: str, jitted):
        self.name = name
        self.jitted = jitted

    def __call__(self, *args, **kwargs):
        tracer = LIVE.tracer
        if tracer is None or _HERE.call is not None or _HERE.depth:
            return self.jitted(*args, **kwargs)
        call = _HERE.call = _Call()
        try:
            with tracer.span(self.name, cat="program", kind="program",
                             args={"program": self.name}) as sp:
                try:
                    return self.jitted(*args, **kwargs)
                finally:
                    sp.set(**call.args())
        finally:
            _HERE.call = None

    def lower(self, *args, **kwargs):
        return self.jitted.lower(*args, **kwargs)

    def trace(self, *args, **kwargs):
        return self.jitted.trace(*args, **kwargs)

    def eval_shape(self, *args, **kwargs):
        return self.jitted.eval_shape(*args, **kwargs)

    def _cache_size(self) -> int:
        return self.jitted._cache_size()

    def clear_cache(self) -> None:
        self.jitted.clear_cache()


def named_jit(name: str, fn, **jit_kwargs) -> NamedProgram:
    """``jax.jit(fn, **jit_kwargs)`` compiled under ``module_name(name)``."""
    import jax
    if name not in PROGRAM_NAMES:
        raise ValueError(f"{name!r} is not in programs.PROGRAM_NAMES")
    try:
        fn.__name__ = fn.__qualname__ = name
    except (AttributeError, TypeError):  # a bound method, a partial
        inner = fn

        def fn(*args, **kwargs):
            return inner(*args, **kwargs)
        fn.__name__ = fn.__qualname__ = name
    return NamedProgram(name, jax.jit(fn, **jit_kwargs))
