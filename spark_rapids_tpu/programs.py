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
"""
from __future__ import annotations

__all__ = ["PROGRAM_NAMES", "named_jit", "module_name"]

PROGRAM_NAMES = frozenset((
    "scan_decode",        # io/parquet_device.py: fused decode of a row group
    "scan_decode_chain",  # ... with the fused operator chain spliced in
    "scan_chain",         # io/scan.py: the chain alone (no device column)
    "fused_stage",        # exec/base.py: one fused operator chain per batch
    "agg_final",          # exec/aggregate.py: the final aggregation
    "concat_batches",     # ops/concat.py: exact and capacity-bounded concat
))


def module_name(name: str) -> str:
    """The XLA module's name, as device traces and compile logs have it."""
    return "jit_" + name


def named_jit(name: str, fn, **jit_kwargs):
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
    return jax.jit(fn, **jit_kwargs)
