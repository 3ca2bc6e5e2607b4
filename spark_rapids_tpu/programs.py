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

__all__ = ["PROGRAM_NAMES", "EAGER_OPS", "named_jit", "module_name"]

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
