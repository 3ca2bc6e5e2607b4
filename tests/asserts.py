"""Dual-run equivalence assertions (reference: integration_tests asserts.py
`assert_gpu_and_cpu_are_equal_collect` — SURVEY.md §4.1; built from
capability description, mount empty).

Expression-level: evaluate the same expression tree on the CPU (pyarrow/
numpy, Spark-semantics oracle) and on the TPU path (device batch), compare.
Plan-level helpers are added with the session API.
"""
from __future__ import annotations

import functools
import importlib.util
import math
import os

import numpy as np
import pyarrow as pa

from spark_rapids_tpu import datatypes as dt
from spark_rapids_tpu.columnar import arrow_to_device
from spark_rapids_tpu.columnar.arrow_bridge import device_column_to_arrow
from spark_rapids_tpu.expr.base import EvalCtx, bind_expr
from spark_rapids_tpu.columnar.arrow_bridge import engine_schema


@functools.lru_cache(maxsize=None)
def obs_checker():
    """``tools/check_obs_output.py`` as a module (it keeps no state, so
    one per process): the operator's schema checker is the oracle for
    every trace, Prometheus dump, incident bundle, query profile, lint
    and lock-order report a test makes the engine write."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        os.pardir, "tools", "check_obs_output.py")
    spec = importlib.util.spec_from_file_location("check_obs_output", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _norm_nested(v):
    """Recursive NaN-stable normalizer for nested (struct/array/map)
    python values."""
    if isinstance(v, float) and math.isnan(v):
        return "NaN"
    if isinstance(v, dict):
        return {k: _norm_nested(x) for k, x in v.items()}
    if isinstance(v, (list, tuple)):
        return tuple(_norm_nested(x) for x in v)
    return v


def _normalize(values, t: dt.DataType, approx_float=False):
    if dt.is_nested(t):
        return [_norm_nested(v) for v in values]
    out = []
    for v in values:
        if v is None:
            out.append(None)
        elif dt.is_floating(t):
            if isinstance(v, float) and math.isnan(v):
                out.append("NaN")
            elif approx_float and isinstance(v, float) and math.isfinite(v):
                out.append(round(v, 10) if abs(v) < 1e100 else v)
            else:
                out.append(v)
        else:
            out.append(v)
    return out


def assert_columns_equal(cpu: pa.Array, tpu: pa.Array, t: dt.DataType,
                         approx_float=False, label=""):
    cl = _normalize(cpu.to_pylist(), t, approx_float)
    tl = _normalize(tpu.to_pylist(), t, approx_float)
    if approx_float and dt.is_floating(t):
        assert len(cl) == len(tl), f"{label}: length {len(cl)} vs {len(tl)}"
        for i, (a, b) in enumerate(zip(cl, tl)):
            if a == b:
                continue
            if isinstance(a, float) and isinstance(b, float):
                assert a == b or abs(a - b) <= 1e-6 * max(1.0, abs(a)), \
                    f"{label} row {i}: cpu={a!r} tpu={b!r}"
            else:
                raise AssertionError(f"{label} row {i}: cpu={a!r} tpu={b!r}")
    else:
        assert cl == tl, (
            f"{label}: mismatch\n cpu={cl[:20]}\n tpu={tl[:20]}"
            + (f"\n (first diff at row "
               f"{next(i for i, (a, b) in enumerate(zip(cl, tl)) if a != b)})"
               if cl != tl and len(cl) == len(tl) else ""))


def assert_tpu_and_cpu_expr_equal(expr, rb: pa.RecordBatch, ansi=False,
                                  approx_float=False, label=""):
    """Evaluate `expr` (with UnresolvedColumn refs) both ways and compare."""
    schema = engine_schema(rb.schema)
    bound = bind_expr(expr, schema)
    ctx = EvalCtx(ansi=ansi)
    cpu = bound.eval_cpu(rb, ctx)
    batch = arrow_to_device(rb, schema)
    tcol = bound.eval_tpu(batch, ctx)
    tpu = device_column_to_arrow(tcol, rb.num_rows)
    assert_columns_equal(cpu, tpu, bound.dtype, approx_float,
                         label or repr(expr))
    return cpu


def _elem_sort_key(v, approx_float):
    """Pairing key for unordered comparison: numeric values compare
    numerically (so -0.0/0.0 and last-ulp approx noise land in the same
    position on both sides), everything else by type+string."""
    if v is None:
        return (3, "", 0.0)
    if isinstance(v, (int, float)) and not isinstance(v, bool):
        x = float(v) if isinstance(v, float) else v
        if isinstance(x, float) and approx_float and x != 0 \
                and math.isfinite(x):
            # quantize to ~6 significant digits so near-equal values tie
            x = round(x, 6 - int(math.floor(math.log10(abs(x)))))
        return (0, "", x)
    return (1, str(type(v)), str(v))


def _sorted_rows(table: pa.Table, types, approx_float):
    cols = [_normalize(c.to_pylist(), t, approx_float)
            for c, t in zip(table.columns, types)]
    rows = list(zip(*cols)) if cols else []
    return sorted(rows, key=lambda r: tuple(
        _elem_sort_key(v, approx_float) for v in r))


def assert_tpu_and_cpu_plan_equal(plan, conf=None, approx_float=False,
                                  ignore_order=False, label=""):
    """Run a physical plan on the TPU path and the CPU oracle path, compare
    full results (the plan-level dual-run harness — SURVEY.md §4.1)."""
    from spark_rapids_tpu.exec.base import (ExecCtx, collect_arrow,
                                            collect_arrow_cpu)
    label = label or plan.describe()
    types = plan.output_schema.types
    tpu = collect_arrow(plan, ExecCtx(conf))
    cpu = collect_arrow_cpu(plan, ExecCtx(conf))
    assert cpu.num_rows == tpu.num_rows, (
        f"{label}: row count cpu={cpu.num_rows} tpu={tpu.num_rows}")
    if ignore_order:
        crows = _sorted_rows(cpu, types, approx_float)
        trows = _sorted_rows(tpu, types, approx_float)
        if approx_float:
            assert len(crows) == len(trows)
            for i, (cr, tr) in enumerate(zip(crows, trows)):
                for a, b in zip(cr, tr):
                    if a == b:
                        continue
                    if isinstance(a, float) and isinstance(b, float) \
                            and abs(a - b) <= 1e-6 * max(1.0, abs(a)):
                        continue
                    raise AssertionError(
                        f"{label} sorted row {i}: cpu={cr!r} tpu={tr!r}")
        else:
            assert crows == trows, (
                f"{label}: mismatch (ignore_order)\n cpu={crows[:10]}\n "
                f"tpu={trows[:10]}")
    else:
        for i, t in enumerate(types):
            assert_columns_equal(cpu.column(i).combine_chunks(),
                                 tpu.column(i).combine_chunks(), t,
                                 approx_float,
                                 f"{label} col {plan.output_schema.names[i]}")
    return cpu
