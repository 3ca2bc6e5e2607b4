"""The comparison that decides ``correct``.

Every table an execution of the window returned is held against the
plain reference's table: key columns exactly, row by row in the
result's order, value columns by their widest relative gap. Each number
compared has its own limit (the reference module's ``VALUES``; an exact
comparison has the limit 0). Nothing here imports the engine.
"""
from __future__ import annotations

import numpy as np
import pyarrow as pa


def compare_table(got: pa.Table, want: pa.Table, keys, values) -> dict:
    """``{name: value}`` of one result against the reference:
    ``rows_differ`` (missing, extra or with a wrong key; also every row
    when a column is missing) and ``<column>_rel_gap`` per value column."""
    out = {}
    missing = [c for c in list(keys) + list(values)
               if c not in got.column_names]
    n = min(got.num_rows, want.num_rows)
    differ = abs(got.num_rows - want.num_rows)
    if missing or got.num_columns != want.num_columns:
        differ = max(got.num_rows, want.num_rows, 1)
    else:
        bad = np.zeros(n, bool)
        for k in keys:
            g = got.column(k).combine_chunks().slice(0, n)
            w = want.column(k).combine_chunks().slice(0, n)
            if g.null_count or g.type != w.type:
                g = g.cast(w.type)
            bad |= ~np.asarray(pa.compute.equal(g, w).fill_null(False))
        differ += int(bad.sum())
    out["rows_differ"] = float(differ)
    for v in values:
        if v in missing or n == 0:
            out[f"{v}_rel_gap"] = 0.0 if (not missing and
                                          want.num_rows == 0) else float("inf")
            continue
        g = got.column(v).to_numpy(zero_copy_only=False)[:n].astype(float)
        w = want.column(v).to_numpy(zero_copy_only=False)[:n].astype(float)
        gap = np.abs(g - w) / np.maximum(np.abs(w), np.finfo(float).tiny)
        gap = np.where(np.isfinite(g), gap, np.inf)
        out[f"{v}_rel_gap"] = float(gap.max())
    return out


def judge(results, want: pa.Table, keys, values) -> tuple[bool, dict]:
    """Worst reading over every execution's table, each beside its
    limit: ``(correct, {name: {"value": v, "limit": l}})``. No result at
    all is not correct."""
    limits = {"rows_differ": 0.0}
    limits.update({f"{v}_rel_gap": float(lim) for v, lim in values.items()})
    worst = {k: (float("inf") if not results else 0.0) for k in limits}
    for got in results:
        for k, v in compare_table(got, want, keys, values).items():
            worst[k] = max(worst[k], v)
    compared = {k: {"value": worst[k], "limit": limits[k]} for k in limits}
    correct = all(c["value"] <= c["limit"] for c in compared.values())
    return correct, compared
