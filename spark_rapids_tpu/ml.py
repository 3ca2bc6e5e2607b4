"""ML bridge: hand device feature columns to a trainer.

TPU analog of the reference's `ColumnarRdd` / `InternalColumnarRddConverter`
(SURVEY.md §2.2-B "RDD/Dataset bridge", §3.5, BASELINE config 4;
reference mount empty): the reference exposes GPU column handles to
XGBoost4J-Spark so DMatrix construction skips row conversion. Here:

- `columnar_rdd(df)` yields the executed plan's DEVICE batches as
  {name: jax.Array} column dicts — no row conversion, no Arrow
  round-trip; a JAX trainer consumes HBM-resident features directly
  (the zero-copy path the reference gets via DMatrix-from-GPU-handles).
- `to_feature_matrix(df, feature_cols, label_col)` stacks numeric
  columns into ONE device (n, f) float32 matrix + label vector with a
  live-row mask — the DMatrix-shaped handoff.
- `to_torch(df, ...)` materializes the matrix for host trainers
  (torch CPU wheels here; on co-located deployments this is the
  device->host hop XGBoost's CPU predictor pays too).

The Mortgage-ETL-shaped pipeline feeding this lives in
`tools/mortgage.py` (BASELINE config 4's ETL half).
"""
from __future__ import annotations

from typing import Dict, Iterator, List, Optional, Tuple

__all__ = ["columnar_rdd", "to_feature_matrix", "to_torch"]


def _emit_ml_query_event(pp, ctx, wall_s: float) -> None:
    """The end-of-query observability collect() performs: write the
    Chrome trace this event's embedded summary references, then append
    the query event. Best effort — never fails the ML handoff."""
    if ctx.tracer.enabled:
        from .obs.tracer import TRACE_DIR
        try:
            ctx.tracer.write_chrome(pp.conf.get(TRACE_DIR))
        except OSError:
            pass
    from .tools.event_log import log_query_event
    log_query_event(pp, ctx, wall_s)


def columnar_rdd(df) -> Iterator[Dict[str, object]]:
    """Execute the DataFrame's plan on device and yield per-batch
    column dicts of jax.Arrays, padded to the batch capacity with
    `row_count` marking live rows: fixed-width columns contribute a
    data lane + `<name>__valid`; string/binary columns contribute
    `<name>__offsets` + `<name>__chars` + `<name>__valid` (the ragged
    Arrow layout — still jax.Arrays, never wrapper objects)."""
    import time as _time

    from .exec.base import ExecCtx
    from .ops.gather import ensure_compacted
    from .planner import query_span
    pp = df._plan()
    ctx = ExecCtx(df._session.conf)
    _t0 = _time.perf_counter()
    # same lifecycle as collect_arrow: device admission for the whole
    # iteration, cleanups (shared-exchange handles) even on abandonment,
    # deferred device checks raised at the natural end-of-stream sync
    try:
        with query_span(ctx, pp.root), \
                ctx.mm.task_slot():  # admission (GpuSemaphore analog)
            for batch in pp.root.execute(ctx):
                batch = ensure_compacted(batch)
                out: Dict[str, object] = {"row_count": batch.row_count}
                for f, c in zip(batch.schema.fields, batch.columns):
                    if c.data is not None:
                        out[f.name] = c.data
                    elif c.offsets is not None and c.chars is not None:
                        out[f.name + "__offsets"] = c.offsets
                        out[f.name + "__chars"] = c.chars
                    else:
                        raise TypeError(
                            f"column {f.name} "
                            f"({f.dtype.simple_string()}) has no flat "
                            "device representation for columnar_rdd")
                    out[f.name + "__valid"] = c.validity
                yield out
    except BaseException:
        ctx.discard_deferred()  # a reused ctx must not report dead flags
        raise
    finally:
        ctx.run_cleanups()
    ctx.check_deferred()
    # ML pipelines must be visible to the qualification/profiling
    # tools too: collect() never runs on this path, so emit the query
    # event here (completed iterations only, mirroring collect())
    _emit_ml_query_event(pp, ctx, _time.perf_counter() - _t0)


def to_feature_matrix(df, feature_cols: List[str],
                      label_col: Optional[str] = None):
    """(features (n, f) float32 jax.Array, labels (n,) float32 | None,
    live (n,) bool) — one device-resident design matrix from the
    executed plan; nulls become 0.0 with the row kept (the reference's
    DMatrix treats missing via a sentinel; mask columns are available
    through columnar_rdd for trainers that model missingness)."""
    import time as _time

    import jax.numpy as jnp

    from .ops.concat import concat_batches
    from .exec.base import ExecCtx
    from .ops.gather import ensure_compacted
    from .planner import query_span
    pp = df._plan()
    ctx = ExecCtx(df._session.conf)
    _t0 = _time.perf_counter()
    try:
        with query_span(ctx, pp.root), \
                ctx.mm.task_slot():  # admission (GpuSemaphore analog)
            batches = [ensure_compacted(b)
                       for b in pp.root.execute(ctx)]
    except BaseException:
        ctx.discard_deferred()
        raise
    finally:
        ctx.run_cleanups()
    ctx.check_deferred()
    _emit_ml_query_event(pp, ctx, _time.perf_counter() - _t0)
    if not batches:
        raise ValueError("empty input")
    big = batches[0] if len(batches) == 1 else concat_batches(batches)
    big = ensure_compacted(big)
    name_to_col = {f.name: c for f, c in zip(big.schema.fields,
                                             big.columns)}
    feats = []
    for name in feature_cols:
        c = name_to_col[name]
        if c.data is None:
            raise TypeError(f"feature column {name} is not numeric")
        feats.append(jnp.where(c.validity, c.data, 0)
                     .astype(jnp.float32))
    X = jnp.stack(feats, axis=1)
    y = None
    if label_col is not None:
        lc = name_to_col[label_col]
        y = jnp.where(lc.validity, lc.data, 0).astype(jnp.float32)
    from .columnar.batch import row_mask
    live = row_mask(big.capacity, big.row_count)
    return X, y, live


def to_torch(df, feature_cols: List[str],
             label_col: Optional[str] = None):
    """Host handoff for torch-family trainers: (X (n, f) float32
    tensor, y | None) with padding rows dropped."""
    import jax
    import numpy as np
    import torch
    X, y, live = to_feature_matrix(df, feature_cols, label_col)
    Xh, yh, lh = jax.device_get((X, y, live))
    lh = np.asarray(lh)
    Xt = torch.from_numpy(np.ascontiguousarray(np.asarray(Xh)[lh]))
    yt = None if yh is None else torch.from_numpy(
        np.ascontiguousarray(np.asarray(yh)[lh]))
    return Xt, yt
