"""Basic physical operators: project, filter, range.

TPU analog of the reference's `basicPhysicalOperators.scala`
(`GpuProjectExec`, `GpuFilterExec`, `GpuRangeExec` — SURVEY.md §2.2-B;
reference mount empty). Filter is LAZY: it attaches a selection mask to
the batch (columnar/batch.py) instead of paying stream compaction; prefix
layout is restored by ensure_compacted only at consumers that need it
(SURVEY.md §7.1.3, §7.3.1).
"""
from __future__ import annotations

from typing import List, Optional, Sequence

import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

from .. import datatypes as dt
from ..columnar.batch import TpuBatch, bucket_rows
from ..columnar.column import TpuColumnVector
from ..expr.base import Alias, Expression, bind_expr
from .base import (ExecCtx, LeafExec, OpContract, TpuExec, UnaryExec,
                   fused_batches)

__all__ = ["TpuProjectExec", "TpuFilterExec", "TpuRangeExec",
           "output_schema_for", "bind_all"]


def output_schema_for(exprs: Sequence[Expression]) -> dt.Schema:
    fields = []
    for i, e in enumerate(exprs):
        name = e.name if hasattr(e, "name") else f"col{i}"
        fields.append(dt.StructField(name, e.dtype, e.nullable))
    return dt.Schema(fields)


def bind_all(exprs: Sequence[Expression], schema: dt.Schema) \
        -> List[Expression]:
    return [bind_expr(e, schema) for e in exprs]


class TpuProjectExec(UnaryExec):
    """Expression evaluation over each batch (GpuProjectExec analog)."""

    def __init__(self, exprs: Sequence[Expression], child: TpuExec):
        super().__init__(child)
        self.exprs = bind_all(exprs, child.output_schema)
        self._schema = output_schema_for(self.exprs)

    @property
    def output_schema(self):
        return self._schema

    def describe(self):
        return f"ProjectExec [{', '.join(map(repr, self.exprs))}]"

    def expressions(self):
        return self.exprs

    PRUNING_NOTE = ("keeps the expressions its parent reads; requires "
                    "their inputs")

    def child_requirements(self, required):
        from .pruning import refs
        return [refs(self.exprs[i] for i in required)]

    def pruned(self, children, maps, required):
        from .pruning import remap
        keep = sorted(required)
        if len(keep) == len(self.exprs) and children[0] is self.child:
            return self, {i: i for i in keep}
        # an unnamed expression is named after its position: keep it
        exprs = [e if hasattr(e, "name") else Alias(e, f.name)
                 for e, f in ((remap(self.exprs[i], maps[0]),
                               self._schema.fields[i]) for i in keep)]
        node = TpuProjectExec(exprs, children[0])
        return node, {o: i for i, o in enumerate(keep)}

    def _run(self, batch: TpuBatch, ectx) -> TpuBatch:
        cols = [e.eval_tpu(batch, ectx) for e in self.exprs]
        return TpuBatch(cols, self._schema, batch.row_count,
                        selection=batch.selection)

    def device_fn(self):
        return self._run

    def execute(self, ctx: ExecCtx):
        op_time = ctx.metric(self, "opTime")
        yield from fused_batches(self, ctx, tail_fn=self._run,
                                 metric=op_time)

    def execute_cpu(self, ctx: ExecCtx):
        from ..columnar.arrow_bridge import arrow_schema
        aschema = arrow_schema(self._schema)
        for rb in self.child.execute_cpu(ctx):
            arrays = [e.eval_cpu(rb, ctx.eval_ctx) for e in self.exprs]
            arrays = [a.combine_chunks() if isinstance(a, pa.ChunkedArray)
                      else a for a in arrays]
            yield pa.RecordBatch.from_arrays(arrays, schema=aschema)


class TpuFilterExec(UnaryExec):
    """Boolean-mask filter + stream compaction (GpuFilterExec analog)."""

    CONTRACT = OpContract(
        schema_preserving=True,
        notes="output rows are a subset of the input; schema passes "
              "through unchanged")

    def __init__(self, condition: Expression, child: TpuExec):
        super().__init__(child)
        self.condition = bind_expr(condition, child.output_schema)
        if not isinstance(self.condition.dtype, dt.BooleanType):
            raise TypeError(
                f"filter condition must be boolean, got "
                f"{self.condition.dtype.simple_string()}")

    def describe(self):
        return f"FilterExec [{self.condition!r}]"

    PRUNING_NOTE = "requires its predicate's columns and its parent's"

    def child_requirements(self, required):
        return self._passthrough_requirements(required)

    def pruned(self, children, maps, required):
        from .pruning import remap
        if children[0] is self.child:
            return self, maps[0]
        return TpuFilterExec(remap(self.condition, maps[0]),
                             children[0]), maps[0]

    def expressions(self):
        return (self.condition,)

    def _run(self, batch: TpuBatch, ectx) -> TpuBatch:
        pred = self.condition.eval_tpu(batch, ectx)
        # SQL filter keeps only rows where the predicate is TRUE (not null).
        keep = pred.data & pred.validity
        # Lazy filter: attach a selection mask instead of paying sort-based
        # stream compaction; consumers that need prefix layout compact via
        # ops.gather.ensure_compacted. Dead rows also become invalid so
        # every null-aware kernel (and any validity-gated ANSI error
        # check) skips them exactly as if they were gone.
        out = batch.with_selection(keep)
        out.columns = [c.with_arrays(validity=c.validity & keep)
                       for c in out.columns]
        return out

    def device_fn(self):
        return self._run

    def execute(self, ctx: ExecCtx):
        op_time = ctx.metric(self, "opTime")
        yield from fused_batches(self, ctx, tail_fn=self._run,
                                 metric=op_time)

    def execute_cpu(self, ctx: ExecCtx):
        for rb in self.child.execute_cpu(ctx):
            mask = self.condition.eval_cpu(rb, ctx.eval_ctx)
            mask = pc.fill_null(mask, False)
            yield rb.filter(mask)


class TpuRangeExec(LeafExec):
    """spark.range() source (GpuRangeExec analog): int64 sequence generated
    directly on device, split into bucketed batches."""

    FUSION_NOTE = "chain root: source leaf — fusable chains begin above it"

    def __init__(self, start: int, end: int, step: int = 1,
                 max_rows_per_batch: int = 1 << 20, name: str = "id"):
        super().__init__()
        if step == 0:
            raise ValueError("step must not be 0")
        self.start, self.end, self.step = start, end, step
        self.max_rows_per_batch = max_rows_per_batch
        self._schema = dt.Schema([dt.StructField(name, dt.INT64, False)])

    @property
    def output_schema(self):
        return self._schema

    def static_bytes_estimate(self):
        return self.num_rows * 8

    @property
    def num_rows(self) -> int:
        n = (self.end - self.start + self.step
             - (1 if self.step > 0 else -1)) // self.step
        return max(0, n)

    def describe(self):
        return f"RangeExec [{self.start}, {self.end}, step={self.step}]"

    def _chunks(self):
        total = self.num_rows
        off = 0
        while off < total:
            n = min(self.max_rows_per_batch, total - off)
            yield off, n
            off += n

    def execute(self, ctx: ExecCtx):
        for off, n in self._chunks():
            cap = bucket_rows(n)
            first = self.start + off * self.step
            data = first + jnp.arange(cap, dtype=jnp.int64) * self.step
            from ..columnar.batch import row_mask
            col = TpuColumnVector(dt.INT64, data=data,
                                  validity=row_mask(cap, n))
            yield TpuBatch([col], self._schema, n)

    def execute_cpu(self, ctx: ExecCtx):
        from ..columnar.arrow_bridge import arrow_schema
        aschema = arrow_schema(self._schema)
        for off, n in self._chunks():
            first = self.start + off * self.step
            vals = first + np.arange(n, dtype=np.int64) * self.step
            yield pa.RecordBatch.from_arrays([pa.array(vals, pa.int64())],
                                             schema=aschema)
