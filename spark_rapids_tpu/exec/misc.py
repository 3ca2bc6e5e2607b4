"""Union / Expand / Sample operators.

TPU analog of the reference's `GpuUnionExec`, `GpuExpandExec`,
`GpuSampleExec` (SURVEY.md §2.2-B "Expand/Generate/Union/Sample";
mount empty, capability-built).
"""
from __future__ import annotations

import time
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import pyarrow as pa

from .. import datatypes as dt
from ..columnar.batch import TpuBatch
from ..columnar.column import TpuColumnVector
from ..expr.base import Expression
from .base import ExecCtx, TpuExec, UnaryExec

__all__ = ["TpuUnionExec", "TpuExpandExec", "TpuSampleExec"]


class TpuUnionExec(TpuExec):
    """UNION ALL: children's batches streamed in child order. Children
    must share the output schema (the DataFrame layer inserts casts)."""

    FUSION_NOTE = ("barrier: multi-child operator — each child's "
                   "stream is its own fusable chain")

    def __init__(self, children: Sequence[TpuExec]):
        super().__init__()
        if not children:
            raise ValueError("union needs >= 1 child")
        self.children = tuple(children)
        first = children[0].output_schema
        for c in children[1:]:
            if c.output_schema.types != first.types:
                raise TypeError(
                    f"union children schemas differ: {first.types} vs "
                    f"{c.output_schema.types}")
        # Spark ORs nullability across children: a later nullable child
        # must not be masked by a non-nullable first schema
        self._schema = dt.Schema([
            dt.StructField(
                f.name, f.dtype,
                any(c.output_schema.fields[i].nullable
                    for c in children))
            for i, f in enumerate(first.fields)])

    @property
    def output_schema(self):
        return self._schema

    def expected_output_schema(self):
        # width/type agreement FIRST: the nullability any() below would
        # otherwise short-circuit on a nullable first-child field and
        # never index (i.e. never notice) a narrower rebuilt child. A
        # raise here surfaces as a named schema_mismatch rejection (the
        # verifier guards derivation hooks).
        first = self.children[0].output_schema
        for c in self.children[1:]:
            if c.output_schema.types != first.types:
                raise TypeError(
                    f"union children schemas differ: {first.types} vs "
                    f"{c.output_schema.types}")
        return dt.Schema([
            dt.StructField(
                f.name, f.dtype,
                any(c.output_schema.fields[i].nullable
                    for c in self.children))
            for i, f in enumerate(first.fields)])

    PRUNING_NOTE = ("requires the same ordinals of every child; "
                    "each child is projected to that one layout")

    def child_requirements(self, required):
        return [set(required) for _ in self.children]

    def pruned(self, children, maps, required):
        from .pruning import narrowed
        keep = sorted(required)
        if all(a is b for a, b in zip(children, self.children)):
            return self, {i: i for i in keep}
        kids = [narrowed(c, m, keep, force=True)[0]
                for c, m in zip(children, maps)]
        return TpuUnionExec(kids), {o: i for i, o in enumerate(keep)}

    def execute(self, ctx: ExecCtx):
        for c in self.children:
            yield from c.execute(ctx)

    def execute_cpu(self, ctx: ExecCtx):
        from ..columnar.arrow_bridge import arrow_schema
        target = arrow_schema(self._schema)
        for c in self.children:
            for rb in c.execute_cpu(ctx):
                if rb.schema != target:  # names may differ; types match
                    rb = pa.RecordBatch.from_arrays(
                        [rb.column(i) for i in range(rb.num_columns)],
                        schema=target)
                yield rb


class TpuExpandExec(UnaryExec):
    """Each input row expands through every projection list (the
    ROLLUP/CUBE/grouping-sets backbone). Emits one batch per projection
    per input batch — same multiset as Spark's row-interleaved output."""

    def __init__(self, projections: Sequence[Sequence[Expression]],
                 names: Sequence[str], child: TpuExec):
        super().__init__(child)
        from .basic import bind_all
        if not projections:
            raise ValueError("expand needs >= 1 projection")
        self.projections = [bind_all(p, child.output_schema)
                            for p in projections]
        width = len(self.projections[0])
        if any(len(p) != width for p in self.projections) \
                or len(names) != width:
            raise ValueError("projection widths/names mismatch")
        first = self.projections[0]
        self._schema = dt.Schema([
            dt.StructField(n, e.dtype,
                           any(p[i].nullable for p in self.projections))
            for i, (n, e) in enumerate(zip(names, first))])
        for p in self.projections[1:]:
            for i, e in enumerate(p):
                if e.dtype != first[i].dtype:
                    raise TypeError(
                        f"expand projection column {i} type mismatch")
        self._jits: List = [None] * len(self.projections)

    @property
    def output_schema(self):
        return self._schema

    def describe(self):
        return f"ExpandExec [{len(self.projections)} projections]"

    PRUNING_NOTE = ("requires the inputs of every projection list; "
                    "keeps its whole output")

    def child_requirements(self, required):
        from .pruning import refs
        return [refs(self.expressions())]

    def pruned(self, children, maps, required):
        from .pruning import identity_map, remap
        out = identity_map(len(self._schema.fields))
        if children[0] is self.child:
            return self, out
        return TpuExpandExec(
            [[remap(e, maps[0]) for e in p] for p in self.projections],
            self._schema.names, children[0]), out

    def expressions(self):
        return [e for p in self.projections for e in p]

    def _project(self, exprs, batch: TpuBatch, ectx) -> TpuBatch:
        cols = [e.eval_tpu(batch, ectx) for e in exprs]
        return TpuBatch(cols, self._schema, batch.row_count,
                        selection=batch.selection)

    def _run_all(self, batch: TpuBatch, ectx) -> TpuBatch:
        """Every projection over one batch as ONE traced map (the
        row-wise-map form stage fusion composes): compact the input
        once (traced — sort-based, no host sync), project each list,
        and concatenate the projected batches with the sync-free
        capacity-sum bound. Output capacity is static (projections x
        input capacity) and the multiset equals the per-projection
        ``execute`` path's — Spark's Expand contract is row-interleaved
        output whose ORDER downstream aggregation never depends on."""
        from ..columnar.batch import bucket_bytes, bucket_rows
        from ..ops.concat import concat_device
        from ..ops.gather import ensure_compacted
        batch = ensure_compacted(batch)
        parts = [self._project(tuple(p), batch, ectx)
                 for p in self.projections]
        out_cap = bucket_rows(len(parts) * batch.capacity)
        char_caps = []
        for ci in range(len(self._schema)):
            c = parts[0].columns[ci]
            if c.is_string_like:
                char_caps.append(bucket_bytes(max(sum(
                    p.columns[ci].chars.shape[0] for p in parts), 1)))
            else:
                char_caps.append(0)
        return concat_device(parts, out_cap, char_caps)

    def device_fn(self):
        """Expand IS a row-wise map once all projections emit into one
        batch (``_run_all``) — the audit's answer for the
        ROLLUP/CUBE backbone, so a partial aggregate above an expand
        fuses expand+partial into one program (and through the scan)."""
        return self._run_all

    def execute(self, ctx: ExecCtx):
        from functools import partial
        op_time = ctx.metric(self, "opTime")
        for batch in self.child.execute(ctx):
            t0 = time.perf_counter()
            for i, p in enumerate(self.projections):
                if self._jits[i] is None:
                    self._jits[i] = jax.jit(
                        partial(self._project, tuple(p)),
                        static_argnums=1)
                yield self._jits[i](batch, ctx.eval_ctx)
            op_time.value += time.perf_counter() - t0

    def execute_cpu(self, ctx: ExecCtx):
        from ..columnar.arrow_bridge import arrow_schema
        target = arrow_schema(self._schema)
        for rb in self.child.execute_cpu(ctx):
            for p in self.projections:
                arrays = [e.eval_cpu(rb, ctx.eval_ctx) for e in p]
                yield pa.RecordBatch.from_arrays(arrays, schema=target)


class TpuSampleExec(UnaryExec):
    """Bernoulli sample without replacement. Row selection is a
    deterministic hash of (seed, global row position) compared against
    the fraction — IDENTICAL on the device and oracle paths, so the
    dual-run harness compares exactly (Spark's XORShift sampler is
    per-partition-seeded and not bit-matched here; the row DISTRIBUTION
    contract is)."""

    FUSION_NOTE = ("barrier: row selection depends on GLOBAL row "
                   "positions accumulated across batches (host-side "
                   "running offset), not on one batch alone")

    def __init__(self, fraction: float, seed: int, child: TpuExec):
        super().__init__(child)
        if not 0.0 <= fraction <= 1.0:
            raise ValueError("fraction must be in [0, 1]")
        self.fraction = float(fraction)
        self.seed = int(seed)
        self._threshold = int(self.fraction * (1 << 32))
        self._jitted = None  # compile once across executions

    def describe(self):
        return f"SampleExec [fraction={self.fraction} seed={self.seed}]"

    PRUNING_NOTE = "requires its parent's columns"

    child_requirements = UnaryExec._parents_columns

    def pruned(self, children, maps, required):
        if children[0] is self.child:
            return self, maps[0]
        return TpuSampleExec(self.fraction, self.seed,
                             children[0]), maps[0]

    def _keep_mask(self, pos, xp):
        """ONE hash/threshold body for both paths (the dual-run contract
        needs them bit-identical): pos is int64 global row positions in
        the given array module."""
        from ..ops.hash import murmur3_int64
        lo = (pos & 0xffffffff).astype(xp.uint32)
        hi = (pos >> 32).astype(xp.uint32)
        h = murmur3_int64((lo, hi), xp.uint32(self.seed & 0xffffffff), xp)
        return h.astype(xp.uint32).astype(xp.int64) < self._threshold

    def _keep_mask_np(self, start: int, n: int):
        import numpy as np
        err = np.seterr(over="ignore")
        out = self._keep_mask(
            np.arange(start, start + n, dtype=np.int64), np)
        np.seterr(**err)
        return out

    def execute(self, ctx: ExecCtx):
        from ..ops.gather import compact_batch
        op_time = ctx.metric(self, "opTime")
        start = 0

        def keep_fn(start_, batch, ectx):
            pos = start_ + jnp.arange(batch.capacity, dtype=jnp.int64)
            return compact_batch(batch, self._keep_mask(pos, jnp))

        if self._jitted is None:
            self._jitted = jax.jit(keep_fn, static_argnums=2)
        jitted = self._jitted
        for batch in self.child.execute(ctx):
            from ..ops.gather import ensure_compacted
            batch = ensure_compacted(batch)  # global positions = prefix
            n = batch.num_rows
            t0 = time.perf_counter()
            yield jitted(jnp.int64(start), batch, ctx.eval_ctx)
            op_time.value += time.perf_counter() - t0
            start += n

    def execute_cpu(self, ctx: ExecCtx):
        import numpy as np
        start = 0
        for rb in self.child.execute_cpu(ctx):
            keep = self._keep_mask_np(start, rb.num_rows)
            idx = np.nonzero(keep)[0]
            yield rb.take(pa.array(idx, pa.int64()))
            start += rb.num_rows