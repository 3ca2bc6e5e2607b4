"""Group-by aggregation operator.

TPU analog of the reference's `aggregate.scala` (`GpuHashAggregateExec` —
SURVEY.md §2.2-B; reference mount empty), built the TPU-idiomatic way
(SURVEY.md §7.1.3): no device hash table — rows are sorted by group key,
segment ids come from key-change boundaries, and aggregate buffers are
segmented reduces. Two phases like the reference: a partial pass per input
batch, then partials are concatenated and merged (update -> merge ->
evaluate), which is exactly the shape a shuffle slots into later.
"""
from __future__ import annotations

import math
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import pyarrow as pa

from .. import datatypes as dt
from ..columnar.arrow_bridge import arrow_schema, arrow_to_device
from ..columnar.batch import TpuBatch, row_mask
from ..columnar.column import TpuColumnVector
from ..expr.aggregates import AggregateFunction
from ..expr.base import Alias, Expression, bind_expr
from ..ops.concat import concat_batches
from ..ops.gather import gather_column
from ..ops.sort_keys import segment_ids_for_keys
from ..programs import named_jit
from .base import ExecCtx, TpuExec, UnaryExec, fused_batches
from .basic import bind_all

__all__ = ["TpuHashAggregateExec"]


from ..ops.sort_keys import normalize_float_key_col as _normalize_float_keys


def _segment_starts(seg: jax.Array) -> jax.Array:
    """starts[g] = first sorted position of segment g — a searchsorted
    over the sorted ids (ops/segments.py), replacing the former
    compaction that paid a full 2-lane sort per aggregate batch."""
    from ..ops.segments import segment_starts_sorted
    return segment_starts_sorted(seg, seg.shape[0])


def _unalias(e: Expression) -> Tuple[AggregateFunction, str]:
    if isinstance(e, Alias):
        fn = e.child
        name = e.name
    else:
        fn = e
        name = fn.pretty_name().lower()
    if not isinstance(fn, AggregateFunction):
        raise TypeError(f"not an aggregate: {e!r}")
    return fn, name


class TpuHashAggregateExec(UnaryExec):
    """Sort-based group-by with partial/merge phases.

    One node runs both (``mode`` "complete", what every frontend builds);
    a gang of one task per chip (exec/gang.py) splits it as Spark's
    planner does, around an exchange of the partial buffers:
    ``as_partial`` (update, and merge what a task's batches gave into ONE
    batch of the partial-buffer schema) below, ``as_final`` (merge,
    evaluate) above."""

    FUSION_NOTE = ("barrier: grouped reduction ACROSS batches; the "
                   "per-batch PARTIAL phase fuses as a chain tail "
                   "(fused_batches tail_fn) — scan-rooted, "
                   "decode->filter->project->partial-agg is one program")

    def __init__(self, group_exprs: Sequence[Expression],
                 agg_exprs: Sequence[Expression], child: TpuExec):
        super().__init__(child)
        self.group_exprs = bind_all(group_exprs, child.output_schema)
        self.aggs: List[AggregateFunction] = []
        self.agg_names: List[str] = []
        for e in agg_exprs:
            bound = bind_expr(e, child.output_schema)
            fn, name = _unalias(bound)
            self.aggs.append(fn)
            self.agg_names.append(name)

        from .basic import output_schema_for
        gfields = list(output_schema_for(self.group_exprs).fields)
        afields = [dt.StructField(n, a.dtype, a.nullable)
                   for a, n in zip(self.aggs, self.agg_names)]
        self._schema = dt.Schema(gfields + afields)
        # partial buffer schema: group keys + per-agg buffer lanes
        bfields = list(gfields)
        self._buf_slices: List[Tuple[int, int]] = []
        off = len(gfields)
        for i, a in enumerate(self.aggs):
            bf = a.buffer_fields
            self._buf_slices.append((off, off + len(bf)))
            bfields.extend(dt.StructField(f"_b{i}_{f.name}", f.dtype,
                                          f.nullable) for f in bf)
            off += len(bf)
        self._partial_schema = dt.Schema(bfields)
        self.mode = "complete"
        self._jit_partial = None
        self._jit_final = None
        self._jit_merge = None
        self._jit_single = None

    @property
    def output_schema(self):
        return self._partial_schema if self.mode == "partial" \
            else self._schema

    def _in_mode(self, mode: str, child: TpuExec):
        import copy
        clone = copy.copy(self)
        clone.children = (child,)
        clone.mode = mode
        clone.__dict__.pop("_fused_jit_cache", None)
        return clone

    def as_partial(self, child: TpuExec) -> "TpuHashAggregateExec":
        """The update half over ``child`` (this node's child's schema):
        yields at most one batch, in the partial-buffer schema."""
        clone = self._in_mode("partial", child)
        clone.__dict__.pop("_op_id", None)  # a node of no planned tree
        return clone

    def as_final(self, child: TpuExec) -> "TpuHashAggregateExec":
        """The merge-and-evaluate half over a child that yields batches
        in the partial-buffer schema (an exchange of ``as_partial``s)."""
        return self._in_mode("final", child)

    def resident_footprint(self):
        # collect_* / exact-percentile aggregates concatenate the whole
        # input on device before the single-pass group sort
        return any(getattr(a, "single_pass", False) for a in self.aggs)

    def describe(self):
        g = ", ".join(map(repr, self.group_exprs))
        a = ", ".join(f"{type(x).__name__.lower()}({', '.join(map(repr, x.children))})"
                      for x in self.aggs)
        mode = "" if self.mode == "complete" else f" mode={self.mode}"
        return f"HashAggregateExec [keys=[{g}] aggs=[{a}]{mode}]"

    def tpu_supported_conf(self, conf):
        """Conf-dependent eligibility (planner hook): float aggregation
        results can vary with reduction order vs CPU Spark; when
        spark.rapids.sql.variableFloatAgg.enabled is false those
        aggregates stay on CPU (reference semantics)."""
        from ..config import VARIABLE_FLOAT_AGG
        if conf.get(VARIABLE_FLOAT_AGG):
            return None
        for a in self.aggs:
            if a.children and dt.is_floating(a.children[0].dtype):
                return (f"float aggregation {a.pretty_name()} disabled "
                        "by spark.rapids.sql.variableFloatAgg.enabled")
        return None

    def tpu_supported(self):
        if any(getattr(a, "single_pass", False) for a in self.aggs):
            # the single-pass path concatenates the whole child input
            from ..ops.concat import device_concat_supported
            for f in self.child.output_schema.fields:
                if not device_concat_supported(f.dtype):
                    return (f"collect_* with nested input column "
                            f"{f.name} needs nested device concat")
        for e in self.group_exprs:
            if dt.is_nested(e.dtype):
                return (f"grouping by nested type "
                        f"{e.dtype.simple_string()} not on device")
        for a in self.aggs:
            for c in a.children:
                if dt.is_nested(c.dtype):
                    return (f"aggregating nested type "
                            f"{c.dtype.simple_string()} not on device")
            r = a.tpu_supported()
            if r:
                return r
        return None

    def expressions(self):
        return list(self.group_exprs) + list(self.aggs)

    PRUNING_NOTE = ("requires the inputs of its grouping keys and "
                    "aggregates; keeps its whole output")

    def child_requirements(self, required):
        from .pruning import refs
        return [refs(self.expressions())]

    def pruned(self, children, maps, required):
        from ..expr.base import Alias
        from .pruning import identity_map, remap
        out = identity_map(len(self._schema.fields))
        if children[0] is self.child:
            return self, out
        aggs = [Alias(remap(a, maps[0]), n)
                for a, n in zip(self.aggs, self.agg_names)]
        return TpuHashAggregateExec(
            [remap(e, maps[0]) for e in self.group_exprs], aggs,
            children[0]), out

    # --- device phases ----------------------------------------------------

    def _group_and_gather(self, key_cols, extra_cols, live):
        """Sort by keys; returns (sorted key cols, sorted extra col lists,
        seg, sorted_live, num_groups, starts)."""
        cap = live.shape[0]
        if key_cols:
            perm, seg, num_groups = segment_ids_for_keys(key_cols, live)
            sorted_live = live[perm]
            skeys = [gather_column(c, perm, sorted_live) for c in key_cols]
            sextras = [[gather_column(c, perm, sorted_live) for c in cols]
                       for cols in extra_cols]
        else:
            # global aggregate: one segment; seg=None selects the
            # plain-reduction path in the agg functions (segment_* is a
            # scatter-add, ~100ms per 2M rows on TPU) with GLOBAL_LANES
            # output lanes
            from ..expr.aggregates import GLOBAL_LANES
            seg = None
            num_groups = jnp.int32(1)
            sorted_live = live
            skeys = []
            sextras = extra_cols
            out_live = row_mask(GLOBAL_LANES, num_groups)
            return skeys, sextras, seg, sorted_live, num_groups, out_live
        out_live = row_mask(cap, num_groups)
        return skeys, sextras, seg, sorted_live, num_groups, out_live

    def _partial(self, batch: TpuBatch, ectx) -> TpuBatch:
        live = batch.live_mask()
        key_cols = [_normalize_float_keys(e.eval_tpu(batch, ectx))
                    for e in self.group_exprs]
        val_cols = [[c.eval_tpu(batch, ectx) for c in a.children]
                    for a in self.aggs]
        skeys, svals, seg, sorted_live, ng, out_live = \
            self._group_and_gather(key_cols, val_cols, live)
        out_cols = []
        if skeys:
            starts = _segment_starts(seg)
            out_cols = [gather_column(k, starts, out_live) for k in skeys]
        for a, sv in zip(self.aggs, svals):
            out_cols.extend(a.update_device(sv, seg, sorted_live, out_live))
        return TpuBatch(out_cols, self._partial_schema, ng)

    def _final(self, pbatch: TpuBatch, ectx) -> TpuBatch:
        live = pbatch.live_mask()
        nkeys = len(self.group_exprs)
        key_cols = pbatch.columns[:nkeys]
        buf_cols = [[pbatch.columns[i] for i in range(lo, hi)]
                    for lo, hi in self._buf_slices]
        skeys, sbufs, seg, sorted_live, ng, out_live = \
            self._group_and_gather(key_cols, buf_cols, live)
        out_cols = []
        if skeys:
            starts = _segment_starts(seg)
            out_cols = [gather_column(k, starts, out_live) for k in skeys]
        for a, sb in zip(self.aggs, sbufs):
            merged = a.merge_device(sb, seg, sorted_live, out_live)
            out_cols.append(a.evaluate_device(merged))
        return TpuBatch(out_cols, self._schema, ng)

    def _merge_only(self, pbatch: TpuBatch, ectx) -> TpuBatch:
        """Merge partial buffers WITHOUT the final evaluate — the rolling
        reduction step of the bounded out-of-core merge (output stays in
        the partial-buffer schema and can be merged again)."""
        live = pbatch.live_mask()
        nkeys = len(self.group_exprs)
        key_cols = pbatch.columns[:nkeys]
        buf_cols = [[pbatch.columns[i] for i in range(lo, hi)]
                    for lo, hi in self._buf_slices]
        skeys, sbufs, seg, sorted_live, ng, out_live = \
            self._group_and_gather(key_cols, buf_cols, live)
        out_cols = []
        if skeys:
            starts = _segment_starts(seg)
            out_cols = [gather_column(k, starts, out_live) for k in skeys]
        for a, sb in zip(self.aggs, sbufs):
            out_cols.extend(a.merge_device(sb, seg, sorted_live, out_live))
        return TpuBatch(out_cols, self._partial_schema, ng)

    def _merge_bounded(self, partials, ctx: ExecCtx):
        """Reduce the partials list under the HBM budget: concat+merge in
        groups whose bytes fit the merge window, shrink each result to its
        live group count, repeat until one remains (the reference's
        'iterative partial->merge loop concatenates ... when over target
        batch size' — SURVEY.md §3.3; no unbounded concat)."""
        from ..columnar.batch import bucket_rows
        from ..ops.gather import shrink_batch
        if self._jit_merge is None:
            self._jit_merge = named_jit("agg_merge", self._merge_only,
                                        static_argnums=1)
        window = max(1, ctx.mm.budget // 4)
        spill = ctx.metric(self, "spillTime")
        while len(partials) > 1:
            t0 = time.perf_counter()
            group = [partials.pop(0)]
            gbytes = group[0].device_size_bytes()
            while partials:
                nb = partials[0].device_size_bytes()
                if len(group) >= 2 and gbytes + nb > window:
                    break
                group.append(partials.pop(0))
                gbytes += nb
            from ..ops.concat import concat_batches_bounded
            merged = self._jit_merge(concat_batches_bounded(group),
                                     ctx.eval_ctx)
            ng = merged.num_rows  # sync: shrink to live groups
            merged = shrink_batch(merged, bucket_rows(max(ng, 128)))
            partials.append(merged)
            spill.value += time.perf_counter() - t0
        return partials[0]

    def _empty_child_batch(self) -> TpuBatch:
        cschema = self.child.output_schema
        rb = pa.RecordBatch.from_arrays(
            [pa.array([], type=dt.to_arrow(f.dtype)) for f in cschema],
            schema=arrow_schema(cschema))
        return arrow_to_device(rb, cschema)

    # --- single-pass path (collect_list/collect_set) ----------------------

    @staticmethod
    def _value_sorted_groups(scol, seg, sorted_live, dedupe: bool):
        """Shared single-pass layout (collect_* AND approx_percentile):
        one more sort puts (valid, group, value) in order, compaction
        drops nulls (and set-duplicates), and kept rows' group ids are
        searchsorted-able — sort/scan/gather only, no scatters
        (SURVEY.md §7.1.3). Returns (perm2, cidx, ccount, kseg,
        elem_live)."""
        from ..ops.gather import compaction_indices
        from ..ops.sort_keys import orderable_int, string_order_ranks
        cap = sorted_live.shape[0]
        valid = scol.validity & sorted_live
        if scol.is_string_like:
            lane = string_order_ranks(scol, valid).astype(jnp.int64)
        elif scol.data is None:
            lane = jnp.zeros((cap,), jnp.int64)
        else:
            lane = jnp.where(valid, orderable_int(scol).astype(jnp.int64),
                             jnp.int64(0))
        drop = jnp.where(valid, jnp.int8(0), jnp.int8(1))
        segl = seg if seg is not None else jnp.zeros((cap,), jnp.int32)
        idx = jnp.arange(cap, dtype=jnp.int32)
        sdrop, sseg, slane, perm2 = jax.lax.sort(
            (drop, segl, lane, idx), num_keys=4)
        keep = sdrop == 0
        if dedupe:
            first = jnp.concatenate([
                jnp.ones((1,), jnp.bool_),
                (sseg[1:] != sseg[:-1]) | (slane[1:] != slane[:-1])])
            keep = keep & first
        cidx, ccount = compaction_indices(keep)
        elem_live = idx < ccount
        # kept rows' group ids in compact prefix; padding pinned past
        # every group so searchsorted lands on ccount
        kseg = jnp.where(elem_live, sseg[cidx], jnp.int32(cap))
        return perm2, cidx, ccount, kseg, elem_live

    def _collect_column(self, agg, scol, seg, sorted_live, out_cap,
                        out_live):
        """collect_list/set ARRAY column over the shared single-pass
        layout; per-group offsets are a searchsorted over kseg."""
        from ..ops.gather import gather_column
        perm2, cidx, _, kseg, elem_live = self._value_sorted_groups(
            scol, seg, sorted_live, agg.dedupe)
        elem = gather_column(scol, perm2[cidx], elem_live)
        offsets = jnp.searchsorted(
            kseg, jnp.arange(out_cap + 1, dtype=jnp.int32),
            side="left").astype(jnp.int32)
        return TpuColumnVector(agg.dtype, validity=out_live,
                               offsets=offsets, children=[elem])

    def _single_pass(self, batch: TpuBatch, ectx) -> TpuBatch:
        live = batch.live_mask()
        key_cols = [_normalize_float_keys(e.eval_tpu(batch, ectx))
                    for e in self.group_exprs]
        val_cols = [[c.eval_tpu(batch, ectx) for c in a.children]
                    for a in self.aggs]
        skeys, svals, seg, sorted_live, ng, out_live = \
            self._group_and_gather(key_cols, val_cols, live)
        out_cap = out_live.shape[0]
        out_cols = []
        if skeys:
            starts = _segment_starts(seg)
            out_cols = [gather_column(k, starts, out_live) for k in skeys]
        from ..expr.aggregates import ApproxPercentile
        for a, sv in zip(self.aggs, svals):
            if isinstance(a, ApproxPercentile):
                out_cols.append(self._percentile_column(
                    a, sv[0], seg, sorted_live, out_cap, out_live))
            elif getattr(a, "single_pass", False):
                out_cols.append(self._collect_column(
                    a, sv[0], seg, sorted_live, out_cap, out_live))
            else:
                bufs = a.update_device(sv, seg, sorted_live, out_live)
                out_cols.append(a.evaluate_device(bufs))
        return TpuBatch(out_cols, self._schema, ng)

    def _percentile_column(self, agg, scol, seg, sorted_live, out_cap,
                           out_live):
        """approx_percentile over the shared single-pass layout: group
        edges come from searchsorted over the kept rows' group ids, and
        each requested percentile is a rank gather at edge+rank — exact,
        no sketch (expr/aggregates.py ApproxPercentile docstring)."""
        from ..ops.gather import gather_column
        cap = sorted_live.shape[0]
        perm2, cidx, _, kseg, _ = self._value_sorted_groups(
            scol, seg, sorted_live, dedupe=False)
        g = jnp.arange(out_cap, dtype=jnp.int32)
        lo = jnp.searchsorted(kseg, g, side="left").astype(jnp.int32)
        hi = jnp.searchsorted(kseg, g, side="right").astype(jnp.int32)
        n_g = hi - lo
        picked = []
        for p in agg.percentages:
            # Spark's ceil(p*n) 1-based rank (ApproxPercentile.rank0)
            r0 = jnp.clip(jnp.ceil(p * n_g).astype(jnp.int32) - 1, 0,
                          jnp.maximum(n_g - 1, 0))
            pos = jnp.clip(lo + r0, 0, cap - 1)
            picked.append(perm2[jnp.clip(cidx[pos], 0, cap - 1)])
        has_vals = out_live & (n_g > 0)
        if not agg.is_list:
            return gather_column(scol, picked[0], has_vals)
        k = len(agg.percentages)
        src = jnp.stack(picked, axis=1).reshape(-1)  # (out_cap*k,)
        elem_valid = jnp.repeat(has_vals, k)
        elem = gather_column(scol, src, elem_valid)
        offsets = (jnp.arange(out_cap + 1, dtype=jnp.int32) * k)
        return TpuColumnVector(agg.dtype, validity=has_vals,
                               offsets=offsets, children=[elem])

    def _execute_single_pass(self, ctx: ExecCtx):
        """collect_* cannot partial/merge (variable-length buffers have
        no device concat): group the WHOLE input in one pass. The input
        accumulates as spillable catalog entries, and when its total
        exceeds the HBM budget (the one-pass concat+sort cannot fit) the
        exec reroutes the ALREADY-PRODUCED batches (downloaded, not
        recomputed) plus the rest of the device stream into the CPU
        grouping and uploads the result — a runtime gate, since
        tpu_supported() sees only types (ADVICE r3 #4). The threshold is
        budget/2: the one-pass path concats a second full copy of the
        input off-ledger."""
        if self._jit_single is None:
            self._jit_single = named_jit("agg_single", self._single_pass,
                                         static_argnums=1)
        op_time = ctx.metric(self, "opTime")
        from ..columnar.arrow_bridge import device_to_arrow
        sbs, total = [], 0
        over = False
        stream = fused_batches(self, ctx)
        batches = []
        try:
            for b in stream:
                total += b.device_size_bytes()
                sbs.append(ctx.mm.register(b))
                if total > ctx.mm.budget // 2:
                    over = True
                    break
            if over:
                # ownership transfers to the reroute generator HERE,
                # inside the guard: its finally releases whatever the
                # CPU path never consumed [ledger-leak-path]
                def downloaded():
                    pending = list(sbs)
                    try:
                        while pending:
                            rb = pending[0].get_host()
                            pending.pop(0).release()
                            yield rb
                        for b in stream:  # same device stream, cont'd
                            yield device_to_arrow(b)
                    finally:
                        for sb in pending:
                            sb.release()
            else:
                t0 = time.perf_counter()
                for sb in sbs:
                    batches.append(sb.get())
                    sb.release()
        except BaseException:
            # a raising child stream (or failed re-upload) must not
            # strand the accumulated input in the process-shared
            # catalog; release() is idempotent, so already-consumed
            # entries are fine [ledger-leak-path]
            for sb in sbs:
                sb.release()
            raise
        if over:
            for rb in self._cpu_aggregate(downloaded(), ctx):
                yield arrow_to_device(rb, self._schema)
            return
        if not batches:
            if self.group_exprs:
                return
            batches = [self._empty_child_batch()]
        merged = concat_batches(batches)
        out = self._jit_single(merged, ctx.eval_ctx)
        if ctx.sync_metrics:
            out.block_until_ready()
        op_time.value += time.perf_counter() - t0
        yield out

    def _wants_single_pass(self, ctx: ExecCtx) -> bool:
        """collect_* always single-pass (no fixed-width merge buffers);
        approx_percentile single-pass only under the exact conf — with
        spark.rapids.sql.approxPercentile.exact=false it rides the
        ordinary partial/merge phases via its mergeable quantile summary
        (VERDICT r4 #6)."""
        from ..config import APPROX_PERCENTILE_EXACT
        from ..expr.aggregates import ApproxPercentile
        exact = ctx.conf.get(APPROX_PERCENTILE_EXACT)
        for a in self.aggs:
            if not getattr(a, "single_pass", False):
                continue
            if isinstance(a, ApproxPercentile) and not exact:
                # the sketch merge builds (segment, mass) compound int64
                # keys with a 2^42 stride; capacities past the stride's
                # headroom would overflow, so oversized plans fall back
                # to the exact single-pass path instead
                if ctx.conf.batch_size_rows * int(a._MASS_SCALE) \
                        <= (1 << 63) - 1:
                    continue
            return True
        return False

    def execute(self, ctx: ExecCtx):
        if self._wants_single_pass(ctx):
            yield from self._execute_single_pass(ctx)
            return
        if self._jit_partial is None:
            self._jit_partial = named_jit("agg_partial", self._partial,
                                          static_argnums=1)
            self._jit_final = named_jit("agg_final", self._final,
                                        static_argnums=1)
        op_time = ctx.metric(self, "opTime")
        if self.mode == "final":  # the child hands over partial buffers
            partials = list(self.child.execute(ctx))
        else:
            # the partial phase fuses with the project/filter chain
            # feeding it into one XLA program per batch (fused_batches)
            partials = list(fused_batches(self, ctx, tail_fn=self._partial,
                                          metric=op_time))
        t0 = time.perf_counter()
        if not partials:
            if self.group_exprs:
                op_time.value += time.perf_counter() - t0
                return
            partials = [self._jit_partial(self._empty_child_batch(),
                                          ctx.eval_ctx)]
        if self.mode == "partial":
            if len(partials) > 1:  # one block a task for the exchange
                if self._jit_merge is None:
                    self._jit_merge = named_jit(
                        "agg_merge", self._merge_only, static_argnums=1)
                from ..ops.concat import concat_batches_bounded
                partials = [self._jit_merge(
                    concat_batches_bounded(partials), ctx.eval_ctx)]
            op_time.value += time.perf_counter() - t0
            yield partials[0]
            return
        if not self.group_exprs:
            from ..ops.concat import concat_batches_bounded
            merged = concat_batches_bounded(partials)
        elif sum(p.device_size_bytes() for p in partials) \
                > ctx.mm.budget // 4:
            merged = self._merge_bounded(partials, ctx)
        elif sum(p.capacity for p in partials) > ctx.conf.batch_size_rows:
            # A partial keeps its INPUT's capacity however few groups it
            # holds, so the capacity-bounded concat below would hand the
            # final a batch wider than the engine's own batch bound made
            # of padding alone (8 partials of 2^20: an 8M-row sort to
            # merge 12 groups; 32 of them: 32M). Past that bound, size by
            # the live group counts instead: one readback for all of them
            # (~0.6 ms dispatch+block on the v5e: chip run, PR 21).
            merged = concat_batches(partials)
        else:
            # capacity-bounded concat: sync-free (no row-count readback),
            # so a small partial->final pipeline never waits on the
            # device mid-query; the final's sort tolerates the padding
            from ..ops.concat import concat_batches_bounded
            merged = concat_batches_bounded(partials)
        out = self._jit_final(merged, ctx.eval_ctx)
        if ctx.sync_metrics:
            out.block_until_ready()
        op_time.value += time.perf_counter() - t0
        yield out

    # --- CPU oracle -------------------------------------------------------

    def execute_cpu(self, ctx: ExecCtx):
        yield from self._cpu_aggregate(self.child.execute_cpu(ctx), ctx)

    def _cpu_aggregate(self, rbs, ctx: ExecCtx):
        """CPU grouping over an iterable of RecordBatches in the child's
        output schema (the oracle body; also the over-budget collect_*
        fallback's sink for already-computed device batches)."""
        groups: Dict[tuple, list] = {}
        key_values: Dict[tuple, tuple] = {}

        def norm_key(v):
            if isinstance(v, float):
                if math.isnan(v):
                    return "\x00__NaN__"
                if v == 0.0:
                    return 0.0
            return v

        for rb in rbs:
            n = rb.num_rows
            kcols = [e.eval_cpu(rb, ctx.eval_ctx).to_pylist()
                     for e in self.group_exprs]
            vcols = [[c.eval_cpu(rb, ctx.eval_ctx).to_pylist()
                      for c in a.children] for a in self.aggs]
            for r in range(n):
                raw = tuple(k[r] for k in kcols)
                key = tuple(norm_key(v) for v in raw)
                if key not in groups:
                    groups[key] = [[] for _ in self.aggs]
                    key_values[key] = tuple(
                        float("nan") if isinstance(v, float)
                        and math.isnan(v) else
                        (0.0 if isinstance(v, float) and v == 0.0 else v)
                        for v in raw)
                bucket = groups[key]
                for ai, a in enumerate(self.aggs):
                    if a.children:
                        bucket[ai].append(vcols[ai][0][r])
                    else:
                        bucket[ai].append(True)  # count(*) placeholder

        if not groups and not self.group_exprs:
            groups[()] = [[] for _ in self.aggs]
            key_values[()] = ()

        out_rows_keys = []
        out_rows_aggs = []
        for key, buckets in groups.items():
            out_rows_keys.append(key_values[key])
            out_rows_aggs.append([a.cpu_agg(vals, ctx.eval_ctx)
                                  for a, vals in zip(self.aggs, buckets)])
        arrays = []
        for i, f in enumerate(self._schema.fields):
            nk = len(self.group_exprs)
            if i < nk:
                vals = [r[i] for r in out_rows_keys]
            else:
                vals = [r[i - nk] for r in out_rows_aggs]
            arrays.append(pa.array(vals, type=dt.to_arrow(f.dtype)))
        yield pa.RecordBatch.from_arrays(arrays,
                                         schema=arrow_schema(self._schema))
