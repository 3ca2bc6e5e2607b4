"""Sort and limit operators.

TPU analog of the reference's `GpuSortExec` / `limit.scala`
(`GpuTopN`, `GpuGlobalLimitExec`, `GpuLocalLimitExec`,
`GpuTakeOrderedAndProjectExec` — SURVEY.md §2.2-B; reference mount empty).
Sort = key normalization + one `lax.sort` permutation + batch gather
(SURVEY.md §7.1.3); global sort concatenates the child's batches on device
first (out-of-core merge comes with the spill framework).
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import List, Optional, Sequence

import jax
import pyarrow as pa

from .. import datatypes as dt
from ..columnar.batch import TpuBatch
from ..expr.base import Expression, bind_expr
from ..ops.concat import concat_batches
from ..ops.gather import gather_batch
from ..ops.sort_keys import SortSpec, sort_permutation
from ..programs import named_jit
from .base import ExecCtx, OpContract, TpuExec, UnaryExec, fused_batches

__all__ = ["SortOrder", "TpuSortExec", "TpuLocalLimitExec",
           "TpuGlobalLimitExec", "TpuTopNExec", "sort_batch_by",
           "cpu_sort_table"]


@dataclasses.dataclass(frozen=True)
class SortOrder:
    """Sort key: expression + direction + null placement (GpuSortOrder).
    Frozen/hashable so order tuples can be jit static arguments."""
    child: Expression
    ascending: bool = True
    nulls_first: Optional[bool] = None  # Spark default: asc <=> nulls first

    def __post_init__(self):
        if self.nulls_first is None:
            object.__setattr__(self, "nulls_first", self.ascending)

    @property
    def spec(self) -> SortSpec:
        return SortSpec(self.ascending, self.nulls_first)


def sort_batch_by(batch: TpuBatch, orders: Sequence[SortOrder],
                  ectx, limit: Optional[int] = None) -> TpuBatch:
    """Traced: sort one batch by the given (bound) orders; optional
    row-count truncation (kept inside the jit — an eager op would pay a
    dispatch round-trip per batch)."""
    import jax.numpy as jnp
    key_cols = [o.child.eval_tpu(batch, ectx) for o in orders]
    live = batch.live_mask()
    perm = sort_permutation(key_cols, [o.spec for o in orders], live)
    if batch.selection is None:
        rc = batch.row_count
    else:
        # lazy-filter batch: dead rows sort last (live-rank lane), so the
        # live count is the new prefix length — sort absorbs compaction
        rc = jnp.sum(live.astype(jnp.int32))
    if limit is not None:
        rc = jnp.minimum(rc, jnp.int32(limit))
    return gather_batch(batch, perm, rc)


# --- CPU oracle sort (Spark semantics over host rows) ---------------------

def _nested_cpu_key(v):
    """Recursive comparable for nested values: null-first, NaN-largest,
    -0.0==0.0; tuples give Spark's field-wise / element-wise-then-length
    ordering."""
    if v is None:
        return (0,)
    if isinstance(v, dict):
        return (1,) + tuple(_nested_cpu_key(x) for x in v.values())
    if isinstance(v, (list, tuple)):
        return (1,) + tuple(_nested_cpu_key(x) for x in v)
    if isinstance(v, float):
        return (1, (1, 0.0)) if math.isnan(v) else (1, (0, v + 0.0))
    return (1, (0, v))


def _cpu_pass_key(t: dt.DataType):
    """Per-value comparable for one sort pass; None handled separately."""
    if dt.is_nested(t):
        return _nested_cpu_key
    if dt.is_floating(t):
        return lambda v: (1, 0.0) if (isinstance(v, float)
                                      and math.isnan(v)) else (0, v + 0.0)
    return lambda v: v


def cpu_sort_table(table: pa.Table, key_arrays: List[pa.Array],
                   orders: Sequence[SortOrder]) -> pa.Table:
    """Stable multi-pass sort of host rows with Spark null/NaN semantics."""
    n = table.num_rows
    idx = list(range(n))
    for o, arr in reversed(list(zip(orders, key_arrays))):
        vals = arr.to_pylist()
        keyf = _cpu_pass_key(o.child.dtype)
        # Direction applies to values only; nulls keep their placement:
        # split the (stable) order into null/non-null blocks per pass.
        nulls = [i for i in idx if vals[i] is None]
        nonnull = [i for i in idx if vals[i] is not None]
        nonnull.sort(key=lambda i: keyf(vals[i]), reverse=not o.ascending)
        idx = nulls + nonnull if o.nulls_first else nonnull + nulls
    return table.take(pa.array(idx, pa.int64()))


class TpuSortExec(UnaryExec):
    """Total or per-batch sort (GpuSortExec analog)."""

    CONTRACT = OpContract(
        schema_preserving=True,
        notes="reorders rows only; sort keys must be primitive")

    FUSION_NOTE = ("barrier: total order is a cross-batch property "
                   "(global merge / out-of-core runs); the TopN "
                   "pre-pass fuses instead (_PerBatchTopN.device_fn)")

    def __init__(self, orders: Sequence[SortOrder], child: TpuExec,
                 global_sort: bool = True):
        super().__init__(child)
        self.orders = [dataclasses.replace(
            o, child=bind_expr(o.child, child.output_schema))
            for o in orders]
        self.global_sort = global_sort
        self._jitted = None

    def describe(self):
        keys = ", ".join(
            f"{o.child!r} {'ASC' if o.ascending else 'DESC'} NULLS "
            f"{'FIRST' if o.nulls_first else 'LAST'}" for o in self.orders)
        return f"SortExec [{keys}] global={self.global_sort}"

    def tpu_supported(self):
        from ..ops.concat import device_concat_supported
        for o in self.orders:
            if dt.is_nested(o.child.dtype):
                return (f"sorting by nested type "
                        f"{o.child.dtype.simple_string()} not on device")
        if self.global_sort:
            # the global merge concatenates batches on device
            for f in self.child.output_schema.fields:
                if not device_concat_supported(f.dtype):
                    return (f"global sort with payload column {f.name} "
                            f"({f.dtype.simple_string()}) needs nested "
                            "device concat")
        return None

    def expressions(self):
        return [o.child for o in self.orders]

    PRUNING_NOTE = ("requires its order keys and its parent's columns; "
                    "a fusable child is narrowed to them")

    def child_requirements(self, required):
        return self._passthrough_requirements(required)

    def pruned(self, children, maps, required):
        from .pruning import narrowed, remap_order
        if children[0] is self.child:
            return self, maps[0]
        child, m = narrowed(children[0], maps[0],
                            self.child_requirements(required)[0])
        return TpuSortExec([remap_order(o, m) for o in self.orders],
                           child, global_sort=self.global_sort), m

    def execute(self, ctx: ExecCtx):
        if self._jitted is None:
            self._jitted = named_jit(
                "sort_batch", lambda b, orders, ectx: sort_batch_by(
                    b, orders, ectx), static_argnums=(1, 2))
        op_time = ctx.metric(self, "opTime")
        orders = tuple(self.orders)
        if self.global_sort:
            batches = list(self.child.execute(ctx))
            if not batches:
                return
            total_bytes = sum(b.device_size_bytes() for b in batches)
            if len(batches) > 1 and total_bytes > ctx.mm.budget // 2:
                # holding input + concat + sorted copies would blow the
                # HBM budget: external sort over host-spilled runs
                yield from self._sort_out_of_core(batches, orders, ctx)
                return
            t0 = time.perf_counter()
            # bounded concat: sync-free (an exact-size readback here
            # would drain the dispatch stream mid-query; its cost on
            # the chip is not measured)
            from ..ops.concat import concat_batches_bounded
            merged = concat_batches_bounded(batches)
            out = self._jitted(merged, orders, ctx.eval_ctx)
            if ctx.sync_metrics:
                out.block_until_ready()
            op_time.value += time.perf_counter() - t0
            yield out
        else:
            for batch in self.child.execute(ctx):
                t0 = time.perf_counter()
                out = self._jitted(batch, orders, ctx.eval_ctx)
                op_time.value += time.perf_counter() - t0
                yield out

    # --- out-of-core global sort -----------------------------------------

    def _sort_out_of_core(self, batches, orders, ctx: ExecCtx):
        """External sort (SURVEY.md §5.7: 'out-of-core sort: sort each
        spillable batch, n-way merge'), the TPU-idiomatic way:

        1. sort each batch on device, register it spillable, spill to host
           Arrow (the runs). Runs ride :class:`SpillableBatch`, so a run
           the host tier cascades to disk lands as a SEALED file
           (CRC32C+length trailer, tmp+rename commit —
           shuffle/integrity.py) under the process's incarnation spill
           namespace, and its read-back is verified: a run the disk
           lost or rotted raises a classified
           :class:`~..memory.SpillReadError` through the task path
           (scheduler retries the task; the reading worker is never
           blamed) instead of feeding garbage into the merge;
        2. chunked k-way merge: per round, pull the next chunk of every
           live run host->device, concat with the carry, sort, and emit
           the prefix whose key tuples are <= the lexicographic MIN over
           each run's last-pulled row (every unread row of run i sorts
           after run i's boundary, so that prefix is globally final);
           the remainder becomes the carry (a lazy selection view — no
           copy). Memory high-water: carry + k chunks, not the dataset.
           A run is released the moment its last chunk is pulled, so
           host-tier spill residency DRAINS as the merge progresses
           instead of ballooning until query end. (Disk residency for
           a run drains earlier, at the verified ``get_host``
           read-back that precedes the merge — the read-back unlinks
           the sealed file and walks the live disk gauge down.)
        """
        import numpy as np
        from ..columnar.arrow_bridge import arrow_to_device
        from ..columnar.batch import bucket_rows
        from ..ops.gather import ensure_compacted, gather_batch, shrink_batch
        from ..ops.sort_keys import key_lanes, lex_leq, lex_min_tuple

        mm = ctx.mm
        ectx = ctx.eval_ctx
        spill_metric = ctx.metric(self, "spillTime")
        schema = self.child.output_schema

        runs = []
        try:
            t0 = time.perf_counter()
            for b in batches:
                sb = self._jitted(b, orders, ectx)
                sp = mm.register(sb)
                # appended BEFORE spill(): a raising spill must leave
                # sp reachable from the finally below [ledger-leak-path]
                runs.append(sp)
                sp.spill()
            spill_metric.value += time.perf_counter() - t0
            hosts = [sp.get_host() for sp in runs]
            rows = [h.num_rows for h in hosts]
            k = len(runs)
            bytes_per_row = max(1, batches[0].device_size_bytes()
                                // max(1, batches[0].capacity))
            budget_rows = max(256, (mm.budget // 2) // bytes_per_row
                              // max(1, k))
            chunk = max(128, bucket_rows(budget_rows) // 2)  # <= budget_rows
            cursors = [0] * k
            carry = None  # compacted, shrunk device batch

            specs = tuple(o.spec for o in self.orders)
            key_exprs = tuple(o.child for o in self.orders)

            import jax.numpy as jnp

            def merge_round(merged, bidx, bvalid):
                key_cols = [e.eval_tpu(merged, ectx) for e in key_exprs]
                live = merged.live_mask()
                lanes = key_lanes(key_cols, specs, live)
                idx = jnp.arange(live.shape[0], dtype=jnp.int32)
                sorted_all = jax.lax.sort(tuple(lanes) + (idx,),
                                          num_keys=len(lanes) + 1)
                perm = sorted_all[-1]
                total = jnp.sum(live.astype(jnp.int32))
                out = gather_batch(merged, perm, total)
                blanes = [lane[bidx] for lane in lanes]
                bmin = lex_min_tuple(blanes, bvalid)
                safe = lex_leq(list(sorted_all[:-1]), bmin)
                # lane0 == 0 <=> live row (key_lanes' live-rank lane)
                safe_count = jnp.sum((safe & (sorted_all[0] == 0))
                                     .astype(jnp.int32))
                return out, total, safe_count

            jit_round = named_jit("sort_merge", merge_round)

            while any(cursors[i] < rows[i] for i in range(k)) \
                    or carry is not None:
                active = [i for i in range(k) if cursors[i] < rows[i]]
                if not active:
                    yield carry
                    return
                parts = [] if carry is None else [carry]
                boundary_idx = []
                boundary_valid = []
                base = 0 if carry is None else carry.num_rows
                for i in active:
                    take = min(chunk, rows[i] - cursors[i])
                    rb = hosts[i].slice(cursors[i], take)
                    parts.append(arrow_to_device(rb, schema,
                                                 capacity=bucket_rows(take)))
                    cursors[i] += take
                    boundary_idx.append(base + take - 1)
                    # an exhausted run imposes no boundary
                    boundary_valid.append(cursors[i] < rows[i])
                    base += take
                    if cursors[i] >= rows[i]:
                        # last chunk pulled (and already on device):
                        # drop the run's catalog entry NOW so its
                        # host-tier residency drains mid-merge (disk
                        # already drained at the get_host read-back)
                        hosts[i] = None
                        if runs[i] is not None:
                            runs[i].release()
                            runs[i] = None
                merged = concat_batches(parts)
                if not any(boundary_valid):
                    # every run exhausted: the whole merge is final
                    out = self._jitted(merged, tuple(self.orders), ectx)
                    yield out
                    return
                bidx = np.asarray(boundary_idx, np.int32)
                bvalid = np.asarray(boundary_valid, np.bool_)
                out, total, safe_count = jit_round(merged, bidx, bvalid)
                yield TpuBatch(out.columns, schema, safe_count)
                carry = TpuBatch(
                    out.columns, schema, total,
                    selection=jnp.arange(out.capacity,
                                         dtype=jnp.int32) >= safe_count)
                carry = ensure_compacted(carry)
                carry_rows = carry.num_rows  # syncs once per round
                if carry_rows == 0:
                    carry = None
                else:
                    carry = shrink_batch(carry, bucket_rows(carry_rows))
        finally:
            # the spilled runs are catalog entries in the PROCESS-
            # SHARED manager: without this they outlive the sort
            # forever (host-tier bytes stay charged, the catalog
            # grows per query). tpu-lint 2.0 flagged the exception
            # window between register and append; the happy path
            # never released them either [ledger-leak-path]. Runs the
            # merge already drained were released in place (None).
            for sp in runs:
                if sp is not None:
                    sp.release()

    def execute_cpu(self, ctx: ExecCtx):
        rbs = list(self.child.execute_cpu(ctx))
        if not rbs:
            return
        if self.global_sort:
            tables = [pa.Table.from_batches([rb]) for rb in rbs]
            table = pa.concat_tables(tables).combine_chunks()
            rbs = [table.to_batches()[0]] if table.num_rows else []
        for rb in rbs:
            keys = [o.child.eval_cpu(rb, ctx.eval_ctx) for o in self.orders]
            t = cpu_sort_table(pa.Table.from_batches([rb]), keys,
                               self.orders)
            for out in t.to_batches():
                yield out


class TpuLocalLimitExec(UnaryExec):
    """Per-stream limit (GpuLocalLimitExec analog): truncates row_count;
    contents past the limit become padding."""

    CONTRACT = OpContract(schema_preserving=True,
                          notes="truncates the stream; schema unchanged")

    FUSION_NOTE = ("barrier: the remaining-rows counter is state "
                   "carried ACROSS batches (device-resident cumsum + "
                   "periodic sync)")

    _SYNC_EVERY = 8

    def __init__(self, limit: int, child: TpuExec):
        super().__init__(child)
        self.limit = limit

    def describe(self):
        return f"LocalLimitExec [{self.limit}]"

    PRUNING_NOTE = "requires its parent's columns"

    child_requirements = UnaryExec._parents_columns

    def pruned(self, children, maps, required):
        if children[0] is self.child:
            return self, maps[0]
        return type(self)(self.limit, children[0]), maps[0]

    def execute(self, ctx: ExecCtx):
        """Sync-free truncation: a device-resident cumulative row count
        clamps each batch's row_count to the rows still allowed — no
        host readback of batch sizes (the old per-batch num_rows sync
        drained the dispatch stream once per batch). Batches past the
        limit flow through with zero live
        rows instead of an early break — the no-sync trade. To keep
        LIMIT n over a huge scan from doing O(input) work (ADVICE r4),
        the device-side 'seen' counter syncs every _SYNC_EVERY batches
        and breaks the loop once the limit is known reached; short
        streams (the common case) finish before the first sync and stay
        readback-free."""
        import jax
        import jax.numpy as jnp

        from ..ops.gather import ensure_compacted
        seen = jnp.int32(0)
        for i, batch in enumerate(self.child.execute(ctx)):
            batch = ensure_compacted(batch)  # truncation needs prefix rows
            start = seen
            rc = batch.row_count
            seen = seen + rc.astype(jnp.int32)
            allowed = jnp.clip(jnp.int32(self.limit) - start, 0,
                               rc.astype(jnp.int32))
            yield batch.with_columns(batch.columns, row_count=allowed)
            if (i + 1) % self._SYNC_EVERY == 0 \
                    and int(jax.device_get(seen)) >= self.limit:
                return

    def execute_cpu(self, ctx: ExecCtx):
        remaining = self.limit
        for rb in self.child.execute_cpu(ctx):
            if remaining <= 0:
                return
            if rb.num_rows <= remaining:
                remaining -= rb.num_rows
                yield rb
            else:
                yield rb.slice(0, remaining)
                return


class TpuGlobalLimitExec(TpuLocalLimitExec):
    """Single-partition global limit — same truncation semantics."""

    def describe(self):
        return f"GlobalLimitExec [{self.limit}]"


class _PerBatchTopN(UnaryExec):
    """Sort each incoming batch and truncate it to `limit` rows — the
    pre-pass that bounds TopN's global merge to O(batches * limit).
    Per-batch sort+truncate is a pure batch->batch map, so it both
    EXPOSES a ``device_fn`` (chains above fuse through it) and fuses
    the chain BELOW it into its own program via ``fused_batches`` —
    scan-rooted, TopN-over-scan runs decode->filter->project->topN as
    one dispatch per coalesced batch."""

    def __init__(self, limit: int, orders: Sequence[SortOrder],
                 child: TpuExec):
        super().__init__(child)
        self.limit = limit
        self.orders = orders  # already bound by the owning TpuTopNExec

    def describe(self):
        return f"PerBatchTopN [{self.limit}]"

    def fusion_content(self) -> str:
        # describe() omits the sort keys; the fused-program content key
        # must not
        return (f"{self.describe()} orders="
                f"[{', '.join(repr(o) for o in self.orders)}]")

    def _run(self, batch, ectx):
        return sort_batch_by(batch, tuple(self.orders), ectx, self.limit)

    def device_fn(self):
        return self._run

    def execute(self, ctx: ExecCtx):
        yield from fused_batches(self, ctx, tail_fn=self._run,
                                 metric=ctx.metric(self, "opTime"))

    def execute_cpu(self, ctx: ExecCtx):
        for rb in self.child.execute_cpu(ctx):
            keys = [o.child.eval_cpu(rb, ctx.eval_ctx) for o in self.orders]
            t = cpu_sort_table(pa.Table.from_batches([rb]), keys,
                               self.orders)
            t = t.slice(0, self.limit)
            yield from t.combine_chunks().to_batches()


class TpuTopNExec(UnaryExec):
    """Take-ordered(-and-project): per-batch top-N, global merge sort,
    limit, optional projection (GpuTopN / GpuTakeOrderedAndProjectExec)."""

    FUSION_NOTE = ("delegating wrapper over its internal pre-topN -> "
                   "sort -> limit pipeline; the per-batch pre-pass "
                   "fuses with the chain below it (_PerBatchTopN)")

    def __init__(self, limit: int, orders: Sequence[SortOrder],
                 child: TpuExec,
                 project: Optional[Sequence[Expression]] = None):
        super().__init__(child)
        self.limit = limit
        self._ctor_orders = list(orders)
        self._ctor_project = list(project) if project is not None else None
        bound = [dataclasses.replace(
            o, child=bind_expr(o.child, child.output_schema))
            for o in orders]
        pre = _PerBatchTopN(limit, bound, child)
        self._sort = TpuSortExec(orders, pre, global_sort=True)
        self._limit = TpuGlobalLimitExec(limit, self._sort)
        if project is not None:
            from .basic import TpuProjectExec
            self._out: TpuExec = TpuProjectExec(project, self._limit)
        else:
            self._out = self._limit

    @property
    def output_schema(self):
        return self._out.output_schema

    def describe(self):
        return f"TopNExec [{self.limit}] {self._sort.describe()}"

    def expressions(self):
        out = [o.child for o in self._sort.orders]
        if self._ctor_project is not None:
            out.extend(self._out.exprs)
        return out

    PRUNING_NOTE = ("requires its order keys and its projection's "
                    "inputs, or its parent's columns where it has no "
                    "projection")

    def child_requirements(self, required):
        from .pruning import refs
        need = refs(self.expressions())
        if self._ctor_project is None:
            need |= set(required)
        return [need]

    def pruned(self, children, maps, required):
        from .pruning import identity_map, narrowed, remap, remap_order
        has_project = self._ctor_project is not None
        if children[0] is self.child:
            return self, identity_map(len(self.output_schema.fields)) \
                if has_project else maps[0]
        child, m = narrowed(children[0], maps[0],
                            self.child_requirements(required)[0])
        project = [remap(e, m) for e in self._out.exprs] \
            if has_project else None
        node = TpuTopNExec(
            self.limit, [remap_order(o, m) for o in self._sort.orders],
            child, project=project)
        return node, identity_map(len(node.output_schema.fields)) \
            if has_project else m

    def with_new_children(self, children):
        if children[0] is self.child:
            return self
        # internal pipeline (pre-topN -> sort -> limit -> project) is wired
        # to the child at construction; rebuild it over the new child
        return TpuTopNExec(self.limit, self._ctor_orders, children[0],
                           project=self._ctor_project)

    def execute(self, ctx: ExecCtx):
        return self._out.execute(ctx)

    def execute_cpu(self, ctx: ExecCtx):
        return self._out.execute_cpu(ctx)
