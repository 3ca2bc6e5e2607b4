"""Exchange operators: shuffle, broadcast, coalesce.

TPU analog of the reference's `GpuShuffleExchangeExecBase`,
`GpuBroadcastExchangeExec`, `GpuCoalesceBatches`, `GpuShuffleCoalesceExec`
(SURVEY.md §2.2-A/B/D; reference mount empty). The single-process engine
uses the LocalShuffleTransport seam; partition split emits selection-mask
views sharing the input's buffers (lazy contiguous_split analog). The ICI
SPMD all-to-all path plugs in behind the same seam (shuffle/ici.py).
"""
from __future__ import annotations

import itertools
import time
from typing import Dict, Iterator, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa

from .. import datatypes as dt
from ..columnar.batch import TpuBatch
from ..ops.concat import concat_batches
from ..shuffle.partitioner import Partitioning, SinglePartitioning
from ..shuffle.transport import LocalShuffleTransport, ShuffleTransport
from .base import ExecCtx, OpContract, TpuExec, UnaryExec

__all__ = ["TpuShuffleExchangeExec", "TpuBroadcastExchangeExec",
           "TpuCoalesceBatchesExec", "ShuffleStageHandle"]

_shuffle_ids = itertools.count()
# guards lazy creation of per-exchange shared locks (see
# materialize_shared — instances must stay picklable, so no Lock in
# __init__)
import threading as _threading
_SHARED_LOCK_INIT = _threading.Lock()


class ShuffleStageHandle:
    """Reduce-side view of a materialized shuffle stage (the
    QueryStageExec boundary analog): read partitions, ask for stats,
    release the store."""

    def __init__(self, transport: ShuffleTransport, sid: int, n: int):
        self.transport = transport
        self.sid = sid
        self.num_partitions = n

    def partition_stats(self, free_only: bool = False) \
            -> Optional[List[int]]:
        """Approximate bytes per partition, or None when the transport
        cannot provide them (AQE then passes through). With free_only,
        only stats the transport gathered as part of work it already
        did (no dedicated sync) are returned."""
        import inspect
        fn = getattr(self.transport, "partition_stats", None)
        if fn is None:
            return None
        # signature probe, not try/except TypeError: a genuine
        # TypeError inside the transport's stats math must propagate
        try:
            has_kw = "free_only" in inspect.signature(fn).parameters
        except (TypeError, ValueError):
            has_kw = False
        if has_kw:
            return fn(self.sid, free_only=free_only)
        return None if free_only else fn(self.sid)

    def total_bytes(self) -> Optional[int]:
        """Stage size from capacity metadata — NO device sync (the AQE
        join-strategy switch's input). None when unknown."""
        fn = getattr(self.transport, "stage_bytes", None)
        return fn(self.sid) if fn is not None else None

    def read(self, p: int):
        yield from self.transport.read_partition(self.sid, p)

    def close(self):
        self.transport.unregister_shuffle(self.sid)


class TpuShuffleExchangeExec(UnaryExec):
    """Repartition child output by a Partitioning strategy. Output batches
    arrive partition-major (partition 0's batches first), map-order within
    a partition — deterministic for the dual-run harness."""

    CONTRACT = OpContract(
        schema_preserving=True,
        notes="repartitions rows; partition keys must be primitive")

    def __init__(self, partitioning: Partitioning, child: TpuExec,
                 transport: Optional[ShuffleTransport] = None):
        super().__init__(child)
        self.partitioning = partitioning.bind(child.output_schema)
        # None = resolve from spark.rapids.shuffle.mode at execute
        self.transport = transport
        self._jit_split = None
        # exchange reuse (AQE, SURVEY.md:161): when the planner sees the
        # same exchange consumed twice (self-joins), it flags it shared;
        # the stage then materializes once and the handle outlives each
        # consumer (closed by the query-level cleanup)
        self.shared = False
        self._shared_handle: Optional["ShuffleStageHandle"] = None
        self._shared_lock = None

    def _resolve_transport(self, ctx: ExecCtx) -> ShuffleTransport:
        if self.transport is None:
            from ..config import SHUFFLE_MODE
            mode = ctx.conf.get(SHUFFLE_MODE)
            if mode == "LOCAL":
                self.transport = LocalShuffleTransport()
            elif mode in ("HOST", "MULTITHREADED"):
                import weakref
                from ..shuffle.host import HostShuffleTransport
                t = HostShuffleTransport(
                    ctx.conf, threads=0 if mode == "HOST" else None)
                # reclaim the pool + temp root when this exec goes away
                weakref.finalize(self, HostShuffleTransport.close, t)
                self.transport = t
            elif mode == "ICI":
                # the process's ONE transport over its local devices
                # (the session brought it up when it was made)
                from ..shuffle.ici import local_transport
                self.transport = local_transport(ctx.conf)
            else:
                raise ValueError(f"unknown shuffle mode {mode!r}")
        return self.transport

    def describe(self):
        return (f"ShuffleExchangeExec [{type(self.partitioning).__name__} "
                f"n={self.partitioning.num_partitions}]")

    PRUNING_NOTE = ("requires its partition keys and its parent's "
                    "columns; a fusable child is narrowed to them")

    def child_requirements(self, required):
        from .pruning import refs
        return [set(required)
                | refs(self.partitioning.key_expressions())]

    def pruned(self, children, maps, required):
        from .pruning import narrowed, remap
        if children[0] is self.child:
            return self, maps[0]
        child, m = narrowed(children[0], maps[0],
                            self.child_requirements(required)[0])
        return TpuShuffleExchangeExec(
            self.partitioning.map_expressions(lambda e: remap(e, m)),
            child, transport=self.transport), m

    def tpu_supported(self):
        key_exprs = getattr(self.partitioning, "key_exprs", None) or \
            [o.child for o in getattr(self.partitioning, "orders", [])]
        for e in key_exprs:
            if dt.is_nested(e.dtype):
                return (f"partitioning by nested type "
                        f"{e.dtype.simple_string()} not on device")
        from ..shuffle.ici import IciShuffleTransport, _lane_spec
        if isinstance(self.transport, IciShuffleTransport):
            try:  # payload shapes the ICI lanes can't carry: plan-time
                _lane_spec(self.child.output_schema)
            except NotImplementedError as e:
                return str(e)
        return None

    #: stage-fusion audit: the exchange itself is a barrier, but its
    #: writer's hash-partition KEY computation is a row-wise map and
    #: fuses as the chain's tail (see ``materialize``)
    FUSION_NOTE = ("barrier: repartitions rows across batches; the "
                   "writer's partition-key split fuses as a chain TAIL "
                   "(fused_batches tail_fn) — with a device-decode scan "
                   "child, decode->chain->partition-ids is one program")

    def fusion_content(self) -> str:
        """describe() omits the partition key expressions; the fused
        split program's content key must not (two exchanges hashing
        different columns are different programs). Range partitionings
        additionally bake their SAMPLED BOUNDS into the traced program
        — identical keys with different bounds are different programs,
        so the bounds values join the content key too (the scan-spliced
        cache is process-global; a collision would silently route rows
        by another exchange's bounds)."""
        key_exprs = getattr(self.partitioning, "key_exprs", None) or \
            [o.child for o in getattr(self.partitioning, "orders", [])]
        content = (f"{self.describe()} keys="
                   f"[{', '.join(map(repr, key_exprs))}]")
        bounds = getattr(self.partitioning, "bounds", None)
        if bounds is not None:  # List[tuple] of host key values
            content += f" bounds={bounds!r}"
        return content

    def _split(self, batch: TpuBatch, ectx):
        """All partitions in ONE traced call: compute pids once, emit one
        selection-masked view per partition. The views share the input's
        device buffers — an n-way split costs one pids kernel and n bool
        masks, not n stream compactions holding n full copies (the
        contiguous_split analog, lazy edition)."""
        pids = self.partitioning.partition_ids_device(batch, ectx)
        return tuple(batch.with_selection(pids == jnp.int32(p))
                     for p in range(self.partitioning.num_partitions))

    def _pids(self, batch: TpuBatch, ectx):
        return self.partitioning.partition_ids_device(batch, ectx)

    def _split_tail(self, batch: TpuBatch, ectx):
        """Fused-chain tail for the map phase: the upstream chain's
        output batch plus its per-partition selection views, all from
        ONE program."""
        return (batch, self._split(batch, ectx))

    def _pids_tail(self, batch: TpuBatch, ectx):
        """write_unsplit transports: (batch, partition ids) tail."""
        return (batch, self._pids(batch, ectx))

    def _single_tail(self, batch: TpuBatch, ectx):
        """n == 1: the whole batch IS the partition — no pids/views
        computed (they would be dead program outputs XLA cannot DCE)."""
        return (batch, None)

    def materialize(self, ctx: ExecCtx) -> "ShuffleStageHandle":
        """Run the WRITE phase (map side) and return a handle exposing the
        reduce side — the stage boundary AQE observes: per-partition stats
        become available here, before any partition is read
        (SURVEY.md:161)."""
        transport = self._resolve_transport(ctx)
        unsplit = getattr(transport, "supports_unsplit", False)
        if hasattr(transport, "set_memory_manager"):
            # shuffle store bytes count against the HBM ledger and spill
            # under pressure (RapidsBufferCatalog-backed store analog)
            transport.set_memory_manager(ctx.mm)
        if hasattr(transport, "set_stats_recording"):
            # writer-side partition stats: when AQE is on, the map phase
            # records per-partition byte counts as it writes, so the
            # adaptive reader gets stats with zero read-side device
            # syncs (spark.rapids.sql.adaptive.freeStatsOnly stays safe)
            from ..config import ADAPTIVE_ENABLED
            transport.set_stats_recording(ctx.conf.get(ADAPTIVE_ENABLED))
        n = self.partitioning.num_partitions
        sid = next(_shuffle_ids)
        transport.register_shuffle(sid, n)
        if hasattr(transport, "set_shuffle_schema"):
            # SPMD gang transports need the schema up front: a process
            # whose leaf slice produced ZERO map blocks must still pack
            # empty slots and join the collective with the right lanes
            transport.set_shuffle_schema(sid, self.child.output_schema)
        op_time = ctx.metric(self, "opTime")
        ctx.metric(self, "numPartitions").set(n)
        # write-side row attribution: the map phase counts every row it
        # partitions (the AQE reader and cluster map tasks drive the
        # exchange through materialize, never through execute(), so
        # without this the exchange shows rows=0 while its consumers
        # see the full stream — blinding the warehouse and any fitted
        # cost model at exactly the operator the planner prices).
        # opm.enter claims the node so the non-AQE execute() path —
        # whose counting shim already counts the read side — never
        # double counts.
        opm = getattr(ctx, "opm", None)
        claimed = opm is not None and opm.enabled and opm.enter(self)
        rows_m = ctx.metric(self, "rows") if claimed else None
        try:
            return self._materialize_write(ctx, transport, unsplit, n,
                                           sid, op_time, rows_m)
        finally:
            if claimed:
                opm.exit(self)

    def _materialize_write(self, ctx: ExecCtx, transport, unsplit: bool,
                           n: int, sid: int, op_time,
                           rows_m) -> "ShuffleStageHandle":
        from ..shuffle.partitioner import RangePartitioning
        needs_bounds = isinstance(self.partitioning, RangePartitioning) \
            and self.partitioning.bounds is None
        if not needs_bounds:
            # the partition-KEY computation is a row-wise map: fuse it
            # as the tail of the chain feeding this exchange
            # (fused_batches), so filter/project — and, scan-rooted,
            # the parquet decode itself — land in ONE program with the
            # pids/split. OOM split-and-retry stays on: the tail is
            # pure (pids/views only — the writer's side effects happen
            # AFTER the yield), so a halved retry simply yields each
            # half as its own map task
            from .base import fused_batches
            if unsplit:
                tail = self._pids_tail
            elif n == 1:
                tail = self._single_tail
            else:
                tail = self._split_tail
            stream = fused_batches(self, ctx, tail_fn=tail,
                                   metric=op_time)
            # writer wall goes to its OWN metric: op_time is stamped by
            # the opmetrics completion watcher for the fused chain, and
            # a second same-metric writer on this thread would race it
            write_t = ctx.metric(self, "writeTime")
            for map_id, (batch, split) in enumerate(stream):
                if rows_m is not None:
                    ctx.opm.count_rows(rows_m, batch)
                writer = transport.writer(sid, map_id)
                t0 = time.perf_counter()
                if unsplit:
                    writer.write_unsplit(batch, split)
                elif n == 1:
                    writer.write(0, batch)
                else:
                    for p in range(n):
                        writer.write(p, split[p])
                write_t.value += time.perf_counter() - t0
                writer.close()
            return ShuffleStageHandle(transport, sid, n)
        if self._jit_split is None:
            fn = self._pids if unsplit else self._split
            self._jit_split = jax.jit(fn, static_argnums=1)
        source = self._with_range_bounds_device(ctx)
        for map_id, batch in enumerate(source):
            if rows_m is not None:
                ctx.opm.count_rows(rows_m, batch)
            writer = transport.writer(sid, map_id)
            t0 = time.perf_counter()
            if unsplit:
                writer.write_unsplit(batch,
                                     self._jit_split(batch, ctx.eval_ctx))
            elif n == 1:
                writer.write(0, batch)
            else:
                parts = self._jit_split(batch, ctx.eval_ctx)
                for p in range(n):
                    writer.write(p, parts[p])
            op_time.value += time.perf_counter() - t0
            writer.close()
        return ShuffleStageHandle(transport, sid, n)

    def materialize_shared(self, ctx: ExecCtx) -> "ShuffleStageHandle":
        """Materialize once per query; subsequent consumers reuse the
        handle (the ReusedExchangeExec analog). The handle closes via
        the ctx cleanup hook, after every consumer finished. The
        per-instance lock is created lazily under a module guard (a
        Lock in __init__ would make the exec unpicklable for the
        process-cluster path) — the guard closes the two-threads-
        install-different-locks race."""
        import threading
        if self._shared_lock is None:
            with _SHARED_LOCK_INIT:
                if self._shared_lock is None:
                    self._shared_lock = threading.Lock()
        with self._shared_lock:
            if self._shared_handle is None:
                handle = self.materialize(ctx)
                self._shared_handle = handle

                def cleanup():
                    # under the same lock as the install: a late
                    # consumer in materialize_shared must never observe
                    # (and re-read from) a handle whose store is being
                    # torn down [unlocked-shared-mutation]
                    with self._shared_lock:
                        self._shared_handle = None
                    handle.close()
                ctx.register_cleanup(cleanup)
            else:
                ctx.metric(self, "stageReuses").value += 1
            return self._shared_handle

    def execute(self, ctx: ExecCtx):
        if self.shared:
            handle = self.materialize_shared(ctx)
            for p in range(handle.num_partitions):
                yield from handle.read(p)
            return
        handle = self.materialize(ctx)
        try:
            for p in range(handle.num_partitions):
                yield from handle.read(p)
        finally:
            handle.close()

    # sampled rows per map batch feeding the range-bound computation
    _RANGE_SAMPLE_ROWS = 4096

    def _with_range_bounds_device(self, ctx):
        """For RangePartitioning without precomputed bounds: materialize
        the child, sample a deterministic prefix of each batch, compute
        the (k-1) bounds host-side (the reference's driver-side sampled
        bounds — SURVEY.md §2.2-B), and replay the batches. Other
        partitionings stream straight through."""
        from ..shuffle.partitioner import RangePartitioning
        if not isinstance(self.partitioning, RangePartitioning) \
                or self.partitioning.bounds is not None:
            return self.child.execute(ctx)
        from ..columnar.arrow_bridge import device_to_arrow
        from ..columnar.batch import TpuBatch
        from ..ops.gather import ensure_compacted, shrink_batch
        k = self._RANGE_SAMPLE_ROWS

        def prefix_sample(b):
            # slice the prefix ON DEVICE before downloading: fixed-width
            # lanes transfer only k rows (string chars stay shared)
            b = ensure_compacted(b)
            n = min(b.num_rows, k)
            if b.capacity > k:
                b = shrink_batch(TpuBatch(b.columns, b.schema, n), k)
            return device_to_arrow(b)

        # each batch registers spillable AS PRODUCED, so a child larger
        # than HBM spills instead of OOMing during materialization too
        # (the sample downloads the prefix before the batch can be
        # evicted; replay re-uploads on demand) — ADVICE r3 #3
        sbs, samples = [], []
        try:
            for b in self.child.execute(ctx):
                samples.append(prefix_sample(b))
                sbs.append(ctx.mm.register(b))
            self.partitioning.compute_bounds(samples, ctx.eval_ctx)
        except BaseException:
            # a raising sample/bounds computation must not strand the
            # registered batches in the process-shared catalog
            # [ledger-leak-path]
            for sb in sbs:
                sb.release()
            raise

        def replay():
            pending = list(sbs)
            try:
                while pending:
                    b = pending[0].get()
                    pending.pop(0).release()
                    yield b
            finally:
                # early close / failed re-upload: release the tail the
                # consumer never took delivery of [ledger-leak-path]
                for sb in pending:
                    sb.release()
        return replay()

    def execute_cpu(self, ctx: ExecCtx):
        from ..shuffle.partitioner import RangePartitioning
        n = self.partitioning.num_partitions
        parts: Dict[int, List[pa.RecordBatch]] = {p: [] for p in range(n)}
        rbs = list(self.child.execute_cpu(ctx))
        if isinstance(self.partitioning, RangePartitioning) \
                and self.partitioning.bounds is None:
            self.partitioning.compute_bounds(
                [rb.slice(0, self._RANGE_SAMPLE_ROWS) for rb in rbs],
                ctx.eval_ctx)
        for rb in rbs:
            pids = self.partitioning.partition_ids_cpu(rb, ctx.eval_ctx)
            for p in range(n):
                idx = np.nonzero(pids == p)[0]
                if n == 1:
                    parts[p].append(rb)
                elif len(idx):
                    parts[p].append(rb.take(pa.array(idx, pa.int64())))
        for p in range(n):
            yield from parts[p]


class TpuBroadcastExchangeExec(UnaryExec):
    """Materialize the child once as the build-side table. With a device
    mesh, each child batch is a per-device block and the table is
    REPLICATED via the ICI all-gather collective (shuffle/ici.py:
    ici_broadcast_batches) — no chip ever holds the only copy
    (SURVEY.md:227). Without a mesh (single-process): device concat. The
    payload is registered in the spill catalog so an idle broadcast
    yields its HBM under pressure and re-uploads on next use."""

    CONTRACT = OpContract(
        schema_preserving=True, resident_footprint=True,
        notes="materializes the whole child device-resident as the "
              "build-side table")

    FUSION_NOTE = ("barrier: materializes/concatenates the WHOLE child "
                   "(cross-batch), optionally through an ICI collective")

    def __init__(self, child: TpuExec, mesh=None, axis: str = "x"):
        super().__init__(child)
        self.mesh = mesh
        self.axis = axis
        self._sb = None  # SpillableBatch

    def tpu_supported(self):
        if self.mesh is not None:
            # the collective path carries column trees as lanes; shapes
            # it can't encode must fall back at PLAN time, not raise
            # mid-query
            from ..shuffle.ici import _lane_spec
            try:
                _lane_spec(self.child.output_schema)
            except NotImplementedError as e:
                return str(e)
        return None

    PRUNING_NOTE = ("requires its parent's columns; a fusable child "
                    "is narrowed to them")

    child_requirements = UnaryExec._parents_columns

    def pruned(self, children, maps, required):
        from .pruning import narrowed
        if children[0] is self.child:
            return self, maps[0]
        child, m = narrowed(children[0], maps[0], required)
        return TpuBroadcastExchangeExec(child, mesh=self.mesh,
                                        axis=self.axis), m

    def spillable(self, ctx: ExecCtx):
        """The catalog handle for the broadcast payload (None if the
        child is empty). Join build sides reuse this handle instead of
        re-registering the same buffers (double-counting the ledger)."""
        if self._sb is None:
            batches = list(self.child.execute(ctx))
            if not batches:
                return None
            if self.mesh is not None:
                from ..shuffle.ici import ici_broadcast_batches
                gathered = ici_broadcast_batches(self.mesh, batches,
                                                 self.axis)
                payload = gathered[0] if len(gathered) == 1 else \
                    concat_batches(gathered)
            else:
                payload = concat_batches(batches)
            self._sb = ctx.mm.register(payload)
            # the catalog holds a strong ref; without this the payload
            # would outlive the plan in the process-shared ledger
            import weakref
            weakref.finalize(self, type(self._sb).release, self._sb)
        return self._sb

    def execute(self, ctx: ExecCtx):
        sb = self.spillable(ctx)
        if sb is not None:
            yield sb.get()

    def execute_cpu(self, ctx: ExecCtx):
        rbs = list(self.child.execute_cpu(ctx))
        if not rbs:
            return
        t = pa.Table.from_batches(rbs).combine_chunks()
        yield from t.to_batches()


class TpuCoalesceBatchesExec(UnaryExec):
    """Concatenate small batches up to a target row count
    (GpuCoalesceBatches analog; target bytes logic arrives with the
    memory manager)."""

    CONTRACT = OpContract(
        schema_preserving=True,
        notes="concatenates small batches; row values unchanged")

    FUSION_NOTE = ("barrier: multi-batch operator — output batches "
                   "combine SEVERAL input batches (size-driven concat)")

    def __init__(self, child: TpuExec, target_rows: int = 1 << 17):
        super().__init__(child)
        self.target_rows = target_rows

    def describe(self):
        return f"CoalesceBatchesExec [target={self.target_rows}]"

    PRUNING_NOTE = ("requires its parent's columns; a fusable child "
                    "is narrowed to them")

    child_requirements = UnaryExec._parents_columns

    def pruned(self, children, maps, required):
        from .pruning import narrowed
        if children[0] is self.child:
            return self, maps[0]
        child, m = narrowed(children[0], maps[0], required)
        return TpuCoalesceBatchesExec(child,
                                      target_rows=self.target_rows), m

    def tpu_supported(self):
        from ..ops.concat import device_concat_supported
        for f in self.child.output_schema.fields:
            if not device_concat_supported(f.dtype):
                return (f"coalescing nested column {f.name} not on "
                        "device (no nested device concat yet)")
        return None

    def execute(self, ctx: ExecCtx):
        from ..config import BATCH_SIZE_BYTES
        target_bytes = ctx.conf.get(BATCH_SIZE_BYTES)
        pending: List[TpuBatch] = []
        pending_rows = 0
        pending_bytes = 0
        concat_time = ctx.metric(self, "concatTime")
        for batch in self.child.execute(ctx):
            n = batch.num_rows
            if n == 0:
                continue
            b = batch.device_size_bytes()
            if pending and (pending_rows + n > self.target_rows
                            or pending_bytes + b > target_bytes):
                t0 = time.perf_counter()
                yield concat_batches(pending)
                concat_time.value += time.perf_counter() - t0
                pending, pending_rows, pending_bytes = [], 0, 0
            pending.append(batch)
            pending_rows += n
            pending_bytes += b
        if pending:
            t0 = time.perf_counter()
            yield concat_batches(pending)
            concat_time.value += time.perf_counter() - t0

    def execute_cpu(self, ctx: ExecCtx):
        pending: List[pa.RecordBatch] = []
        pending_rows = 0
        for rb in self.child.execute_cpu(ctx):
            if rb.num_rows == 0:
                continue
            if pending_rows + rb.num_rows > self.target_rows and pending:
                yield _concat_host(pending)
                pending, pending_rows = [], 0
            pending.append(rb)
            pending_rows += rb.num_rows
        if pending:
            yield _concat_host(pending)


def _concat_host(rbs: List[pa.RecordBatch]) -> pa.RecordBatch:
    t = pa.Table.from_batches(rbs).combine_chunks()
    return t.to_batches()[0]
