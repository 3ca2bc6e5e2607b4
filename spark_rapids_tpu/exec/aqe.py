"""Adaptive query execution at shuffle stage boundaries.

TPU analog of the reference's AQE integration (`GpuShuffleCoalesceExec`,
`GpuCustomShuffleReaderExec`, skew-join handling — SURVEY.md:161, 228;
reference mount empty). Spark's AQE re-plans whole stages on the driver;
this engine's equivalent decision point is the materialized shuffle
stage: `TpuAQEShuffleReadExec` sits above an exchange, reads the
per-partition byte statistics the transport gathered during the write
phase, and

- COALESCES runs of adjacent partitions below the advisory size into a
  single device batch (fewer, fuller programs downstream — the
  coalesce-reader analog), and
- SPLITS skewed partitions (> factor x median, above the threshold) into
  capacity-halved sub-batches so one hot key cannot blow a downstream
  operator's memory cliff (the skew-join split analog; the sub-batches
  stream through the same consumer).

Statistics are (nearly) free on the default paths: the host transport
records per-partition byte counts at WRITE time (the writer downloads
and splits every map batch anyway — serving them touches no device
state at all), and the local in-process transport dispatches a
writer-side count kernel alongside each map batch's split (async, so
the map phase stays pipelined) whose few-int32 results fold in with
ONE deferred readback at the stage boundary. No payload downloads, no
read-time stats kernels, no re-upload of spilled entries — coalesce/
skew engages on the default path for the cost of, at most, one tiny
transfer per exchange. Transports/shuffles without recorded stats
report None under `spark.rapids.sql.adaptive.freeStatsOnly` (the
default) and the reader passes through.
"""
from __future__ import annotations

from typing import List, Optional

from ..config import (ADAPTIVE_ADVISORY_BYTES, ADAPTIVE_COALESCE,
                      ADAPTIVE_FREE_STATS, ADAPTIVE_SKEW_FACTOR,
                      ADAPTIVE_SKEW_THRESHOLD, AUTO_BROADCAST_THRESHOLD)
from .base import ExecCtx, LeafExec, OpContract, TpuExec, UnaryExec
from .exchange import TpuShuffleExchangeExec

__all__ = ["TpuAQEShuffleReadExec", "TpuAQEJoinExec",
           "plan_partition_groups"]


def plan_partition_groups(stats: List[int], advisory: int,
                          skew_factor: int, skew_threshold: int,
                          coalesce: bool):
    """Pure planning: partition indices -> list of (kind, members) with
    kind in {'coalesced', 'skewed', 'plain'}. Separated from execution so
    tests can drive it with synthetic stats."""
    n = len(stats)
    live = sorted(v for v in stats if v > 0)
    median = live[len(live) // 2] if live else 0
    skew_cut = max(skew_factor * median, skew_threshold)
    groups = []
    run: List[int] = []
    run_bytes = 0
    for p in range(n):
        if stats[p] >= skew_cut and median > 0:
            if run:
                groups.append(("coalesced" if len(run) > 1 else "plain",
                               run))
                run, run_bytes = [], 0
            groups.append(("skewed", [p]))
            continue
        if not coalesce:
            groups.append(("plain", [p]))
            continue
        if run and run_bytes + stats[p] > advisory:
            groups.append(("coalesced" if len(run) > 1 else "plain", run))
            run, run_bytes = [], 0
        run.append(p)
        run_bytes += stats[p]
    if run:
        groups.append(("coalesced" if len(run) > 1 else "plain", run))
    return groups


class TpuAQEShuffleReadExec(UnaryExec):
    """Adaptive reader over a shuffle exchange (see module docstring).
    Inserted by the planner when spark.sql.adaptive.enabled; transparent
    to the CPU oracle (partition boundaries carry no row semantics for
    the single downstream consumer)."""

    CONTRACT = OpContract(
        schema_preserving=True,
        wrapper_over="TpuShuffleExchangeExec",
        notes="planner-inserted adaptive reader; only valid directly "
              "over a shuffle exchange")

    def __init__(self, child: TpuShuffleExchangeExec):
        super().__init__(child)
        self.last_groups = None  # exposed for tests/metrics

    def describe(self):
        return "AQEShuffleReadExec"

    PRUNING_NOTE = UnaryExec.WRAPPER_PRUNING_NOTE
    child_requirements = UnaryExec._parents_columns
    pruned = UnaryExec._wrapper_pruned

    def execute(self, ctx: ExecCtx):
        from ..memory import split_batch
        from ..ops.concat import concat_batches_bounded
        shared = getattr(self.child, "shared", False)
        handle = self.child.materialize_shared(ctx) if shared \
            else self.child.materialize(ctx)
        coalesced_m = ctx.metric(self, "numCoalescedPartitions")
        skew_m = ctx.metric(self, "numSkewSplits")
        try:
            stats = handle.partition_stats(
                free_only=ctx.conf.get(ADAPTIVE_FREE_STATS))
            if stats is None:
                for p in range(handle.num_partitions):
                    yield from handle.read(p)
                return
            conf = ctx.conf
            advisory = conf.get(ADAPTIVE_ADVISORY_BYTES)
            groups = plan_partition_groups(
                stats, advisory, conf.get(ADAPTIVE_SKEW_FACTOR),
                conf.get(ADAPTIVE_SKEW_THRESHOLD),
                conf.get(ADAPTIVE_COALESCE))
            self.last_groups = groups
            for kind, members in groups:
                if kind == "coalesced":
                    batches = [b for p in members for b in handle.read(p)]
                    coalesced_m.value += len(members)
                    if not batches:
                        continue
                    yield concat_batches_bounded(batches)
                elif kind == "skewed":
                    def halves_in_order(piece):
                        # recursive in-order emission: the exchange's
                        # map-order-within-partition contract must
                        # survive the split (a LIFO stack would yield
                        # second halves first)
                        if piece.device_size_bytes() > advisory and \
                                piece.capacity >= 2:
                            skew_m.value += 1
                            b1, b2 = split_batch(piece)
                            yield from halves_in_order(b1)
                            yield from halves_in_order(b2)
                        else:
                            yield piece
                    for b in handle.read(members[0]):
                        yield from halves_in_order(b)
                else:
                    for p in members:
                        yield from handle.read(p)
        finally:
            if not shared:
                handle.close()

    def execute_cpu(self, ctx: ExecCtx):
        yield from self.child.execute_cpu(ctx)


class _StageReadExec(LeafExec):
    """Leaf over an already-materialized shuffle stage handle — how the
    AQE join re-plan feeds the SAME materialized bytes to whichever
    strategy it picks (the QueryStageExec reuse analog)."""

    def __init__(self, handle, schema):
        super().__init__()
        self._handle = handle
        self._schema = schema

    @property
    def output_schema(self):
        return self._schema

    def describe(self):
        return f"StageReadExec [s{self._handle.sid}]"

    def execute(self, ctx: ExecCtx):
        for p in range(self._handle.num_partitions):
            yield from self._handle.read(p)

    def execute_cpu(self, ctx: ExecCtx):
        raise NotImplementedError("materialized stages are device-side")


def _unwrap_exchange(node: TpuExec) -> Optional[TpuShuffleExchangeExec]:
    if isinstance(node, TpuAQEShuffleReadExec):
        node = node.child
    return node if isinstance(node, TpuShuffleExchangeExec) else None


class TpuAQEJoinExec(UnaryExec):
    """Runtime join-strategy switch (the half of the reference's AQE the
    round-4 reader lacked — SURVEY.md:161, VERDICT r4 #4): wraps a
    shuffled hash join whose children are shuffle exchanges. At execute:

    1. materialize the BUILD-side exchange (its map phase runs);
    2. read the stage size from capacity metadata — NO device sync, so
       the decision never waits on the device;
    3. small build (<= spark.sql.autoBroadcastJoinThreshold): demote to
       a broadcast-shaped join — the STREAM side's exchange is skipped
       entirely (its child feeds the join directly), which is the real
       win: one whole shuffle never happens;
    4. otherwise keep the shuffled join, but feed it the already-
       materialized build stage (no re-shuffle of the build side).

    The wrapped join object itself is reused with swapped children —
    key binding is schema-based and both strategies share the join
    core, mirroring how GpuShuffledHashJoinExec/GpuBroadcastHashJoinExec
    share GpuHashJoin."""

    CONTRACT = OpContract(
        schema_preserving=True,
        wrapper_over="TpuShuffledHashJoinExec",
        notes="planner-inserted runtime join-strategy switch; only "
              "valid directly over a shuffled hash join")

    def __init__(self, join):
        super().__init__(join)
        self.last_strategy = None  # "broadcast" | "shuffled" | None

    def describe(self):
        return "AQEJoinExec"

    PRUNING_NOTE = UnaryExec.WRAPPER_PRUNING_NOTE
    child_requirements = UnaryExec._parents_columns
    pruned = UnaryExec._wrapper_pruned

    @property
    def output_schema(self):
        return self.child.output_schema

    def execute(self, ctx: ExecCtx):
        join = self.child
        rex = _unwrap_exchange(join.right)
        lex = _unwrap_exchange(join.left)
        threshold = ctx.conf.get(AUTO_BROADCAST_THRESHOLD)
        if rex is None or threshold < 0:
            self.last_strategy = None
            yield from join.execute(ctx)
            return
        handle = rex.materialize_shared(ctx) if rex.shared \
            else rex.materialize(ctx)
        owned = not rex.shared
        try:
            nbytes = handle.total_bytes()
            build = _StageReadExec(handle, rex.output_schema)
            if nbytes is not None and nbytes <= threshold \
                    and lex is not None:
                self.last_strategy = "broadcast"
                ctx.metric(self, "numBroadcastDemotions").value += 1
                replanned = join.with_new_children((lex.child, build))
            else:
                self.last_strategy = "shuffled"
                replanned = join.with_new_children((join.left, build))
            yield from replanned.execute(ctx)
        finally:
            if owned:
                handle.close()

    def execute_cpu(self, ctx: ExecCtx):
        yield from self.child.execute_cpu(ctx)
