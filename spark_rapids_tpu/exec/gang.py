"""One task per chip: an aggregation plan run as an in-process gang.

Under ``spark.rapids.shuffle.mode=ICI`` a session has ONE mesh over its
local devices and ONE ``IciShuffleTransport`` (``shuffle/ici.py::
local_transport``). A plan of the shape

    <unary operators> ( HashAggregate ( ShuffleExchange [hash] ( STAGE )))

whose STAGE is scans, filters, projections and hash joins then runs the
way Spark runs it on an executor host with one accelerator a task
(ROADMAP M7, the in-process half):

- the STAGE's Parquet scan of most row groups (the fact table; ties go
  to the larger files) is sliced by row group: member ``k`` of ``ndev``
  reads, uploads and decodes a contiguous share on chip ``k``; the shares
  are disjoint and cover every row group (``TpuFileScanExec.sliced``). Every other leaf is read WHOLE by every
  member (what a broadcast gives a task): no fact row crosses a chip;
- each member runs the STAGE and the aggregate's update half over its
  share on its chip, and writes the partial buffers through a
  ``ShuffleExchangeExec`` into its slot of the gang's exchange
  (``IciGang``): one all-to-all between the chips, no host round trip;
- each member merges and evaluates the partitions that landed on its
  chip, there; the ``ndev`` small results are brought to one chip once,
  for the operators above the aggregate (projection, global sort, limit).

``split`` decides from the plan alone whether that is EXACT, and says why
not: a stage operator outside the list (UNION ALL: a table whole in every
member would be counted once per member), an outer join whose preserved
side is not the sliced one, an aggregate that cannot merge partial
buffers. Such a plan runs as one task (the transport's one-task path) and
is right. A member's failure aborts the others and fails the query: there
is no partial answer.

Spans (``obs/tracer.py``): ``gang.member`` per member (``member``,
``device``, ``slice_row_groups``, ``rows``); ``exchange.ici`` /
``exchange.wait`` from the gang's exchange. Counters on the exchange
node: ``gangMembers``, ``iciEpochs``, ``iciBytes``.
"""
from __future__ import annotations

import threading
from typing import List, Optional, Tuple

import jax

from ..columnar.batch import TpuBatch
from .aggregate import TpuHashAggregateExec
from .base import DeviceBatchSourceExec, ExecCtx, TpuExec, UnaryExec
from .basic import TpuFilterExec, TpuProjectExec
from .exchange import TpuCoalesceBatchesExec, TpuShuffleExchangeExec
from .joins import TpuShuffledHashJoinExec

__all__ = ["GangSplit", "split", "run"]

#: the join types that distribute over a slicing of ONE side:
#: join(slice_k(A), B) over all k is join(A, B)
_SLICE_LEFT = ("inner", "cross", "left_outer", "left_semi", "left_anti")
_SLICE_RIGHT = ("inner", "cross", "right_outer")
#: metrics a member SETS (every member the same value) or keeps a maximum
#: of: folded by taking the largest, where every other metric is a count
#: or a time and adds up
_SET_METRICS = ("fusedInto", "fusedChainOps", "numPartitions",
                "fetchAheadMax")


class GangSplit:
    """An eligible plan cut at its aggregate (``split``)."""

    def __init__(self, top: List[TpuExec], agg: TpuHashAggregateExec,
                 exchange: TpuShuffleExchangeExec, fact, row_groups: int):
        self.top = top            # root ... the aggregate's parent
        self.agg = agg
        self.exchange = exchange
        self.fact = fact          # the scan that is sliced
        self.row_groups = row_groups  # ... and how many it has


def _leaves(node: TpuExec) -> List[TpuExec]:
    if not node.children:
        return [node]
    return [leaf for c in node.children for leaf in _leaves(c)]


def _holds(node: TpuExec, leaf: TpuExec) -> bool:
    return node is leaf or any(_holds(c, leaf) for c in node.children)


def _stage_verdict(node: TpuExec, fact: TpuExec) -> Optional[str]:
    """Why the stage under ``node`` does not distribute over a slicing of
    ``fact`` with every other leaf whole, or None."""
    from ..io import TpuFileScanExec
    from .base import HostBatchSourceExec
    if isinstance(node, (TpuFileScanExec, HostBatchSourceExec)):
        return None
    if isinstance(node, (TpuFilterExec, TpuProjectExec,
                         TpuCoalesceBatchesExec)):
        return _stage_verdict(node.child, fact)
    if isinstance(node, TpuShuffledHashJoinExec):
        on_left = _holds(node.left, fact)
        on_right = _holds(node.right, fact)
        if on_left and on_right:
            return "both sides of a join read the sliced table's path"
        if on_left and node.join_type not in _SLICE_LEFT \
                or on_right and node.join_type not in _SLICE_RIGHT:
            return (f"a {node.join_type} join whose preserved side is "
                    "whole in every member would repeat its rows")
        return _stage_verdict(node.left, fact) \
            or _stage_verdict(node.right, fact)
    return (f"{node.pretty_name()} in the member stage (a table whole in "
            "every member would be counted once per member)")


def split(root: TpuExec, conf) -> Tuple[Optional[GangSplit], str]:
    """``(split, verdict)``: the plan cut for a gang of one task per chip,
    or ``None`` and why it runs as one task, under a conf whose
    ``spark.rapids.shuffle.mode`` is ICI (``PhysicalPlan.gang_split``
    asks for no other). Decided from the plan and the conf alone, so
    EXPLAIN can say it before anything runs."""
    from ..io import TpuFileScanExec
    from ..shuffle.ici import IciShuffleTransport, local_transport
    from ..shuffle.partitioner import HashPartitioning
    ndev = local_transport(conf).ndev
    if ndev < 2:
        return None, ("one task: the mesh is one device wide (local devices "
                      "and spark.sql.shuffle.partitions, whichever is less)")
    top: List[TpuExec] = []
    node = root
    while not isinstance(node, TpuHashAggregateExec):
        if not isinstance(node, UnaryExec):
            return None, (f"one task: {node.pretty_name()} above the "
                          "aggregate (or no aggregate over an exchange)")
        top.append(node)
        node = node.child
    agg, exchange = node, node.child
    if not isinstance(exchange, TpuShuffleExchangeExec) \
            or not isinstance(exchange.partitioning, HashPartitioning) \
            or exchange.shared:
        return None, ("one task: the aggregate does not read a hash "
                      "exchange of its own")
    if exchange.transport is not None \
            and not isinstance(exchange.transport, IciShuffleTransport):
        return None, "one task: the exchange is bound to another transport"
    if agg.mode != "complete" or not agg.group_exprs \
            or any(getattr(a, "single_pass", False) for a in agg.aggs):
        return None, ("one task: the aggregate cannot merge partial "
                      "buffers across an exchange")
    scans = [leaf for leaf in _leaves(exchange.child)
             if isinstance(leaf, TpuFileScanExec)
             and leaf._use_device_decode(conf)]
    if not scans:
        return None, "one task: no device-decoded Parquet scan to slice"
    # the table that splits best: most row groups, then most bytes
    row_groups, _, fact = max(
        ((len(s._device_rg_tasks()), s.static_bytes_estimate() or 0, s)
         for s in scans), key=lambda t: t[:2])
    why = _stage_verdict(exchange.child, fact)
    if why:
        return None, "one task: " + why
    return GangSplit(top, agg, exchange, fact, row_groups), (
        f"gang of {ndev} member tasks, one per device: "
        f"{fact.describe()} sliced by row group, every other table whole "
        f"in every member, partial aggregates over the ICI all-to-all "
        f"({exchange.partitioning.num_partitions} partitions)")


def _member_stage(node: TpuExec, fact, k: int, n: int) -> TpuExec:
    """A member's own copy of the stage, ``fact`` cut to share ``k`` of
    ``n``. A copy starts with none of what an operator compiles or opens
    per instance: a member compiles for its own chip, on its own thread."""
    import copy
    if node is fact:
        return fact.sliced(k, n)
    clone = copy.copy(node)
    for name, value in list(clone.__dict__.items()):
        if name in ("_fused_jit_cache", "_chain_jit_cache", "_pf_local"):
            del clone.__dict__[name]
        elif name.startswith("_jit"):
            clone.__dict__[name] = {} if isinstance(value, dict) else None
    clone.children = tuple(_member_stage(c, fact, k, n)
                           for c in node.children)
    return clone


class _Member:
    """One member task: thread, device, plan, context, outcome."""

    def __init__(self, k: int, device, plan: TpuExec, ctx: ExecCtx):
        self.k, self.device, self.plan, self.ctx = k, device, plan, ctx
        self.out: List[TpuBatch] = []
        self.error: Optional[BaseException] = None
        self.thread: Optional[threading.Thread] = None


def _member_ctx(ctx: ExecCtx) -> ExecCtx:
    """A member's own metric sink and deferred checks over the query's
    conf, memory manager, tracer and lifecycle context (the counters of
    one ExecCtx are written by one thread)."""
    m = ExecCtx(ctx.conf)
    m.tracer = ctx.tracer
    m.qctx = ctx.qctx
    return m


def _fold_metrics(ctx: ExecCtx, members: List[_Member]) -> None:
    """The members' per-operator metrics into the query's, by operator
    label (a member's copy of an operator keeps its planned id)."""
    for m in members:
        m.ctx.opm.finalize()
        for label, metrics in m.ctx.metrics.items():
            for name, metric in metrics.items():
                into = ctx.metrics.setdefault(label, {})
                if name not in into:
                    into[name] = metric
                elif name in _SET_METRICS:
                    into[name].value = max(into[name].value, metric.value)
                else:
                    into[name].value += metric.value


def run(gs: GangSplit, ctx: ExecCtx) -> List[TpuBatch]:
    """Run the split plan: the member tasks, then the operators above the
    aggregate over their gathered results. Returns the root's batches."""
    from ..expr.base import BoundReference
    from ..shuffle import ici
    from ..shuffle.partitioner import HashPartitioning
    from .exchange import _shuffle_ids
    transport = ici.local_transport(ctx.conf)
    ndev = transport.ndev
    agg, exchange = gs.agg, gs.exchange
    nparts = exchange.partitioning.num_partitions
    tracer = ctx.tracer
    parent = tracer.current_span_id()
    sid = next(_shuffle_ids)
    pschema = agg._partial_schema
    gang = ici.IciGang(transport, nparts, pschema, tracer, parent, sid)
    keys = [BoundReference(i, f.dtype, f.nullable)
            for i, f in enumerate(pschema.fields[:len(agg.group_exprs)])]
    members: List[_Member] = []
    for k in range(ndev):
        stage = _member_stage(exchange.child, gs.fact, k, ndev)
        ex = TpuShuffleExchangeExec(HashPartitioning(keys, nparts),
                                    agg.as_partial(stage),
                                    transport=gang.member(k))
        ex._op_id = getattr(exchange, "_op_id", None)
        members.append(_Member(k, gang.devices[k], agg.as_final(ex),
                               _member_ctx(ctx)))
    share = lambda k: k * gs.row_groups // ndev  # noqa: E731 (as sliced)

    def work(m: _Member):
        try:
            with jax.default_device(m.device), tracer.span(
                    "gang.member", cat="gang", parent_id=parent,
                    args={"member": m.k, "device": int(m.device.id),
                          "slice_row_groups":
                              share(m.k + 1) - share(m.k)}) as sp:
                m.out = list(m.plan.execute(m.ctx))
                m.ctx.check_deferred()
                sp.set(rows=int(m.ctx.metric(
                    gs.fact, "numOutputRows").value))
        except BaseException as e:  # noqa: BLE001 — handed to the query
            m.error = e
            gang.abort()
        finally:
            m.ctx.run_cleanups()

    for m in members:
        m.thread = threading.Thread(target=work, args=(m,), daemon=True,
                                    name=f"gang-member_{m.k}")
        m.thread.start()
    for m in members:
        m.thread.join()
    _fold_metrics(ctx, members)
    ctx.metric(exchange, "gangMembers").set(ndev)
    ctx.metric(exchange, "iciEpochs").value += gang.epochs
    ctx.metric(exchange, "iciBytes").value += gang.bytes
    failed = [m.error for m in members if m.error is not None]
    if failed:
        for m in members:
            m.ctx.discard_deferred()
        raise next((e for e in failed
                    if not isinstance(e, ici.GangAborted)), failed[0])
    # the ndev small results, together on one chip, once
    home = jax.config.jax_default_device or jax.local_devices()[0]
    with tracer.span("gather", cat="gang",
                     args={"batches": sum(len(m.out) for m in members)}):
        results = [ici._on_device(b, home) for m in members for b in m.out]
    node: TpuExec = DeviceBatchSourceExec(results, agg.output_schema)
    for parent_op in reversed(gs.top):
        node = parent_op.with_new_children([node])
    return list(node.execute(ctx))
