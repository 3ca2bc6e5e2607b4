"""Planner / override engine (L5).

TPU analog of the reference's `GpuOverrides.scala` + `RapidsMeta.scala` +
`GpuTransitionOverrides.scala` (SURVEY.md §2.2-A "Override engine" /
"Transition optimizer", §3.2; reference mount empty — built from the
capability description). The input plan is an exec tree whose every node
carries BOTH a device path (`execute`) and a Spark-semantics CPU path
(`execute_cpu`); the planner

1. wraps each node in a `NodeMeta` (the SparkPlanMeta analog),
2. tags TPU eligibility bottom-up: master kill switch, per-op and
   per-expression conf kill switches (`spark.rapids.sql.exec.<Name>` /
   `.expression.<Name>`), `tpu_supported()` hooks on operators and every
   expression tree node (`willNotWorkOnTpu` reasons accumulate),
3. rebuilds the tree with `DeviceToHostExec` / `HostToDeviceExec`
   transitions at every device<->CPU boundary (CPU islands execute via
   their Spark-semantics `execute_cpu` path),
4. renders `spark.rapids.sql.explain` = ALL | NOT_ON_GPU output.

`PhysicalPlan.collect()` is the runner: it picks `execute` or
`execute_cpu` at the root according to the final placement.
"""
from __future__ import annotations

import sys
from typing import List, Optional, Sequence

import pyarrow as pa

from .config import EXPLAIN, RapidsConf, SQL_ENABLED
from .exec.base import ExecCtx, TpuExec
from .exec.transitions import DeviceToHostExec, HostToDeviceExec

__all__ = ["NodeMeta", "PhysicalPlan", "TpuOverrides", "overrides"]


def _walk_expr(expr) -> List[object]:
    """Flatten an expression tree (incl. the root) in pre-order."""
    out = [expr]
    for c in getattr(expr, "children", ()):
        out.extend(_walk_expr(c))
    return out


class NodeMeta:
    """Per-node planning state (SparkPlanMeta analog): the wrapped exec,
    child metas, and the accumulated cannot-run-on-TPU reasons."""

    def __init__(self, node: TpuExec, children: Sequence["NodeMeta"]):
        self.node = node
        self.children = list(children)
        self.reasons: List[str] = []
        self.on_device = True  # decided by tag()

    def will_not_work(self, reason: str):
        self.reasons.append(reason)

    def tag(self, conf: RapidsConf):
        """Eligibility checks for this node (children tagged separately)."""
        from .config import INCOMPATIBLE_OPS
        name = self.node.pretty_name()
        if not conf.get(SQL_ENABLED):
            self.will_not_work("spark.rapids.sql.enabled is false")
        if not conf.is_op_enabled("exec", name):
            self.will_not_work(
                f"the operator has been disabled by "
                f"spark.rapids.sql.exec.{name}")
        r = self.node.tpu_supported()
        if r:
            self.will_not_work(r)
        conf_hook = getattr(self.node, "tpu_supported_conf", None)
        if conf_hook is not None:
            r = conf_hook(conf)
            if r:
                self.will_not_work(r)
        allow_incompat = conf.get(INCOMPATIBLE_OPS)
        for root in self.node.expressions():
            for e in _walk_expr(root):
                ename = e.pretty_name()
                if not conf.is_op_enabled("expression", ename):
                    self.will_not_work(
                        f"expression {e!r} has been disabled by "
                        f"spark.rapids.sql.expression.{ename}")
                    continue
                incompat = getattr(e, "incompat", None)
                if incompat and not allow_incompat:
                    self.will_not_work(
                        f"expression {e!r} is incompatible ({incompat}) "
                        "and spark.rapids.sql.incompatibleOps.enabled "
                        "is false")
                    continue
                er = e.tpu_supported()
                if er:
                    self.will_not_work(f"expression {e!r}: {er}")
                    continue
                e_hook = getattr(e, "tpu_supported_conf", None)
                if e_hook is not None:
                    er = e_hook(conf)
                    if er:
                        self.will_not_work(f"expression {e!r}: {er}")
        self.on_device = not self.reasons

    # --- explain ---------------------------------------------------------

    def explain_lines(self, mode: str, depth: int = 0) -> List[str]:
        out = []
        pad = "  " * depth
        desc = self.node.describe()
        if self.on_device:
            if mode == "ALL":
                out.append(f"{pad}*Exec* {desc} will run on TPU")
        else:
            why = "; ".join(self.reasons)
            out.append(f"{pad}!Exec! {desc} cannot run on TPU because "
                       f"{why}")
        for c in self.children:
            out.extend(c.explain_lines(mode, depth + 1))
        return out




def query_span(ctx: ExecCtx, root: TpuExec):
    """The root span of a query (``spark:query`` on the profiler), for
    every path that executes a plan locally: ``PhysicalPlan.collect``
    and the ML bridge. Disabled, it is the shared no-op and the plan is
    not fingerprinted."""
    if not ctx.tracer.enabled:
        return ctx.tracer.span("query")
    from .tools.event_log import plan_fingerprint
    return ctx.tracer.span("query", cat="query",
                           args={"fingerprint": plan_fingerprint(root)})


class PhysicalPlan:
    """Planner output: the rebuilt tree + placement + explain report."""

    def __init__(self, root: TpuExec, root_on_device: bool,
                 meta: NodeMeta, conf: RapidsConf,
                 source: str = "plan"):
        self.root = root
        self.root_on_device = root_on_device
        self.meta = meta
        self.conf = conf
        self.source = source  # "sql" | "plan": how the tree was built
        self.last_ctx: Optional[ExecCtx] = None  # metrics of last collect
        self.last_qctx = None  # lifecycle context of last collect
        self.last_profile_path: Optional[str] = None
        self._gang = None  # gang_split(), decided once

    @property
    def output_schema(self):
        return self.root.output_schema

    def fallback_nodes(self) -> List[str]:
        """pretty names of every operator that fell back to CPU (the
        assert_gpu_fallback_collect hook)."""
        out = []

        def rec(m: NodeMeta):
            if not m.on_device:
                out.append(m.node.pretty_name())
            for c in m.children:
                rec(c)

        rec(self.meta)
        return out

    def gang_split(self):
        """``(split, verdict)`` of ``exec/gang.py::split`` for this plan,
        decided once: whether it runs as one member task per chip under
        ``spark.rapids.shuffle.mode=ICI``, or as one task, and why."""
        if self._gang is None:
            from .config import SHUFFLE_MODE
            if self.conf.get(SHUFFLE_MODE) != "ICI":
                self._gang = (None, "")  # nothing to say of a plan that
                # never asked for the mesh
            elif not self.root_on_device or self.fallback_nodes():
                self._gang = (None, "one task: the plan leaves the device")
            else:
                from .exec.gang import split
                self._gang = split(self.root, self.conf)
        return self._gang

    def gang_verdict(self) -> str:
        """How the plan runs across the session's chips, in a line (empty
        unless the conf asks for the ICI mesh)."""
        return self.gang_split()[1]

    def explain(self, mode: Optional[str] = None) -> str:
        mode = mode or self.conf.get(EXPLAIN)
        if mode == "NONE":
            return ""
        lines = self.meta.explain_lines(mode)
        if mode == "ALL" and self.gang_verdict():
            lines.append("ici: " + self.gang_verdict())
        return "\n".join(lines)

    def collect(self, ctx: Optional[ExecCtx] = None,
                qctx=None) -> pa.Table:
        import contextlib
        from .config import PROFILE_PATH
        prof_dir = self.conf.get(PROFILE_PATH)
        if prof_dir:
            import jax
            session = jax.profiler.trace(prof_dir)
        else:
            session = contextlib.nullcontext()
        # the session before the ExecCtx: its tracer is live when a
        # profiler session runs, and the query's spans land in the
        # profile beside the device operations
        with session:
            return self._collect(ctx or ExecCtx(self.conf), qctx)

    def _collect(self, ctx: ExecCtx, qctx) -> pa.Table:
        import time as _time
        self.last_ctx = ctx
        # query lifecycle (lifecycle.py): default-on — every collect
        # gets a QueryContext (deadline/tenant/budget from conf) unless
        # the caller supplied one; the token threads through ExecCtx
        # into every operator shim and the upload pipelines
        from .lifecycle import (LIFECYCLE_ENABLED, QueryCancelled,
                                QueryContext)
        if qctx is None:
            qctx = getattr(ctx, "qctx", None)
        if qctx is None and self.conf.get(LIFECYCLE_ENABLED):
            qctx = QueryContext.from_conf(self.conf)
        ctx.qctx = qctx
        self.last_qctx = qctx
        # telemetry warehouse bracket: counter baselines now, one
        # sealed row at every exit (completed/cancelled/degraded/
        # failed) — obs/attribution.py. None when the warehouse is off.
        from .obs.attribution import QueryAttribution
        attrib = QueryAttribution.begin(self.conf)
        from .columnar.arrow_bridge import arrow_schema
        _t0 = _time.perf_counter()
        schema = arrow_schema(self.root.output_schema)
        try:
            with query_span(ctx, self.root):
                if self.root_on_device:
                    rbs = self._collect_device(ctx, qctx)
                else:
                    # CPU-rooted plans can still contain device islands
                    # (under DeviceToHostExec): their cleanups and
                    # deferred device checks run below too
                    rbs = self._collect_cpu(ctx)
                # from the last download to the return: one span, so
                # that no second of a query lies under the query alone
                with ctx.tracer.span("finish", cat="query"):
                    ctx.run_cleanups()
                    ctx.check_deferred()  # downloads were the sync point
                    wall_s = _time.perf_counter() - _t0
                    self.last_wall_s = wall_s
                    # fold the deferred row counts in now — the
                    # downloads above were the natural sync point, so
                    # this readback is already satisfied
                    ctx.opm.finalize()
                    from .obs.metrics import QUERY_DURATION
                    QUERY_DURATION.labels(self.source,
                                          "local").observe(wall_s)
                    # (the event's span rollup is taken here, before
                    # the query and finish spans close: it holds every
                    # other span)
                    from .tools.event_log import log_query_event
                    log_query_event(self, ctx, wall_s)
                    self._write_profile(ctx, wall_s)
                    self._emit_warehouse(attrib, ctx, qctx, wall_s)
        except QueryCancelled as e:
            self._report_cancel(ctx, e, _time.perf_counter() - _t0)
            self._emit_warehouse(attrib, ctx, qctx,
                                 _time.perf_counter() - _t0, error=e)
            raise
        except BaseException as e:
            self._emit_warehouse(attrib, ctx, qctx,
                                 _time.perf_counter() - _t0, error=e)
            raise
        finally:
            # width-1 exclusivity must not outlive the query (a
            # degraded CPU-island subtree can set it while holding no
            # admission slot — nothing else would clear it)
            if qctx is not None:
                ctx.mm.admission.clear_exclusive(qctx.query_id)
            # failed queries are exactly the ones whose timeline is
            # needed; a trace-dir write failure must never fail a query
            if ctx.tracer.enabled:
                from .obs.tracer import TRACE_DIR
                try:
                    ctx.tracer.write_chrome(self.conf.get(TRACE_DIR))
                except OSError:
                    pass
        return pa.Table.from_batches(rbs, schema=schema)

    def _collect_device(self, ctx: ExecCtx, qctx) -> List:
        """Device-rooted execution under fair admission; the
        degradation ladder's terminal rung answers a
        ladder-exhausted OOM with the classified CPU fallback. Query-end
        cleanups and deferred checks of a run that succeeded are the
        caller's (``_collect``: the ``finish`` span)."""
        import contextlib
        from .columnar.arrow_bridge import device_to_arrow
        from .memory import TpuRetryOOM
        try:
            with contextlib.ExitStack() as slot:
                # GpuSemaphore admission: the blocking happens at entry,
                # and is charged to the root operator (the
                # semaphoreWaitTime analog)
                with ctx.tracer.span("admit", cat="query",
                                     timed=True) as wait:
                    slot.enter_context(ctx.mm.task_slot(qctx))
                ctx.metric(self.root, "ledgerWaitTime").value += wait.dur
                rbs = []
                gang, _ = self.gang_split()
                if gang is not None:  # one member task per chip
                    from .exec.gang import run as run_gang
                    batches = run_gang(gang, ctx)
                else:
                    batches = self.root.execute(ctx)
                for b in batches:
                    with ctx.tracer.span("download", cat="query") as down:
                        rb = device_to_arrow(b)
                        down.set(rows=rb.num_rows, bytes=rb.nbytes)
                    rbs.append(rb)
        except TpuRetryOOM as oom:
            ctx.discard_deferred()  # dead attempt's flags
            ctx.opm.discard()
            ctx.run_cleanups()
            if qctx is None or not getattr(oom, "ladder_exhausted",
                                           False):
                raise
            # ladder rung `cpu`: re-run on the Spark-semantics CPU
            # path (the shims flag every operator cpuFallback, so
            # EXPLAIN ANALYZE/profiles show the degradation per
            # operator); the rung itself was already counted by
            # DegradationLadder.escalate
            from .obs.recorder import RECORDER
            RECORDER.record("lifecycle", ev="cpu_fallback",
                            query=qctx.query_id,
                            detail=str(oom)[:200])
            # drop the aborted device attempt's per-operator counts:
            # the shims re-count on the CPU rerun, and keeping the
            # residue would double rows/batches in EXPLAIN ANALYZE
            # and the query profile
            for ms in ctx.metrics.values():
                for name in ("rows", "batches", "outputBytes"):
                    ms.pop(name, None)
            ctx.metric(self.root, "ladderCpuFallback").set(1)
            return self._collect_cpu(ctx)
        except BaseException:
            ctx.discard_deferred()  # dead query's flags
            ctx.opm.discard()
            ctx.run_cleanups()
            raise
        return rbs

    def _collect_cpu(self, ctx: ExecCtx) -> List:
        try:
            return list(self.root.execute_cpu(ctx))
        except BaseException:
            ctx.discard_deferred()
            ctx.opm.discard()
            ctx.run_cleanups()
            raise

    def _report_cancel(self, ctx: ExecCtx, e, wall_s: float) -> None:
        """Classified-cancel evidence: one event-log line (type
        query_cancelled) + a flight-recorder event; the Prometheus
        counter was incremented by the token at classification time."""
        from .obs.recorder import RECORDER
        RECORDER.record("lifecycle", ev="cancelled_query",
                        query=e.query_id, reason=e.reason,
                        wall_s=round(wall_s, 6))
        from .tools.event_log import log_query_cancelled
        try:
            log_query_cancelled(self.conf, e, wall_s,
                                source=self.source)
        except OSError:
            pass  # evidence must never mask the cancellation

    def _emit_warehouse(self, attrib, ctx, qctx, wall_s: float,
                        error=None) -> None:
        """One telemetry-warehouse row for this collect — the folded
        per-operator metrics carry exact scan/fusion/row attribution;
        counter deltas (inside ``finish``) carry transports and spill.
        Best-effort like ``_write_profile``: telemetry never fails the
        query it describes."""
        if attrib is None:
            return
        try:
            from .obs.opmetrics import fold_ctx
            folded = fold_ctx(ctx)
        except Exception:  # noqa: BLE001 — partial row beats no row
            folded = {}
        attrib.finish(root=self.root, folded=folded, qctx=qctx,
                      wall_s=wall_s, source=self.source, error=error)

    def _write_profile(self, ctx: ExecCtx, wall_s: float) -> None:
        """Persist one query-profile JSON (spark.rapids.history.dir) —
        the record `profiling history`/`compare` mine."""
        from .obs.opmetrics import (HISTORY_DIR, build_profile, fold_ctx,
                                    write_profile)
        if not self.conf.get(HISTORY_DIR):
            return  # don't pay the fold/fingerprint when history is off
        try:
            tr = getattr(ctx, "tracer", None)
            tid = tr.trace_id if tr is not None \
                and getattr(tr, "enabled", False) else None
            doc = build_profile(
                self.root, fold_ctx(ctx), wall_s, source=self.source,
                cluster="local", trace_id=tid, conf=self.conf,
                extra={"fallbacks": self.fallback_nodes()})
            self.last_profile_path = write_profile(self.conf, doc)
        except Exception:  # noqa: BLE001 — history must never fail
            pass           # the query it records

    def explain_analyze(self, formatted: bool = False) -> str:
        """The EXPLAIN ANALYZE text for the last collect(): the
        executed tree with per-operator rows / batches / time / spill /
        decode-coverage annotations (obs/opmetrics.py). Requires a
        prior collect() on this plan."""
        from .obs.opmetrics import fold_ctx, render_analyzed
        ctx = self.last_ctx
        if ctx is None:
            return self.explain("ALL") + \
                "\n(no metrics: run collect() first)"
        ctx.opm.finalize()
        text = render_analyzed(self.root, fold_ctx(ctx),
                               wall_s=getattr(self, "last_wall_s", None),
                               formatted=formatted, cluster="local")
        if self.gang_verdict():
            text += "\nici: " + self.gang_verdict()
        return text

    def metrics_report(self, ctx: Optional[ExecCtx] = None) -> str:
        """Explain-style tree annotated with the metrics the last
        collect() (or the given ctx) accumulated per operator — opTime /
        spillTime / row counts, so regressions are attributable to a
        node (SURVEY.md §5.1/§5.5; run with metrics.level=DEBUG for
        device-time opTime)."""
        ctx = ctx or self.last_ctx
        metrics = ctx.metrics if ctx is not None else {}

        def fmt(v):
            if isinstance(v, float):
                return f"{v * 1e3:.2f}ms"
            return str(v)

        lines = []

        def rec(node: TpuExec, depth: int):
            m = metrics.get(node.node_label(), {})
            ann = ", ".join(f"{k}: {fmt(mm.value)}"
                            for k, mm in sorted(m.items()))
            pad = "  " * depth
            lines.append(f"{pad}{node.describe()}"
                         + (f"  [{ann}]" if ann else ""))
            for c in node.children:
                rec(c, depth + 1)

        rec(self.root, 0)
        return "\n".join(lines)


class TpuOverrides:
    """The override rule: wrap -> tag -> convert (SURVEY.md §3.2)."""

    def __init__(self, conf: Optional[RapidsConf] = None):
        self.conf = conf or RapidsConf()

    def _wrap(self, node: TpuExec) -> NodeMeta:
        return NodeMeta(node, [self._wrap(c) for c in node.children])

    def _tag(self, meta: NodeMeta):
        for c in meta.children:
            self._tag(c)
        meta.tag(self.conf)

    def _convert(self, meta: NodeMeta) -> TpuExec:
        """Rebuild with transitions: a device parent over a CPU child gets
        HostToDeviceExec; a CPU parent over a device child gets
        DeviceToHostExec (GpuTransitionOverrides analog). Batch-size-
        sensitive device ops re-entering from a CPU island additionally get
        a coalesce so they see full batches, not CPU-island crumbs."""
        from .config import BATCH_SIZE_ROWS
        from .exec.aggregate import TpuHashAggregateExec
        from .exec.exchange import TpuCoalesceBatchesExec
        from .exec.joins import _BaseJoinExec
        from .exec.sort import TpuSortExec
        batch_sensitive = (TpuHashAggregateExec, _BaseJoinExec, TpuSortExec)
        new_children = []
        for c in meta.children:
            built = self._convert(c)
            if meta.on_device and not c.on_device:
                built = HostToDeviceExec(built)
                if isinstance(meta.node, batch_sensitive):
                    built = TpuCoalesceBatchesExec(
                        built, target_rows=self.conf.get(BATCH_SIZE_ROWS))
            elif not meta.on_device and c.on_device:
                built = DeviceToHostExec(built)
            built = self._maybe_aqe(c, built)
            new_children.append(built)
        out = meta.node.with_new_children(new_children)
        return self._maybe_aqe_join(meta, out)

    def _maybe_aqe(self, meta: NodeMeta, built: TpuExec) -> TpuExec:
        """With spark.sql.adaptive.enabled, wrap device-side shuffle
        exchanges in the adaptive reader (coalesce + skew split,
        exec/aqe.py) — inserted like transitions, below the consumer.
        An exchange instance seen for a second time (self-joins reuse
        the same subtree object) is flagged `shared`: it materializes
        once and every consumer reads the same stage (the
        ReusedExchangeExec analog, SURVEY.md:161)."""
        from .config import ADAPTIVE_ENABLED
        from .exec.exchange import TpuShuffleExchangeExec
        if not self.conf.get(ADAPTIVE_ENABLED):
            return built
        if meta.on_device and isinstance(built, TpuShuffleExchangeExec):
            # _seen_exchanges is reset per apply(): the exchanges are
            # alive for the whole walk, so id() is unambiguous there —
            # but across applies a freed id could recur (CPython reuses
            # addresses) and falsely flag a single-consumer exchange
            if id(built) in self._seen_exchanges:
                built.shared = True
            self._seen_exchanges.add(id(built))
            from .exec.aqe import TpuAQEShuffleReadExec
            return TpuAQEShuffleReadExec(built)
        return built

    def _verify(self, root: TpuExec) -> None:
        """Static contract pass over the REBUILT tree (transitions and
        AQE wrappers included) — on by default, fail-fast: a plan that
        violates an operator contract is rejected with a named reason
        before any kernel runs (analysis/plan_verifier.py)."""
        from .config import VERIFY_PLAN
        if not self.conf.get(VERIFY_PLAN):
            return
        from .analysis.plan_verifier import (PlanVerificationError,
                                             report_rejection,
                                             verify_plan)
        report = verify_plan(root, self.conf)
        if not report.ok:
            report_rejection(self.conf, report, root)
            raise PlanVerificationError(report)

    def _maybe_aqe_join(self, meta: NodeMeta, built: TpuExec) -> TpuExec:
        """With AQE: wrap device-side shuffled hash joins over exchange
        children in the runtime strategy switch (shuffled -> broadcast
        demotion from sync-free stage size — exec/aqe.py,
        SURVEY.md:161)."""
        from .config import ADAPTIVE_ENABLED
        from .exec.joins import TpuShuffledHashJoinExec
        if not self.conf.get(ADAPTIVE_ENABLED) or not meta.on_device:
            return built
        if isinstance(built, TpuShuffledHashJoinExec):
            from .exec.aqe import TpuAQEJoinExec, _unwrap_exchange
            if _unwrap_exchange(built.right) is not None:
                return TpuAQEJoinExec(built)
        return built

    def apply(self, plan: TpuExec) -> PhysicalPlan:
        self._seen_exchanges = set()
        # required-column pushdown over the tree as the frontend built
        # it (exec/pruning.py): always, for every frontend
        from .exec.pruning import prune_plan
        meta = self._wrap(prune_plan(plan))
        self._tag(meta)
        root = self._convert(meta)
        self._verify(root)
        # stable per-plan operator-instance ids: metric labels survive
        # pickles, deep copies, AQE reuse and worker processes, so
        # EXPLAIN ANALYZE / profiles fold per INSTANCE instead of the
        # old name-based dedup across AQE-duplicated labels
        from .obs.opmetrics import assign_op_ids
        assign_op_ids(root, force=True)
        source = "sql" if getattr(plan, "_sql_origin", False) else "plan"
        pp = PhysicalPlan(root, meta.on_device, meta, self.conf,
                          source=source)
        # flight-recorder tap: an incident bundle wants to know what
        # fell back to CPU and why without re-planning — one bounded
        # event per planned query in the always-on ring
        from .obs.recorder import RECORDER
        if RECORDER.enabled:
            reasons = []

            def _fb(m: NodeMeta):
                if not m.on_device and m.reasons:
                    reasons.append(f"{m.node.pretty_name()}: "
                                   + "; ".join(m.reasons)[:120])
                for c in m.children:
                    _fb(c)

            _fb(meta)
            RECORDER.record("plan", n_fallbacks=len(reasons),
                            fallbacks=" | ".join(reasons[:8])[:600])
        mode = self.conf.get(EXPLAIN)
        if mode in ("ALL", "NOT_ON_GPU"):
            text = pp.explain(mode)
            if text:
                # stderr, never stdout: driver scripts (chip_smoke.py,
                # benchmark/run.py) end stdout with one JSON result line
                print(text, file=sys.stderr)
        return pp


def overrides(plan: TpuExec,
              conf: Optional[RapidsConf] = None) -> PhysicalPlan:
    """Convenience: run the override pass over an exec tree."""
    return TpuOverrides(conf).apply(plan)
