"""Physical operator layer.

TPU analog of the reference's `GpuExec` SparkPlan hierarchy (SURVEY.md
§2.2-B; reference mount empty — built from the capability inventory). Every
operator implements BOTH:

- ``execute(ctx)``     — iterator of device `TpuBatch`es. Each operator
  traces/jits its per-batch function once per capacity bucket; operators
  exchange materialized device batches (cross-operator XLA fusion — the
  whole-stage-codegen analog — is future work at the planner layer).
- ``execute_cpu(ctx)`` — iterator of pyarrow RecordBatches with Spark
  semantics; the CPU fallback path AND the oracle for the dual-run harness
  (SURVEY.md §4.1/4.4).

Operators carry `TpuMetric`s (opTime, numOutputRows, …) mirroring the
reference's GpuMetric surface (SURVEY.md §5.1).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import pyarrow as pa

from .. import datatypes as dt
from ..columnar.arrow_bridge import arrow_to_device, device_to_arrow
from ..columnar.batch import TpuBatch
from ..config import RapidsConf
from ..expr.base import EvalCtx
from ..programs import named_jit

__all__ = ["ExecCtx", "TpuMetric", "TpuExec", "LeafExec", "UnaryExec",
           "HostBatchSourceExec", "OpContract", "collect_arrow",
           "collect_arrow_cpu", "fused_batches", "fn_content_key"]


@dataclasses.dataclass(frozen=True)
class OpContract:
    """Static operator contract — the single source of truth the plan
    verifier (analysis/plan_verifier.py) checks before execution and the
    SUPPORTED_OPS.md generator renders. Every `TpuExec` subclass either
    inherits the permissive default or declares its invariants here;
    checks that need per-instance data (derived output schemas, bound
    expression inputs) live on the instance hooks below
    (`expected_output_schema`, `expr_bindings`, `resident_footprint`).
    """

    #: output schema must equal the (first) child's, field for field —
    #: names, dtypes, and nullability may only widen, never narrow.
    schema_preserving: bool = False
    #: the operator materializes its whole input device-resident at
    #: once with no out-of-core path (broadcast gather, single-pass
    #: aggregates) — the verifier checks its static byte estimate
    #: against the memory-ledger budget.
    resident_footprint: bool = False
    #: children that are both shuffle exchanges must agree on
    #: partitioning scheme and partition count (hash-join
    #: co-partitioning).
    requires_copartition: bool = False
    #: planner-inserted wrapper: the child must be an instance of the
    #: named class (checked by class name to avoid import cycles).
    wrapper_over: Optional[str] = None
    #: one-line contract note rendered into SUPPORTED_OPS.md.
    notes: str = ""

    def doc_flags(self) -> str:
        """Compact rendering for the generated supported-ops doc."""
        flags = []
        if self.schema_preserving:
            flags.append("schema-preserving")
        if self.resident_footprint:
            flags.append("resident-footprint")
        if self.requires_copartition:
            flags.append("co-partitioned children")
        if self.wrapper_over:
            flags.append(f"wraps {self.wrapper_over}")
        return ", ".join(flags)


class TpuMetric:
    """Accumulator metric, analog of GpuMetric over SQLMetric."""

    __slots__ = ("name", "value")

    def __init__(self, name: str):
        self.name = name
        self.value = 0

    def __iadd__(self, v):
        self.value += v
        return self

    def set(self, v):
        self.value = v

    def __repr__(self):
        return f"{self.name}={self.value}"


class ExecCtx:
    """Per-query execution context: conf snapshot + eval ctx + metric sink."""

    def __init__(self, conf: Optional[RapidsConf] = None):
        self.conf = conf or RapidsConf()
        self.eval_ctx = EvalCtx(
            ansi=self.conf.ansi,
            timezone=self.conf.get("spark.sql.session.timeZone"))
        self.metrics: Dict[str, Dict[str, TpuMetric]] = {}
        # DEBUG metrics block on device completion inside timed regions so
        # opTime is device time; otherwise timings are async-dispatch cost
        # (cheap, pipelining preserved).
        self.sync_metrics = \
            self.conf.get("spark.rapids.sql.metrics.level") == "DEBUG"
        from ..config import STAGE_FUSION
        self.stage_fusion = self.conf.get(STAGE_FUSION)
        from ..memory import DeviceMemoryManager
        # process-level: concurrent queries share one semaphore + ledger
        # (the reference's GpuSemaphore/RapidsBufferCatalog are singletons)
        self.mm = DeviceMemoryManager.shared(self.conf)
        # span tracer: the shared no-op unless spark.rapids.trace.dir is
        # set or a JAX profiler session is running NOW (so a caller that
        # profiles starts its session before it makes the ctx); cluster
        # workers overwrite this with a tracer joined to the driver's
        # trace context
        from ..obs.tracer import tracer_from_conf
        self.tracer = tracer_from_conf(self.conf)
        from ..obs.metrics import maybe_start_http_server
        maybe_start_http_server(self.conf)
        # always-on flight recorder adopts this query's bounds
        # (spark.rapids.flight.*); recording stays a bounded deque
        # append whether or not tracing is enabled
        from ..obs.recorder import RECORDER
        RECORDER.configure(self.conf)
        # always-on per-operator accounting (rows/batches/bytes via the
        # execute() shims below); deferred device row counts fold in at
        # the query's natural sync point (obs/opmetrics.py)
        from ..obs.opmetrics import OpMetricsCollector
        self.opm = OpMetricsCollector(self.conf)
        # query lifecycle (lifecycle.py): set by the collect roots /
        # cluster task runners; when present the execute shims below
        # run a cooperative cancellation/deadline check per batch
        self.qctx = None

    def metric(self, node: "TpuExec", name: str) -> TpuMetric:
        m = self.metrics.setdefault(node.node_label(), {})
        if name not in m:
            m[name] = TpuMetric(name)
        return m[name]

    # --- query-end cleanup ------------------------------------------------

    def register_cleanup(self, fn) -> None:
        """Run `fn` when the query finishes (shared exchange handles,
        etc.). Invoked by the collect paths; idempotent."""
        if not hasattr(self, "_cleanups"):
            self._cleanups = []
        self._cleanups.append(fn)

    def run_cleanups(self) -> None:
        fns = getattr(self, "_cleanups", None)
        if not fns:
            return
        self._cleanups = []
        for fn in fns:
            fn()

    # --- deferred device-side checks --------------------------------------
    # Assertions whose predicate lives on the device (a bool scalar,
    # True = violated). Reading it back eagerly would cost a host sync
    # in the middle of the query, so violations are recorded here and
    # raised at the query's first NATURAL readback (collect/download),
    # before any result reaches the caller. Used by the join's
    # build_unique hint probe and the regex engine's ASCII-data gate.

    def add_deferred_check(self, flag, message: str) -> None:
        if not hasattr(self, "deferred_checks"):
            self.deferred_checks = []
        self.deferred_checks.append((flag, message))

    def discard_deferred(self) -> None:
        """Drop pending checks without evaluating — called when a query
        FAILED before its natural sync point, so a reused ctx does not
        report the dead query's flags (and their device buffers are
        released)."""
        self.deferred_checks = []

    def check_deferred(self) -> None:
        """Evaluate and clear pending device-side checks; raises on the
        first batch of violations (ONE fused readback for all flags)."""
        checks = getattr(self, "deferred_checks", None)
        if not checks:
            return
        import jax
        self.deferred_checks = []
        flags = jax.device_get([f for f, _ in checks])
        bad = [msg for (_, msg), v in zip(checks, flags) if bool(v)]
        if bad:
            raise RuntimeError(
                "deferred device checks failed:\n  " + "\n  ".join(bad))


def _count_execute(fn):
    """Wrap an operator's ``execute`` with the always-on per-operator
    accounting shim (obs/opmetrics.py): rows / batches / outputBytes
    accumulate into the per-query metric store under the node's stable
    label. Per batch this is two integer adds and a host-side byte sum;
    batches whose live row count is device-resident defer the tiny
    scalar to the collector's ONE fused readback at the query's natural
    sync point — no extra host syncs on any path."""
    if getattr(fn, "_opm_wrapped", False):
        return fn

    def execute(self, ctx):
        opm = getattr(ctx, "opm", None)
        # cooperative cancellation point (lifecycle.py): one attribute
        # read per batch when nothing is cancelled; raises the
        # classified QueryCancelled between batches at EVERY operator
        qx = getattr(ctx, "qctx", None)
        # opm.enter: a subclass execute that delegates to a wrapped
        # super().execute (conditionless cross joins) must count each
        # batch once — the inner frame passes through
        if opm is None or not opm.enabled or not opm.enter(self):
            if qx is None:
                yield from fn(self, ctx)
                return
            for b in fn(self, ctx):
                qx.check()
                yield b
            return
        rows_m = ctx.metric(self, "rows")
        batches_m = ctx.metric(self, "batches")
        bytes_m = ctx.metric(self, "outputBytes")
        try:
            for b in fn(self, ctx):
                if qx is not None:
                    qx.check()
                batches_m.value += 1
                opm.count_rows(rows_m, b)
                try:
                    bytes_m.value += b.device_size_bytes()
                except Exception:  # noqa: BLE001 — best-effort
                    pass
                yield b
        finally:
            opm.exit(self)

    execute._opm_wrapped = True
    execute.__wrapped__ = fn
    execute.__doc__ = fn.__doc__
    return execute


def _count_execute_cpu(fn):
    """The CPU-island twin of ``_count_execute``: rows/batches count
    from the Arrow batches (free — host values), and the node is
    flagged ``cpuFallback`` so EXPLAIN ANALYZE and profiles show where
    a query left the device."""
    if getattr(fn, "_opm_wrapped", False):
        return fn

    def execute_cpu(self, ctx):
        opm = getattr(ctx, "opm", None)
        qx = getattr(ctx, "qctx", None)
        if opm is None or not opm.enabled or not opm.enter(self):
            if qx is None:
                yield from fn(self, ctx)
                return
            for rb in fn(self, ctx):
                qx.check()
                yield rb
            return
        rows_m = ctx.metric(self, "rows")
        batches_m = ctx.metric(self, "batches")
        ctx.metric(self, "cpuFallback").set(1)
        try:
            for rb in fn(self, ctx):
                if qx is not None:
                    qx.check()
                batches_m.value += 1
                rows_m.value += rb.num_rows
                yield rb
        finally:
            opm.exit(self)

    execute_cpu._opm_wrapped = True
    execute_cpu.__wrapped__ = fn
    execute_cpu.__doc__ = fn.__doc__
    return execute_cpu


class TpuExec:
    """Base physical operator."""

    children: Tuple["TpuExec", ...] = ()

    _label_counter = 0

    def __init__(self):
        TpuExec._label_counter += 1
        self._label_id = TpuExec._label_counter

    def __init_subclass__(cls, **kw):
        # every subclass that defines its own execute/execute_cpu gets
        # the per-operator accounting shims — metric plumbing for ALL
        # operators without touching each one
        super().__init_subclass__(**kw)
        if "execute" in cls.__dict__:
            cls.execute = _count_execute(cls.__dict__["execute"])
        if "execute_cpu" in cls.__dict__:
            cls.execute_cpu = _count_execute_cpu(
                cls.__dict__["execute_cpu"])

    # --- static metadata --------------------------------------------------
    @property
    def output_schema(self) -> dt.Schema:
        raise NotImplementedError(type(self).__name__)

    def pretty_name(self) -> str:
        n = type(self).__name__
        return n[3:] if n.startswith("Tpu") else n

    def node_label(self) -> str:
        """Metric/trace label. ``#op<N>`` when the planner stamped a
        stable per-plan instance id (obs/opmetrics.assign_op_ids —
        survives pickles, deep copies, and AQE reuse, so metrics fold
        across workers and runs); otherwise the process-local
        construction counter."""
        oid = getattr(self, "_op_id", None)
        if oid is not None:
            return f"{self.pretty_name()}#op{oid}"
        return f"{self.pretty_name()}#{self._label_id}"

    # --- planner hooks ----------------------------------------------------
    def tpu_supported(self) -> Optional[str]:
        """None if runnable on TPU, else the willNotWorkOnTpu reason."""
        return None

    # --- static contract (plan verifier + SUPPORTED_OPS.md) ---------------
    #: class-level operator contract; subclasses override with their
    #: invariants. The plan verifier and the doc generator both read
    #: this, so the doc can never drift from what is enforced.
    CONTRACT: "OpContract" = OpContract()

    @classmethod
    def contract(cls) -> "OpContract":
        return cls.CONTRACT

    def expected_output_schema(self) -> Optional[dt.Schema]:
        """Re-derive the output schema from the CURRENT children, for
        operators whose cached schema depends on child state (join,
        union, window override this). The verifier compares it against
        the declared `output_schema` — a mismatch means the tree was
        rebuilt over children the cached schema no longer describes.
        None = not re-derivable; operators whose schema is a pure
        function of their own bound expressions (project, aggregate)
        stay None — their stale-rebuild class is caught by the
        `expr_bindings` ordinal/dtype checks instead."""
        return None

    def expr_bindings(self) -> Sequence[Tuple[object, dt.Schema]]:
        """(expression tree, input schema) pairs: which schema each of
        this operator's bound expressions must resolve against. The
        verifier checks every BoundReference's ordinal/dtype/nullability
        against that schema. Default: all `expressions()` bind against
        the first child (joins and other multi-input ops override)."""
        if not self.children:
            return ()
        schema = self.children[0].output_schema
        return [(e, schema) for e in self.expressions()]

    def resident_footprint(self) -> bool:
        """Instance-level override of CONTRACT.resident_footprint for
        operators whose residency depends on configuration (e.g. an
        aggregate is resident only when a single-pass aggregate
        function is present)."""
        return self.contract().resident_footprint

    def static_bytes_estimate(self) -> Optional[int]:
        """Leaf-source byte estimate for the verifier's HBM footprint
        pass (host batches: exact; file scans: file sizes; None =
        unknown)."""
        return None

    #: Row-wise-map audit note rendered into SUPPORTED_OPS.md's stage-
    #: fusion section: operators implementing ``device_fn`` are fusable
    #: and need no note; every other operator states WHY it is a fusion
    #: barrier (the audited reason, not an omission). The doc generator
    #: reads this together with the live ``device_fn`` overrides, so the
    #: published table cannot drift from the code (tpu-lint
    #: --check-docs).
    FUSION_NOTE: str = "barrier: not audited"

    def device_fn(self):
        """Pure per-batch device function `(TpuBatch, EvalCtx) -> TpuBatch`
        when this operator is a row-wise map over one batch (project,
        filter-as-selection-mask, expand-as-traced-concat) — the unit of
        stage fusion. None for barriers (sort, aggregate, exchange) and
        multi-batch operators; barriers document why in ``FUSION_NOTE``.
        Operators that fuse via a ``fused_batches`` *tail* instead
        (aggregate's partial phase, the exchange writer's partition-key
        split) also say so there."""
        return None

    def fusion_content(self) -> str:
        """Content string identifying this operator's per-batch
        semantics for the fused-program cache key (``fn_content_key``).
        Defaults to ``describe()``; operators whose describe() omits
        semantics-bearing state (the exchange's partition key
        expressions) override."""
        return self.describe()

    def expressions(self) -> Sequence["object"]:
        """The expression trees this operator evaluates — walked by the
        planner for per-expression eligibility tagging (the RapidsMeta
        childExprs analog)."""
        return ()

    def with_new_children(self, children: Sequence["TpuExec"]) -> "TpuExec":
        """Rebuild this node over new children (planner transition
        insertion). Default: shallow copy with the children tuple swapped —
        valid because transitions preserve the child's output schema, so
        bound expression ordinals stay correct. Nodes with internal wiring
        (TopN) override."""
        import copy as _copy
        if len(children) == len(self.children) and \
                all(c is o for c, o in zip(children, self.children)):
            return self
        clone = _copy.copy(self)
        clone.children = tuple(children)
        return clone

    # --- required-column pushdown (exec/pruning.py) -----------------------
    #: how the operator takes part in column pruning, rendered into
    #: SUPPORTED_OPS.md beside the live ``child_requirements`` overrides
    PRUNING_NOTE: str = ("requires every column of its children; "
                         "pruning goes on below it")
    #: False where the subtree below must stay the objects they are (a
    #: cache replays what it materialized once)
    PRUNE_BELOW: bool = True

    def child_requirements(self, required) -> Optional[List[set]]:
        """Top-down half of column pruning: the ordinals of each child
        this operator reads when its parent reads the output ordinals
        ``required`` — its own bound expressions plus what it passes
        through. ``None`` (the default) states no requirement: the
        operator requires everything and pruning stops at it."""
        return None

    def pruned(self, children: Sequence["TpuExec"], maps, required):
        """Bottom-up half: ``(operator rebuilt over the narrowed
        children, old -> new ordinal map of its output)``. ``maps`` holds
        one old -> new ordinal map per child; every ``BoundReference``
        is re-bound through it (``pruning.remap``). The output may keep
        more than ``required``; the map must cover ``required``."""
        raise NotImplementedError(type(self).__name__)

    def __getstate__(self):
        """What a plan carries when it is copied (``with_new_children``),
        deep-copied or pickled (cluster tasks): everything but the
        pruning pass's memo of the trees it built from this node."""
        state = self.__dict__.copy()
        state.pop("_pruned_memo", None)
        return state

    def _passthrough_requirements(self, required):
        """``child_requirements`` of a unary operator whose output IS
        its child's: what the parent reads plus what its own
        expressions (``expr_bindings``) read."""
        from .pruning import refs
        return [set(required) | refs(e for e, _ in self.expr_bindings())]

    # --- execution --------------------------------------------------------
    def execute(self, ctx: ExecCtx) -> Iterator[TpuBatch]:
        raise NotImplementedError(type(self).__name__)

    def execute_cpu(self, ctx: ExecCtx) -> Iterator[pa.RecordBatch]:
        raise NotImplementedError(type(self).__name__)

    # --- tree utilities ---------------------------------------------------
    def tree_string(self, indent: int = 0) -> str:
        lines = [("  " * indent) + self.describe()]
        for c in self.children:
            lines.append(c.tree_string(indent + 1))
        return "\n".join(lines)

    def describe(self) -> str:
        return self.pretty_name()

    def __repr__(self):
        return self.tree_string()


def fn_content_key(f):
    """Stable content key for one fused-chain callable: op class +
    method name + the owner's semantic content string. Keyed on content,
    not id(): after a planner rebuild a recycled id could silently hit a
    stale program with different semantics. Identical keys imply
    identical per-batch semantics, so sharing a compiled program is
    correct — including across the global fused-decode cache the
    scan-rooted splice uses (io/parquet_device.py)."""
    owner = getattr(f, "__self__", None)
    if owner is None:
        return getattr(f, "__qualname__", repr(f))
    content = getattr(owner, "fusion_content", None)
    content = content() if content is not None else owner.describe()
    return (type(owner).__qualname__, getattr(f, "__name__", ""), content)


def _record_stage_time(ctx, metric, t0, out) -> None:
    """opTime for a fused stage, honestly: under async dispatch the
    wall-clock around the jitted call measures LAUNCH time, not compute
    — so the (t0, output) pair is handed to the opmetrics collector's
    completion watcher, which stamps the metric when the output is
    actually ready (the deferred-readback idiom extended to time; no
    sync on this thread). Launch cost stays visible as its own
    ``dispatchTime`` metric. DEBUG metrics (sync_metrics) and disabled
    opmetrics fall back to the synchronous wall-clock add."""
    if metric is None:
        return
    opm = getattr(ctx, "opm", None)
    if not ctx.sync_metrics and opm is not None \
            and opm.defer_stage_time(metric, t0, out):
        return
    metric.value += time.perf_counter() - t0


def fused_batches(consumer: TpuExec, ctx: ExecCtx, tail_fn=None,
                  metric: Optional[TpuMetric] = None) -> Iterator[TpuBatch]:
    """Stream the device batches feeding `consumer`, composing the chain of
    fusable operators below it — plus the consumer's own per-batch
    `tail_fn` — into ONE jitted XLA program per batch: the
    whole-stage-codegen analog (reference: operator-at-a-time cudf calls;
    here XLA fuses the chain into one kernel schedule, eliding intermediate
    HBM materialization). When the chain bottoms out at a scan whose
    device-decode path can splice the chain INTO its fused-decode program
    (``fused_scan_execute``), the whole stage — parquet decode included —
    runs as ONE dispatch per coalesced row-group batch. Falls back to
    per-op execution when `spark.rapids.sql.stageFusion.enabled` is off.
    Tails must be PURE per-batch functions: the OOM split-and-retry
    wrapper may re-run them over batch halves, yielding each half as its
    own stream item (the exchange writer's side effects therefore live
    outside the tail, after the yield)."""
    node = consumer.children[0]
    fns = []
    fused_nodes = []
    if ctx.stage_fusion:
        while isinstance(node, UnaryExec) and node.device_fn() is not None:
            fns.append(node.device_fn())
            fused_nodes.append(node)
            node = node.children[0]
        fns.reverse()
        fused_nodes.reverse()
    if tail_fn is not None:
        fns.append(tail_fn)
    if not fns:
        yield from node.execute(ctx)
        return
    key = tuple(fn_content_key(f) for f in fns)
    label = consumer.node_label()
    # fusion observability: every operator instance that executes inside
    # this consumer's program records WHICH program (the consumer's
    # stable op id) — a plain numeric metric, so it folds across
    # snapshots/workers and EXPLAIN ANALYZE can render the membership
    oid = getattr(consumer, "_op_id", None) or consumer._label_id
    for fn_node in fused_nodes:
        ctx.metric(fn_node, "fusedInto").set(oid)
    ctx.metric(consumer, "fusedChainOps").set(len(fns))
    dispatch_m = ctx.metric(consumer, "dispatchTime")
    # scan-rooted splice: a leaf that can run the chain INSIDE its own
    # fused-decode program declines with None when that path is off
    scan_fused = getattr(node, "fused_scan_execute", None)
    if scan_fused is not None and ctx.stage_fusion:
        gen = scan_fused(ctx, tuple(fns), key)
        if gen is not None:
            ctx.metric(node, "fusedInto").set(oid)
            try:
                while True:
                    try:
                        out = next(gen)
                    except StopIteration:
                        return
                    # the dispatch happened on the scan's feeder thread
                    # (its uploadTime/uploadWaitTime account for launch
                    # and wait) — the consumer's stage time starts at
                    # HANDOVER and runs to output readiness, so it is
                    # residual chain compute, not a re-count of the
                    # scan's read/plan/upload wall
                    t0 = time.perf_counter()
                    with ctx.tracer.span(label, cat="op", kind="op",
                                         args={"op": label,
                                               "fused": "scan"}):
                        if ctx.sync_metrics and isinstance(out, TpuBatch):
                            out.block_until_ready()
                        _record_stage_time(ctx, metric, t0, out)
                    yield out
            finally:
                # deterministic teardown: an early-closed consumer must
                # close the scan's feeder pipeline (ledger releases,
                # pool shutdown) now, not at GC time
                gen.close()
    cache = consumer.__dict__.setdefault("_fused_jit_cache", {})
    entry = cache.get(key)
    if entry is None:
        def composed(b, ectx):
            for f in fns:
                b = f(b, ectx)
            return b
        # hold the fns alongside the program: the key is content-based,
        # but the compiled program closes over these exact callables
        entry = (named_jit("fused_stage", composed, static_argnums=1),
                 fns)
        cache[key] = entry
    jitted = entry[0]
    rows = ctx.metric(consumer, "numOutputRows") if ctx.sync_metrics \
        else None
    for b in node.execute(ctx):
        with ctx.tracer.span(label, cat="op", kind="op",
                             args={"op": label, "fused": "stage"}):
            t0 = time.perf_counter()
            # split-and-retry on device OOM: the fused stage re-runs
            # over batch halves (memory.py; SURVEY.md §5.3 layer 3);
            # the query context carries the per-query budget and the
            # degradation ladder above the halving
            outs = ctx.mm.with_retry(
                b, lambda bb: jitted(bb, ctx.eval_ctx),
                qctx=getattr(ctx, "qctx", None))
            dispatch_m.value += time.perf_counter() - t0
            if ctx.sync_metrics:
                for out in outs:
                    if isinstance(out, TpuBatch):
                        out.block_until_ready()
                        rows += out.num_rows  # syncs; DEBUG metrics only
            _record_stage_time(ctx, metric, t0, outs)
        yield from outs


class LeafExec(TpuExec):
    children = ()


class UnaryExec(TpuExec):
    def __init__(self, child: TpuExec):
        super().__init__()
        self.children = (child,)

    @property
    def child(self) -> TpuExec:
        return self.children[0]

    @property
    def output_schema(self) -> dt.Schema:
        return self.child.output_schema

    def _parents_columns(self, required):
        """``child_requirements`` of a unary operator that reads
        nothing itself: what its parent reads."""
        return [set(required)]

    # column pruning of the planner-inserted wrappers (transitions, AQE
    # reader and join switch): ``Class(child)`` over whatever the child
    # became
    WRAPPER_PRUNING_NOTE = ("planner-inserted wrapper: requires its "
                            "parent's columns")

    def _wrapper_pruned(self, children, maps, required):
        if children[0] is self.child:
            return self, maps[0]
        return type(self)(children[0]), maps[0]


class HostBatchSourceExec(LeafExec):
    """Leaf over in-memory host Arrow batches — the LocalTableScan analog
    and the entry point the JVM-side bridge feeds (Arrow C Data batches)."""

    FUSION_NOTE = "chain root: source leaf — fusable chains begin above it"

    def __init__(self, batches: Sequence[pa.RecordBatch],
                 schema: Optional[dt.Schema] = None):
        super().__init__()
        self.batches = list(batches)
        if schema is None:
            from ..columnar.arrow_bridge import engine_schema
            if not self.batches:
                raise ValueError("empty source needs an explicit schema")
            schema = engine_schema(self.batches[0].schema)
        self._schema = schema

    @property
    def output_schema(self):
        return self._schema

    def static_bytes_estimate(self):
        return sum(rb.nbytes for rb in self.batches)

    def _normalized(self):
        """Input batches cast (checked) to the declared schema, so the
        device and CPU paths see identical values."""
        from ..columnar.arrow_bridge import arrow_schema
        target = arrow_schema(self._schema)
        for rb in self.batches:
            if rb.schema != target:
                rb = pa.RecordBatch.from_arrays(
                    [rb.column(i).cast(target.field(i).type)
                     for i in range(rb.num_columns)], schema=target)
            yield rb

    def execute(self, ctx):
        rows = ctx.metric(self, "numOutputRows")
        t = ctx.metric(self, "uploadTime")
        label = self.node_label()
        for rb in self._normalized():
            with ctx.tracer.span(label, cat="op", kind="op",
                                 args={"op": label, "phase": "upload"}):
                t0 = time.perf_counter()
                b = arrow_to_device(rb, self._schema)
                t.value += time.perf_counter() - t0
            rows += rb.num_rows
            yield b

    def execute_cpu(self, ctx):
        yield from self._normalized()


class DeviceBatchSourceExec(LeafExec):
    """Leaf over already-resident device batches (bench/internal use)."""

    FUSION_NOTE = "chain root: source leaf — fusable chains begin above it"

    def __init__(self, batches: Sequence[TpuBatch], schema: dt.Schema):
        super().__init__()
        self.batches = list(batches)
        self._schema = schema

    @property
    def output_schema(self):
        return self._schema

    def static_bytes_estimate(self):
        try:
            return sum(b.device_size_bytes() for b in self.batches)
        except Exception:  # noqa: BLE001 — estimate only, never fail
            return None

    def execute(self, ctx):
        yield from self.batches

    def execute_cpu(self, ctx):
        from ..columnar.arrow_bridge import device_to_arrow
        for b in self.batches:
            yield device_to_arrow(b)


def collect_arrow(plan: TpuExec, ctx: Optional[ExecCtx] = None) -> pa.Table:
    """Run the TPU path and download results as one Arrow table."""
    ctx = ctx or ExecCtx()
    try:
        t0 = time.perf_counter()
        # admission control (GpuSemaphore analog; fair/cancellable when
        # the ctx carries a QueryContext)
        with ctx.mm.task_slot(getattr(ctx, "qctx", None)):
            ctx.metric(plan, "ledgerWaitTime").value += \
                time.perf_counter() - t0
            batches = [device_to_arrow(b) for b in plan.execute(ctx)]
    except BaseException:
        ctx.discard_deferred()  # a reused ctx must not report dead flags
        ctx.opm.discard()
        raise
    finally:
        ctx.run_cleanups()
    ctx.check_deferred()  # the download was the natural sync point
    ctx.opm.finalize()    # ... and satisfied the deferred row counts
    from ..columnar.arrow_bridge import arrow_schema
    return pa.Table.from_batches(batches, schema=arrow_schema(
        plan.output_schema))


def collect_arrow_cpu(plan: TpuExec, ctx: Optional[ExecCtx] = None) \
        -> pa.Table:
    """Run the CPU oracle path."""
    ctx = ctx or ExecCtx()
    batches = list(plan.execute_cpu(ctx))
    from ..columnar.arrow_bridge import arrow_schema
    return pa.Table.from_batches(batches, schema=arrow_schema(
        plan.output_schema))
