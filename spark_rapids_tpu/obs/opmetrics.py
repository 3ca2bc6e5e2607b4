"""Per-operator runtime metrics: the GpuMetric surface, end to end.

The reference attaches a ``GpuMetric`` set (opTime, concatTime,
spillTime, semaphoreWaitTime, ...) to every physical operator and
surfaces it in the Spark UI (SURVEY.md §5.1, :147); its profiling tool
compares those metrics across runs (SURVEY.md :211-212). This module is
that layer for the TPU engine:

- **stable operator-instance ids** — the planner stamps every node of a
  rebuilt plan with a pre-order ``_op_id`` (``assign_op_ids``), so the
  same logical operator keeps ONE label across AQE deep-copied reuse,
  task pickles, worker processes, and runs of the same plan. Labels are
  ``<Op>#op<N>`` (``TpuExec.node_label``); plans that never met the
  planner fall back to the process-local ``#<counter>`` labels.
- **always-on per-operator accounting** — ``exec/base.py`` wraps every
  operator's ``execute``/``execute_cpu`` with a counting shim
  (rows/batches/outputBytes plus a CPU-fallback flag) that is
  lock-cheap like the flight recorder: per batch it is two integer adds
  and, for batches whose live row count is still device-resident, a
  deferred scalar collected by ONE fused readback at the query's
  natural sync point (``OpMetricsCollector.finalize`` — the
  ``check_deferred`` idiom, zero extra syncs). ``opTime``/``spillTime``/
  ``uploadWaitTime``/``deviceChunks``/... keep coming from the
  operators themselves; everything lands in the same per-query
  ``ctx.metrics`` store under the stable label.
- **cross-worker aggregation** — cluster workers flush a
  ``<task>.opm.json`` snapshot next to their rendezvous markers
  (``flush_task_opmetrics``); the driver folds the WINNING attempts'
  snapshots (``fold_snapshots``) into per-operator totals plus
  per-task maxima and a task-skew ratio. Torn or missing files are
  skipped, never fatal — a crashed worker leaves partial attribution,
  not a broken query.
- **EXPLAIN ANALYZE rendering** (``render_analyzed``) and **persistent
  query profiles** (``build_profile``/``write_profile``): one
  ``profile-<id>.json`` per query under ``spark.rapids.history.dir``
  with the same retention bound as traces; ``tools/profiling.py``
  grows ``history`` and ``compare`` over them.
"""
from __future__ import annotations

import json
import os
import threading
import time
import uuid
from typing import Dict, List, Optional, Sequence, Tuple

from ..config import RapidsConf, register

__all__ = ["OP_METRICS_ENABLED", "HISTORY_DIR", "HISTORY_ENABLED",
           "OpMetricsCollector", "assign_op_ids", "plan_source",
           "snapshot_ctx",
           "fold_ctx", "fold_snapshots", "flush_task_opmetrics",
           "read_task_opmetrics", "render_analyzed", "plan_nodes",
           "top_op_sinks", "build_profile", "write_profile",
           "read_profiles"]

OP_METRICS_ENABLED = register(
    "spark.rapids.metrics.op.enabled", True,
    "Always-on per-operator metric accounting (rows, batches, output "
    "bytes, CPU-fallback flags) on every executed operator, feeding "
    "EXPLAIN ANALYZE, query profiles, and the event log's top-sink "
    "embedding. Recording is two integer adds per batch plus one fused "
    "device readback at the query's natural sync point; disable only "
    "to rule it out while measuring.")
HISTORY_ENABLED = register(
    "spark.rapids.history.enabled", True,
    "Write one query-profile JSON per executed query (plan with stable "
    "operator ids + folded per-operator metrics) when "
    "spark.rapids.history.dir is set — the input to "
    "`profiling history` / `profiling compare`.")
HISTORY_DIR = register(
    "spark.rapids.history.dir", "",
    "Directory for persistent query profiles "
    "(profile-<id>.json, one per query, spark.rapids.trace.maxFiles "
    "retention). Empty disables profile history.")

#: metric names the fold treats as row-like (integers summed across
#: tasks) vs time-like (seconds, rendered in ms) — anything else is
#: summed and rendered raw.
_TIME_METRICS = frozenset((
    "opTime", "spillTime", "uploadTime", "uploadWaitTime", "scanTime",
    "assembleTime", "arenaWaitTime", "downloadTime", "writeTime",
    "concatTime", "ledgerWaitTime", "dispatchTime", "fetchTime"))

#: metrics that are identifiers/flags (fold by max across tasks), not
#: accumulators (fold by sum): the fused-program membership id and the
#: chain length are the same value on every task that executed the node;
#: the scan's fetchAheadMax is a maximum already
_IDENTITY_METRICS = frozenset(("fusedInto", "fusedChainOps",
                               "cpuFallback", "fetchAheadMax"))


# process-wide fused-stage completion watcher: ONE daemon thread per
# process (lazily started; queries/collectors come and go per query —
# a per-collector thread would leak one thread per executed query on
# long-lived sessions/workers). Stamping is a plain float add on the
# enqueued TpuMetric, so per-query ownership needs no bookkeeping.
_STAGE_TIMEQ = None
_STAGE_TIMER_LOCK = threading.Lock()
# set when a drain barrier times out: the watcher is stuck on a
# never-ready output (wedged dispatch), so further deferrals fall back
# to wall-clock adds instead of growing an unserviced queue (and every
# later finalize skips the doomed 30s wait)
_STAGE_TIMER_WEDGED = False


def _stage_timer_queue():
    global _STAGE_TIMEQ
    if _STAGE_TIMEQ is None:
        with _STAGE_TIMER_LOCK:
            if _STAGE_TIMEQ is None:
                import queue as _queue
                q = _queue.Queue()
                threading.Thread(target=_stage_timer_loop, args=(q,),
                                 name="opm-stage-timer",
                                 daemon=True).start()
                _STAGE_TIMEQ = q
    return _STAGE_TIMEQ


def _stage_timer_loop(q) -> None:
    while True:
        item = q.get()
        if isinstance(item, threading.Event):
            item.set()  # a finalize's drain barrier
            continue
        collector, metric, t0, out = item
        item = None  # no dangling ref to the pytree while idle on get()
        try:
            import jax
            jax.block_until_ready(out)
            out = None
            # measured here, APPLIED on the query thread at the drain
            # barrier: metric.value += from two threads would be a lost-
            # update race with the owning operator's own adds
            with collector._times_lock:
                collector._stage_results.append(
                    (metric, time.perf_counter() - t0))
        except Exception:  # noqa: BLE001 — accounting is best-effort
            pass


class OpMetricsCollector:
    """Per-query collector the execute() shims feed. Row counts whose
    batches carry a device-resident live count are deferred: the shim
    appends the tiny scalar here and ``finalize`` folds them in with
    ONE fused ``device_get`` at the query's natural sync point —
    exactly the ``ExecCtx.check_deferred`` pattern, so the always-on
    accounting never adds a host sync of its own.

    Fused-stage TIME rides the same deferral philosophy: under async
    dispatch the wall-clock around a jitted call measures launch cost,
    not compute, so ``defer_stage_time`` hands (metric, t0, output) to
    the process-wide completion watcher, which MEASURES time-to-ready
    (``jax.block_until_ready`` off the query thread — a completion
    wait, not a readback, so the query thread keeps dispatching) and
    parks the result; ``finalize`` drains the watcher and APPLIES the
    measurements on the query's own thread (no cross-thread ``+=`` on
    a live metric), so EXPLAIN ANALYZE / profiles report honest
    per-stage time with zero syncs added to the execution path."""

    __slots__ = ("enabled", "_pending", "_active", "_deferred_times",
                 "_stage_results", "_times_lock")

    def __init__(self, conf: Optional[RapidsConf] = None):
        conf = conf or RapidsConf()
        self.enabled = conf.get(OP_METRICS_ENABLED)
        self._pending: List[Tuple[object, object]] = []
        # nodes with a counting shim currently live on this query's
        # stack: an execute() that delegates to a wrapped super()
        # implementation (cross joins) must count each batch ONCE
        self._active: set = set()
        # whether THIS query enqueued stage times on the process-wide
        # watcher (finalize only pays the drain barrier if so), plus
        # the watcher's measured (metric, seconds) results awaiting
        # application on this query's own thread
        self._deferred_times = False
        self._stage_results: List[Tuple[object, float]] = []
        self._times_lock = threading.Lock()

    def enter(self, node) -> bool:
        """Claim accounting for one node's execution; False when an
        enclosing shim of the SAME node already counts (re-entrant
        super() delegation — the inner frame must pass through)."""
        if id(node) in self._active:
            return False
        self._active.add(id(node))
        return True

    def exit(self, node) -> None:
        self._active.discard(id(node))

    def count_rows(self, metric, batch) -> None:
        """Accumulate a device batch's live row count into ``metric``
        without syncing: known-on-host counts add immediately; traced
        counts defer to ``finalize``."""
        n = getattr(batch, "_num_rows_cache", None)
        if n is not None:
            metric.value += n
            return
        rc = getattr(batch, "row_count", None)
        if rc is None:
            return
        if getattr(batch, "selection", None) is not None:
            # lazy-filtered batch: dispatch the (async) mask popcount
            # now so only the scalar result stays alive until finalize
            from ..columnar.batch import _live_count
            rc = _live_count(batch)
        self._pending.append((metric, rc))

    # --- deferred fused-stage timing -------------------------------------

    def defer_stage_time(self, metric, t0, out) -> bool:
        """Attribute ``now() - t0`` to ``metric`` when ``out`` (any jax
        pytree) completes on device, measured by the process-wide
        watcher thread — the honest opTime for an async-dispatched
        fused stage. Returns False (caller falls back to wall-clock)
        when accounting is disabled."""
        if not self.enabled or _STAGE_TIMER_WEDGED:
            return False
        _stage_timer_queue().put((self, metric, t0, out))
        self._deferred_times = True
        return True

    def _drain_stage_times(self) -> None:
        """Barrier the watcher: every deferred stage time THIS query
        enqueued is folded in before this returns (the queue is FIFO,
        so a barrier enqueued now follows them; bounded wait — a wedged
        device must not hang the query's sync point on accounting)."""
        if not self._deferred_times:
            return
        self._deferred_times = False
        barrier = threading.Event()
        _stage_timer_queue().put(barrier)
        if not barrier.wait(timeout=30.0):
            # the watcher is stuck behind a never-ready output: stop
            # feeding it (wall-clock fallback from here on) rather
            # than queueing pytrees it will never release
            global _STAGE_TIMER_WEDGED
            _STAGE_TIMER_WEDGED = True
        with self._times_lock:
            results, self._stage_results = self._stage_results, []
        for metric, dt_s in results:  # applied on the query's thread
            metric.value += dt_s

    def finalize(self) -> None:
        """Fold every deferred row count in with one fused readback.
        Called at the query's natural sync points (collect download,
        worker task flush); metrics must never fail the query."""
        self._drain_stage_times()
        if not self._pending:
            return
        pending, self._pending = self._pending, []
        try:
            import jax
            vals = jax.device_get([v for _, v in pending])
        except Exception:  # noqa: BLE001 — accounting is best-effort
            return
        for (m, _), v in zip(pending, vals):
            m.value += int(v)

    def discard(self) -> None:
        self._pending = []


def plan_source(root) -> str:
    """``sql`` when any node of the tree was compiled by the SQL
    frontend (sql_to_plan marks its root; rebuilds shallow-copy the
    mark), else ``plan`` — the label the query-duration histogram and
    profiles carry."""
    stack = [root]
    seen = set()
    while stack:
        n = stack.pop()
        if id(n) in seen:
            continue
        seen.add(id(n))
        if getattr(n, "_sql_origin", False):
            return "sql"
        stack.extend(getattr(n, "children", ()))
    return "plan"


# --- stable operator-instance ids -------------------------------------------

def assign_op_ids(root, force: bool = False) -> None:
    """Stamp every node of a plan with a stable pre-order instance id
    (1-based). Aliased subtrees (self-joins hold the same node object
    under two parents) keep one id; deep copies — AQE reuse, task
    pickles — carry their ids with them, which is exactly what makes
    cross-worker and cross-run folding line up. No-op when the root is
    already stamped unless ``force``."""
    if not force and getattr(root, "_op_id", None) is not None:
        return
    seen = set()
    counter = [0]

    def walk(node):
        if id(node) in seen:
            return
        seen.add(id(node))
        counter[0] += 1
        node._op_id = counter[0]
        for c in getattr(node, "children", ()):
            walk(c)

    walk(root)


def _fold_key(label: str) -> str:
    """Fold key for one metric label: the stable ``op<N>`` part when
    present (so an exchange and the ProcessShuffleReadExec that
    replaced it fold together), else the whole label."""
    if "#op" in label:
        return "op" + label.rsplit("#op", 1)[1]
    return label


# --- snapshots and folding ---------------------------------------------------

def snapshot_ctx(ctx) -> Dict[str, Dict[str, float]]:
    """One task's/query's per-operator metrics as plain JSON-able
    numbers (finalizes deferred row counts first)."""
    opm = getattr(ctx, "opm", None)
    if opm is not None:
        opm.finalize()
    return {label: {name: m.value for name, m in ms.items()}
            for label, ms in ctx.metrics.items()}


def fold_snapshots(snaps: Sequence[Dict]) -> Dict[str, Dict]:
    """Fold per-task snapshots (``{"task":..., "ops": {label: {m:
    v}}}`` dicts, or bare ``{label: {m: v}}`` maps) into per-operator
    aggregates::

        {"op3": {"label": "ProjectExec#op3",
                 "metrics": {...totals...},
                 "max": {...per-task maxima...},
                 "tasks": 2, "skew": 1.4}}

    ``skew`` is max/mean of per-task opTime (1.0 = perfectly even),
    the straggler-attribution number SURVEY's profiling tool reports
    per operator."""
    agg: Dict[str, Dict] = {}
    for snap in snaps:
        ops = snap.get("ops", snap) if isinstance(snap, dict) else {}
        for label, ms in ops.items():
            if not isinstance(ms, dict):
                continue
            key = _fold_key(label)
            st = agg.setdefault(key, {"label": label, "metrics": {},
                                      "max": {}, "tasks": 0,
                                      "_op_times": []})
            # deterministic representative label across fold orders
            if label < st["label"]:
                st["label"] = label
            st["tasks"] += 1
            for name, v in ms.items():
                if not isinstance(v, (int, float)):
                    continue
                if name in _IDENTITY_METRICS:
                    # identifiers/flags, not accumulators: summing the
                    # same program id across worker tasks would render
                    # a nonsense op id
                    st["metrics"][name] = max(
                        st["metrics"].get(name, 0), v)
                else:
                    st["metrics"][name] = st["metrics"].get(name, 0) + v
                if v > st["max"].get(name, float("-inf")):
                    st["max"][name] = v
            st["_op_times"].append(float(ms.get("opTime", 0.0) or 0.0))
    for st in agg.values():
        ts = st.pop("_op_times")
        mean = sum(ts) / len(ts) if ts else 0.0
        st["skew"] = round(max(ts) / mean, 2) if mean > 0 else 1.0
    return agg


def fold_ctx(ctx) -> Dict[str, Dict]:
    """The single-process (local collect) fold: one snapshot, tasks=1."""
    return fold_snapshots([{"ops": snapshot_ctx(ctx)}])


def top_op_sinks(folded: Dict[str, Dict], n: int = 3) -> List[Dict]:
    """The top-N per-operator time sinks, the shape the event log
    embeds so qualification/profiling tools get operator attribution
    without opening the profile file."""
    ranked = sorted(folded.values(),
                    key=lambda st: -st["metrics"].get("opTime", 0.0))
    out = []
    for st in ranked[:n]:
        t = st["metrics"].get("opTime", 0.0)
        if t <= 0:
            continue
        out.append({"op": st["label"], "time_s": round(t, 6),
                    "rows": int(st["metrics"].get("rows", 0))})
    return out


# --- worker-side flush / driver-side harvest ---------------------------------

def flush_task_opmetrics(task_path: str, ctx, task_id: str,
                         attempt: int) -> Optional[str]:
    """Atomically commit this attempt's per-operator snapshot next to
    its rendezvous markers (``<task>.opm.json``) — same protocol as the
    ``.spans`` file, written BEFORE the .ok/.err marker so the driver's
    harvest finds it. Best effort: accounting must never fail (or
    resurrect) the task."""
    opm = getattr(ctx, "opm", None)
    if opm is None or not opm.enabled:
        return None
    try:
        doc = {"task": task_id, "attempt": attempt,
               "ops": snapshot_ctx(ctx)}
        tmp = task_path + ".opm.json.tmp"
        with open(tmp, "w") as f:
            json.dump(doc, f)
        os.replace(tmp, task_path + ".opm.json")
        return task_path + ".opm.json"
    except Exception:  # noqa: BLE001 — observability is best-effort
        return None


def read_task_opmetrics(tasks_dir: str,
                        winners: Sequence[Tuple[str, int, int]]) \
        -> List[Dict]:
    """The committed (winning) attempts' snapshots: one per (task_id,
    attempt, worker) triple the scheduler retired as ``task_ok``.
    Missing files (crashed worker, opmetrics disabled) and torn JSON
    are skipped — partial attribution, never a failed harvest."""
    out: List[Dict] = []
    for task_id, attempt, worker in winners:
        path = os.path.join(
            tasks_dir, f"{task_id}.a{attempt}.w{worker}.task.opm.json")
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, json.JSONDecodeError, ValueError):
            continue
        if isinstance(doc, dict) and isinstance(doc.get("ops"), dict):
            out.append(doc)
    return out


# --- rendering ---------------------------------------------------------------

def _fmt_metric(name: str, v) -> str:
    if name in _TIME_METRICS:
        return f"{name}={v * 1e3:.2f}ms"
    if isinstance(v, float) and v.is_integer():
        v = int(v)
    if name in ("outputBytes", "inputBytes") and v >= 10 << 20:
        return f"{name}={v / (1 << 20):.1f}MB"
    return f"{name}={v}"

_COMPACT_METRICS = ("rows", "batches", "opTime", "spillTime",
                    "uploadWaitTime", "ledgerWaitTime", "deviceChunks",
                    "fallbackChunks", "nullFreeChunks", "fusedDispatches",
                    "scanPrograms", "columnsRead", "columnsPruned",
                    "gangMembers", "iciEpochs", "iciBytes")


def render_analyzed(root, folded: Dict[str, Dict],
                    wall_s: Optional[float] = None,
                    formatted: bool = False,
                    cluster: str = "local") -> str:
    """The EXPLAIN ANALYZE text: the executed plan tree with every node
    tagged by its stable instance id and annotated with its folded
    metrics (rows / batches / time / spill / device-vs-fallback chunk
    counts; on cluster runs also tasks + per-task max + skew).
    ``formatted`` renders EVERY recorded metric instead of the compact
    set. Nodes with no recorded batches are marked — a fused operator
    executes inside its consumer's XLA program, a CPU island under a
    transition."""
    head = f"== Analyzed Physical Plan ({cluster}"
    if wall_s is not None:
        head += f", {wall_s * 1e3:.1f} ms"
    head += ") =="
    lines = [head]
    seen = set()

    def key_for(node):
        oid = getattr(node, "_op_id", None)
        return f"op{oid}" if oid is not None else node.node_label()

    def walk(node, depth):
        pad = "  " * depth
        label = node.node_label()
        st = folded.get(key_for(node)) or folded.get(label)
        tag = "#op" in label and label.rsplit("#", 1)[1] or label
        if st is None:
            ann = "[not executed directly: fused into a parent stage]"
        else:
            m = dict(st["metrics"])
            if "cpuFallback" in m:
                m.pop("cpuFallback", None)
                pad_mark = "!"
            else:
                pad_mark = ""
            fused_into = m.pop("fusedInto", None)
            chain_ops = m.pop("fusedChainOps", None)
            names = list(m) if formatted else \
                [n for n in _COMPACT_METRICS if n in m]
            parts = []
            if fused_into is not None:
                # which program this instance executed inside — the
                # whole-stage-fusion membership record
                parts.append(f"fused into op{int(fused_into)}'s program")
            if chain_ops is not None and (formatted or chain_ops > 1):
                parts.append(f"fusedChainOps={int(chain_ops)}")
            parts += [_fmt_metric(n, m[n]) for n in names]
            if st.get("tasks", 1) > 1:
                parts.append(f"tasks={st['tasks']}")
                mx = st["max"].get("opTime")
                if mx:
                    parts.append(f"maxTaskOpTime={mx * 1e3:.2f}ms")
                parts.append(f"skew={st.get('skew', 1.0)}")
            ann = "[" + ", ".join(parts) + "]" + \
                (" [CPU]" if pad_mark else "")
        lines.append(f"{pad}{node.describe()} ({tag})  {ann}")
        if id(node) in seen:
            return  # aliased subtree: render its children once
        seen.add(id(node))
        for c in getattr(node, "children", ()):
            walk(c, depth + 1)

    walk(root, 0)
    return "\n".join(lines)


def plan_nodes(root) -> List[Dict]:
    """Flat (depth, label, describe) list of the plan — the profile's
    re-renderable plan record (no exec tree needed to inspect it)."""
    out = []

    def walk(node, depth):
        out.append({"depth": depth, "label": node.node_label(),
                    "op": node.pretty_name(),
                    "describe": node.describe()})
        for c in getattr(node, "children", ()):
            walk(c, depth + 1)

    walk(root, 0)
    return out


# --- persistent query profiles ----------------------------------------------

def build_profile(root, folded: Dict[str, Dict], wall_s: float,
                  query: str = "", source: str = "plan",
                  cluster: str = "local",
                  trace_id: Optional[str] = None,
                  conf: Optional[RapidsConf] = None,
                  extra: Optional[Dict] = None) -> Dict:
    """One query's persistent profile document."""
    from ..tools.event_log import plan_fingerprint
    pid = trace_id or uuid.uuid4().hex[:16]
    try:
        import jax
        device_kind = jax.devices()[0].device_kind
    except Exception:  # noqa: BLE001 — profiles must never fail a query
        device_kind = "unknown"
    doc = {
        "version": 1,
        "profile_id": f"profile-{pid}",
        "ts": time.time(),
        "query": query,
        "source": source,
        "cluster": cluster,
        "wall_s": round(wall_s, 6),
        # the hardware the numbers were measured on: `profiling
        # compare` refuses cross-device comparisons (a CPU-backend run
        # vs a TPU run is a ~1000x apples-to-oranges ratio, not a
        # regression)
        "device_kind": device_kind,
        "fingerprint": plan_fingerprint(root),
        "nodes": plan_nodes(root),
        "ops": folded,
        "conf": {k: str(v) for k, v in (conf.items() if conf else {})
                 .items()},
    }
    if extra:
        doc.update(extra)
    return doc


def write_profile(conf: RapidsConf, doc: Dict) -> Optional[str]:
    """Atomically commit one profile under spark.rapids.history.dir
    with the shared trace-file retention bound; no-op (None) when
    history is unconfigured or disabled."""
    base = conf.get(HISTORY_DIR)
    if not base or not conf.get(HISTORY_ENABLED):
        return None
    from ..obs.tracer import TRACE_MAX_FILES
    from .recorder import prune_oldest
    try:
        os.makedirs(base, exist_ok=True)
        path = os.path.join(base, doc["profile_id"] + ".json")
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(doc, f)
        os.replace(tmp, path)
        prune_oldest(base, conf.get(TRACE_MAX_FILES),
                     prefix="profile-", suffix=".json")
        return path
    except OSError:
        return None  # history must never fail the query


def read_profiles(path: str) -> List[Tuple[str, Dict]]:
    """Every readable profile under a history dir (or one file),
    sorted by timestamp; torn files skipped."""
    files: List[str] = []
    if os.path.isdir(path):
        files = [os.path.join(path, n) for n in sorted(os.listdir(path))
                 if n.startswith("profile-") and n.endswith(".json")]
    elif os.path.exists(path):
        files = [path]
    out: List[Tuple[str, Dict]] = []
    for fp in files:
        try:
            with open(fp) as f:
                doc = json.load(f)
        except (OSError, json.JSONDecodeError):
            continue
        if isinstance(doc, dict) and isinstance(doc.get("ops"), dict):
            out.append((fp, doc))
    out.sort(key=lambda t: t[1].get("ts", 0.0))
    return out
