"""Multi-process execution: driver + worker OS processes.

TPU analog of the reference's executor model (SURVEY.md:186-189, §3.4:
separate executor JVMs exchanging shuffle blocks; reference mount empty).
This is rung 1 of the blueprint's shuffle ladder verbatim — "plain Spark
host shuffle of Arrow-serialized batches, works day one, any topology"
(SURVEY.md:524-527): each worker is a real OS process with its own
device runtime; stages exchange through the HOST transport's Arrow-IPC
files on a shared filesystem; the driver is the scheduler.

Execution model (Spark's, §2.6 data parallelism):
  - the driver splits the physical plan at shuffle-exchange boundaries
    into stages, deepest first;
  - a map stage ships each worker a pickled plan slice (a partition of
    the stage's leaf input) + the exchange's Partitioning; workers
    execute on their own device runtime and write per-(map, partition)
    Arrow IPC files via `HostShuffleTransport`, staged per attempt and
    atomically committed (first commit wins — see shuffle/host.py);
  - the next stage's plan reads those files through
    `ProcessShuffleReadExec` (each worker owns a partition range);
  - the final stage's per-partition results concatenate on the driver.

Scheduling/rendezvous is filesystem-based (task pickles + claim/done/err
markers + heartbeat files) — no sockets to configure, matching how
Spark's shuffle files need only shared storage. Fault tolerance lives in
`scheduler/task_scheduler.py` (the TaskSetManager analog): failed tasks
retry on other workers, dead/wedged workers are detected via process
polls + heartbeat staleness and respawned, stragglers optionally get
speculative duplicates. Task pickles carry only plan structure (plans
are pickled BEFORE any execution, so jit caches are empty).
"""
from __future__ import annotations

import copy
import json
import os
import pickle
import subprocess
import sys
import tempfile
import threading
import time
import traceback
from typing import Dict, List, Optional, Sequence, Tuple

import pyarrow as pa

from . import datatypes as dt
from .config import (FLIGHT_ENABLED, FLIGHT_STRAGGLER_FACTOR,
                     HEARTBEAT_INTERVAL, HEARTBEAT_TIMEOUT,
                     INJECT_FAULTS, RapidsConf,
                     SHUFFLE_FETCH_MAX_RETRIES,
                     SHUFFLE_FETCH_RETRY_WAIT_MS,
                     SHUFFLE_MAX_STAGE_RETRIES)
from .exec.base import ExecCtx, LeafExec, TpuExec
from .lifecycle import QueryCancelled as _QueryCancelled
from .memory import SpillReadError as _SpillReadError
from .obs.metrics import (METRICS_ENABLED, REGISTRY,
                          flush_worker_metrics, maybe_start_http_server,
                          read_worker_metrics, render_merged_snapshots)
from .obs.recorder import (RECORDER, flush_worker_ring,
                           next_incident_seq, read_flight_dumps,
                           read_worker_rings, resolve_flight_dir,
                           write_incident_bundle)
from .obs.tracer import (NULL_TRACER, TRACE_DIR, TRACE_MAX_FILES, Tracer,
                         tracer_from_conf)
from .scheduler import TaskScheduler, TaskSpec
from .scheduler.task_scheduler import FetchFailedError, GangFailedError
from .shuffle import integrity
from .shuffle.host import (HostShuffleTransport, SHUF_BYTES_FETCHED,
                           SHUF_FETCH_WAIT, SHUF_PARTS_FETCHED)
from .shuffle.transport import FetchFailure

__all__ = ["TpuProcessCluster", "ProcessShuffleReadExec",
           "run_process_query"]

_STAGE_RERUNS = REGISTRY.counter(
    "rapids_shuffle_stage_reruns_total",
    "Map tasks re-executed from lineage because a reader classified "
    "their committed shuffle output as missing/corrupt/torn or "
    "persistently unreadable.")


class ProcessShuffleReadExec(LeafExec):
    """Reduce-side leaf: streams the Arrow-IPC partition files a map
    stage wrote (the RapidsCachingReader / shuffle-fetch analog for the
    file transport — SURVEY.md §2.2-D). Only COMMITTED attempt output is
    visible: map tasks write into per-attempt staging dirs and publish
    with one atomic rename, so a zombie attempt racing its retry can
    never interleave files here."""

    def __init__(self, shuffle_root: str, shuffle_id: int,
                 partitions: Sequence[int], schema: dt.Schema,
                 expected_mapouts: Optional[Sequence[str]] = None):
        super().__init__()
        self.shuffle_root = shuffle_root
        self.shuffle_id = shuffle_id
        self.partitions = list(partitions)
        self._schema = schema
        # the driver's lineage knowledge: one task key per map task
        # that committed output into this shuffle — a whole committed
        # dir that later vanished is detected as kind=missing instead
        # of silently reading fewer rows
        self.expected_mapouts = list(expected_mapouts or [])

    @property
    def output_schema(self):
        return self._schema

    def describe(self):
        return (f"ProcessShuffleReadExec [s{self.shuffle_id} "
                f"p={self.partitions}]")

    def tpu_supported(self):
        return None

    def _block_index(self):
        """{pid: [(path, manifest_meta)]} the reader must consume —
        ONE dir walk + manifest parse per task (manifests are immutable
        after commit), and manifest-driven, so a file that should exist
        but doesn't is a classified failure, not a shorter stream."""
        d = os.path.join(self.shuffle_root, f"s{self.shuffle_id}")
        return integrity.expected_partition_index(
            d, self.expected_mapouts, shuffle_id=self.shuffle_id)

    def _host_batches(self, ctx: Optional[ExecCtx] = None):
        tracer = ctx.tracer if ctx is not None else NULL_TRACER
        conf = ctx.conf if ctx is not None else RapidsConf()
        retries = conf.get(SHUFFLE_FETCH_MAX_RETRIES)
        wait_s = conf.get(SHUFFLE_FETCH_RETRY_WAIT_MS) / 1e3
        fetched = SHUF_PARTS_FETCHED.labels("process")
        fbytes = SHUF_BYTES_FETCHED.labels("process")
        fwait = SHUF_FETCH_WAIT.labels("process")
        try:
            index = self._block_index()
        except FetchFailure as ff:
            HostShuffleTransport._record_fetch_failure(
                ff, -1, transport="process")
            raise
        for pid in self.partitions:
            # stream one file at a time (large shuffles must not pin a
            # whole partition's tables in host memory); the fetch span
            # covers only blocked-on-IO time and is emitted
            # retroactively, parented on the enclosing op/task span
            parent = tracer.current_span_id()
            t_wall = time.time()
            io_s = 0.0
            try:
                for path, meta in index.get(pid, []):
                    t1 = time.perf_counter()
                    payload = integrity.read_block(
                        path, meta, shuffle_id=self.shuffle_id,
                        max_retries=retries, retry_wait_s=wait_s,
                        on_retry=lambda n, e: RECORDER.record(
                            "shuffle", ev="fetch_retry",
                            sid=self.shuffle_id, part=int(pid), n=n,
                            error=str(e)[:120]))
                    table = pa.ipc.open_file(
                        pa.BufferReader(payload)).read_all()
                    dt_io = time.perf_counter() - t1
                    io_s += dt_io
                    fwait.observe(dt_io)
                    fbytes.inc(table.nbytes)
                    for rb in table.combine_chunks().to_batches():
                        if rb.num_rows:
                            yield rb
            except FetchFailure as ff:
                # kind-labeled metric + flight-recorder event, then
                # escalate: the worker loop turns this into a
                # .fetchfail marker the driver recovers from
                HostShuffleTransport._record_fetch_failure(
                    ff, pid, transport="process")
                raise
            fetched.inc()
            # flight-recorder tap: fetch-blocked time lands in the
            # always-on ring even with tracing disabled
            RECORDER.record("shuffle", ev="fetch", sid=self.shuffle_id,
                            part=int(pid), wait_s=round(io_s, 6))
            if tracer.enabled:
                tracer.emit(
                    f"shuffle_fetch s{self.shuffle_id} p{pid}",
                    "shuffle", t_wall, io_s, parent_id=parent)

    def execute(self, ctx: ExecCtx):
        from .columnar.arrow_bridge import arrow_to_device
        for rb in self._host_batches(ctx):
            b = arrow_to_device(rb, self._schema)
            # fetched uploads are device-memory-ledger-visible, like the
            # in-process host transport's (shuffle/host.py): eviction
            # pressure sees them and the flight recorder gets the
            # reserve/release transitions for its HBM timeline. Released
            # on handoff — the consumer owns the batch from here.
            sb = ctx.mm.register(b, pinned=True)
            sb.release()
            yield b

    def execute_cpu(self, ctx: ExecCtx):
        yield from self._host_batches(ctx)


# --- worker-side task execution (one function per task kind) ---------------

def _run_map_task(payload: Dict, tracer=NULL_TRACER,
                  obs_sink: Optional[Dict] = None) -> None:
    """Execute a map plan slice and write its partitions as Arrow IPC
    files into an attempt-private staging dir, then commit atomically
    (HostShuffleTransport is the writer; batch i of this slice is map id
    base+i so multi-batch slices never collide). Losing the commit race
    to a sibling attempt is SUCCESS: the winner's output is complete."""
    from .shuffle.host import HostShuffleTransport
    conf = RapidsConf(payload["conf"])
    plan: TpuExec = payload["plan"]
    partitioning = payload["partitioning"].bind(plan.output_schema)
    transport = HostShuffleTransport(conf, threads=0,
                                     root=payload["shuffle_root"])
    sid = payload["shuffle_id"]
    task_key = payload.get("task_id", f"m{payload['map_id_base']}")
    attempt = payload.get("attempt", 0)
    transport.register_shuffle(sid, partitioning.num_partitions)
    staging = transport.begin_task_attempt(sid, task_key, attempt)
    ctx = ExecCtx(conf)
    ctx.tracer = tracer  # join the driver's trace, not a fresh one
    # lifecycle: the worker-side token polls the driver's cancel
    # marker between batches and honors the wall deadline locally
    from .lifecycle import QueryContext
    ctx.qctx = QueryContext.for_worker(payload, conf)
    if obs_sink is not None:
        # exposed BEFORE execution so a failed attempt's partial
        # per-operator snapshot can still flush next to its .err
        obs_sink["ctx"] = ctx
    base = payload["map_id_base"]
    try:
        for i, batch in enumerate(plan.execute(ctx)):
            with tracer.span(f"shuffle_write s{sid} m{base + i}",
                             cat="shuffle"):
                pids = partitioning.partition_ids_device(batch,
                                                         ctx.eval_ctx)
                writer = transport.writer(sid, base + i, subdir=staging)
                writer.write_unsplit(batch, pids)
                writer.close()
    except BaseException:
        transport.abort_task_attempt(sid, task_key, attempt)
        raise
    with tracer.span(f"shuffle_commit s{sid}", cat="shuffle"):
        transport.commit_task_attempt(sid, task_key, attempt)


def _write_collect_result(plan: TpuExec, ctx: ExecCtx,
                          payload: Dict) -> None:
    """Execute ``plan`` and publish the result as one Arrow IPC file;
    the final hard link is the commit — first attempt to link wins, a
    later (speculative/zombie) attempt discards its own file."""
    from .columnar.arrow_bridge import arrow_schema, device_to_arrow
    rbs = [device_to_arrow(b) for b in plan.execute(ctx)]
    target = arrow_schema(plan.output_schema)
    out = payload["out"]
    tmp = f"{out}.a{payload.get('attempt', 0)}.tmp"
    with pa.OSFile(tmp, "wb") as f, \
            pa.ipc.new_file(f, target) as w:
        for rb in rbs:
            if rb.num_rows:
                w.write_batch(rb)
    try:
        os.link(tmp, out)  # atomic first-commit-wins (EEXIST = lost)
    except FileExistsError:
        pass
    finally:
        try:
            os.unlink(tmp)
        except OSError:
            pass


def _run_collect_task(payload: Dict, tracer=NULL_TRACER,
                      obs_sink: Optional[Dict] = None) -> None:
    """Execute a (reduce/final) plan slice on this worker's device and
    publish the result via the atomic hard-link commit."""
    conf = RapidsConf(payload["conf"])
    plan: TpuExec = payload["plan"]
    ctx = ExecCtx(conf)
    ctx.tracer = tracer
    from .lifecycle import QueryContext
    ctx.qctx = QueryContext.for_worker(payload, conf)
    if obs_sink is not None:
        obs_sink["ctx"] = ctx
    _write_collect_result(plan, ctx, payload)


def _run_mesh_task(payload: Dict, tracer=NULL_TRACER,
                   obs_sink: Optional[Dict] = None) -> None:
    """One gang member of a mesh query: bind every shuffle exchange in
    the plan to the cross-process `GangIciShuffleTransport` and execute
    the WHOLE plan as this process's slice of one SPMD program. All N
    members run the identical program — the collectives inside require
    every participant — but each member's exchanges only re-emit the
    partitions whose global devices this process owns, so the N result
    files union to exactly the full query output. Publishing reuses the
    collect task's atomic hard-link commit."""
    from .distributed import get_runtime
    from .distributed.gang import GangIciShuffleTransport
    from .exec.exchange import TpuShuffleExchangeExec
    conf = RapidsConf(payload["conf"])
    rt = get_runtime()
    if rt is None:
        # no runtime = this worker's bootstrap failed or it was
        # respawned into a newer incarnation than the task expects;
        # fail the attempt so the gang fails fast and the driver
        # remeshes or falls back
        raise RuntimeError(
            "mesh task on a worker without a bootstrapped mesh runtime")
    plan: TpuExec = payload["plan"]
    ctx = ExecCtx(conf)
    ctx.tracer = tracer
    from .lifecycle import QueryContext
    ctx.qctx = QueryContext.for_worker(payload, conf)
    if obs_sink is not None:
        obs_sink["ctx"] = ctx
    transport = GangIciShuffleTransport(
        rt, payload["exchange_root"], conf=conf, qctx=ctx.qctx)

    def bind(node):
        if isinstance(node, TpuShuffleExchangeExec):
            node.transport = transport
        for c in getattr(node, "children", ()):
            bind(c)

    bind(plan)
    _write_collect_result(plan, ctx, payload)


_TASK_KINDS = {"map": _run_map_task, "collect": _run_collect_task,
               "mesh": _run_mesh_task}


def _flush_task_flight(root: str, worker_id: int, task_path: str,
                       task_id: str, attempt: int, since: float,
                       failed: bool, error: str = "") -> None:
    """Worker-side anomaly evaluation after an attempt: when a trigger
    fires (task failure, OOM-retry, spill cascade — obs/anomaly.py),
    atomically commit a ``<task>.flight.json`` dump next to the task's
    rendezvous markers, then re-flush the incarnation ring. Best
    effort: forensics must never fail (or resurrect) the task."""
    if not RECORDER.enabled:
        return
    try:
        from .obs.anomaly import AnomalyDetector
        trig = AnomalyDetector().check_task(
            RECORDER.snapshot(since=since), failed, error)
        if trig is not None:
            kind, reason = trig
            doc = {"proc": f"w{worker_id}", "pid": os.getpid(),
                   "task": task_id, "attempt": attempt,
                   "trigger": kind, "reason": reason,
                   "ts": time.time(), "events": RECORDER.snapshot(),
                   "metrics": REGISTRY.snapshot()}
            tmp = task_path + ".flight.json.tmp"
            with open(tmp, "w") as f:
                json.dump(doc, f)
            os.replace(tmp, task_path + ".flight.json")
        flush_worker_ring(root, worker_id)
    except Exception:  # noqa: BLE001 — observability is best-effort
        pass


def _flush_task_obs(root: str, worker_id: int, task_path: str, tracer,
                    settings: Dict, ctx=None, task_id: str = "?",
                    attempt: int = 0) -> None:
    """Commit this attempt's spans and per-operator metric snapshot
    next to its task file (BEFORE the .ok/.err marker, so the driver's
    harvest pass finds them) and rewrite the worker's metrics snapshot
    in the rendezvous. Best effort: observability failures must never
    fail the task."""
    try:
        if tracer.enabled:
            tmp = task_path + ".spans.tmp"
            with open(tmp, "w") as f:
                # dropped count rides along so the driver's stitched
                # trace reports worker-side drops too
                json.dump({"spans": tracer.drain(),
                           "dropped": tracer.dropped}, f)
            os.replace(tmp, task_path + ".spans")
        if ctx is not None:
            # per-(op_id, task) snapshot: the driver folds the winning
            # attempts' files into per-operator totals + max/skew
            from .obs.opmetrics import flush_task_opmetrics
            flush_task_opmetrics(task_path, ctx, task_id, attempt)
        from .config import _to_bool
        if _to_bool(settings.get(METRICS_ENABLED.key, False)):
            flush_worker_metrics(root, worker_id)
    except Exception:  # noqa: BLE001 — observability is best-effort
        pass


def _write_marker(path: str, suffix: str, doc: Dict) -> None:
    """Commit a structured classification marker (``.qcancel`` /
    ``.spillfail`` / ``.fetchfail``) next to a task's ``.err`` via
    tmp+rename, so the driver never reads a torn marker
    (`TaskScheduler._read_marker` is the consumer)."""
    tmp = f"{path}.{suffix}.tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f)
    os.replace(tmp, f"{path}.{suffix}")


class _Heartbeat:
    """Worker-side liveness beacon: a daemon thread rewriting
    ``heartbeats/w<K>.hb`` every ``interval`` seconds. The driver treats
    a stale file as a wedged worker. A native call hung while holding
    the GIL (a stuck Pallas compile) starves this thread too, so real
    wedges are caught, not just cooperative ones; chaos `hang` simulates
    that via suspend()."""

    def __init__(self, root: str, worker_id: int, interval: float):
        self.path = os.path.join(root, "heartbeats", f"w{worker_id}.hb")
        os.makedirs(os.path.dirname(self.path), exist_ok=True)
        self.interval = interval
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)

    def start(self):
        self._beat()
        self._thread.start()

    def _beat(self):
        try:
            with open(self.path + ".tmp", "w") as f:
                f.write(str(time.time()))
            os.replace(self.path + ".tmp", self.path)
        except OSError:
            pass

    def _run(self):
        while not self._stop.wait(self.interval):
            self._beat()

    def suspend(self):
        self._stop.set()


def worker_main(root: str, worker_id: int, poll_s: float = 0.02,
                heartbeat_interval: float = 0.5) -> None:
    """Worker process loop: claim task files addressed to this worker,
    run them (after the chaos hook), write .ok/.err markers. Exits on
    root/shutdown."""
    from .scheduler import chaos
    tasks_dir = os.path.join(root, "tasks")
    hb = _Heartbeat(root, worker_id, heartbeat_interval)
    hb.start()
    while True:
        if os.path.exists(os.path.join(root, "shutdown")):
            return
        ran = False
        try:
            names = sorted(os.listdir(tasks_dir))
        except FileNotFoundError:
            names = []
        for name in names:
            if not name.endswith(f".w{worker_id}.task"):
                continue
            path = os.path.join(tasks_dir, name)
            done = path + ".ok"
            err = path + ".err"
            if os.path.exists(done) or os.path.exists(err):
                continue
            try:
                with open(path, "rb") as f:
                    kind, payload = pickle.load(f)
            except (OSError, EOFError):
                continue  # unlinked under us (worker was declared lost)
            except BaseException:
                # deserialization failure (version skew, missing class)
                # is a TASK failure the driver must see as a traceback —
                # escaping here would look like a worker death and burn
                # the respawn budget re-crashing on every retry
                with open(err + ".tmp", "w") as f:
                    f.write(traceback.format_exc())
                os.replace(err + ".tmp", err)
                ran = True
                continue
            # trace context propagated in the task pickle: this task's
            # spans join the driver's trace under its attempt span
            tctx = payload.get("trace")
            tracer = Tracer(
                trace_id=tctx["trace_id"], pid=worker_id + 1,
                max_spans=tctx.get("max_spans", 100_000),
                id_prefix=f"{payload.get('task_id', 't')}."
                          f"a{payload.get('attempt', 0)}.") \
                if tctx else NULL_TRACER
            settings = payload.get("conf", {}) or {}
            task_id = payload.get("task_id", "?")
            attempt = payload.get("attempt", 0)
            obs_sink: Dict = {}  # task fns expose their ExecCtx here
            # the flight recorder is always-on: record the claim and
            # flush the incarnation ring to disk BEFORE the chaos hook
            # / user code runs, so even an os._exit crash leaves the
            # attempt's preceding events behind for the driver harvest
            RECORDER.configure(RapidsConf(settings))
            claim_wall = time.time()
            RECORDER.record("task", ev="claim", task=task_id,
                            attempt=attempt, task_kind=kind,
                            worker=worker_id)
            if RECORDER.enabled:
                try:
                    flush_worker_ring(root, worker_id)
                except OSError:
                    pass
            try:
                with open(path + ".claim.tmp", "w") as f:
                    f.write(f"{worker_id} {time.time()}")
                os.replace(path + ".claim.tmp", path + ".claim")
                # lifecycle checkpoint AT CLAIM: a task claimed after
                # its query was cancelled never runs — the classified
                # error takes the normal .err path below
                lc = payload.get("lifecycle") or {}
                if lc.get("cancel_path") \
                        and os.path.exists(lc["cancel_path"]):
                    from .lifecycle import (QueryCancelled,
                                            read_cancel_marker)
                    r, d = read_cancel_marker(lc["cancel_path"])
                    raise QueryCancelled(
                        r, f"cancel marker observed at task claim: {d}",
                        lc.get("query_id", ""))
                # query-scoped chaos (oom_storm) rides per-task conf
                # overrides — applied before the task builds its
                # ExecCtx/DeviceMemoryManager
                overrides = chaos.conf_overrides(
                    settings.get(INJECT_FAULTS.key, ""), worker_id,
                    task_id, attempt)
                if overrides:
                    payload["conf"] = dict(payload.get("conf") or {},
                                           **overrides)
                chaos.maybe_inject(
                    settings.get(INJECT_FAULTS.key, ""), worker_id,
                    payload.get("task_id", ""),
                    payload.get("attempt", 0), hb,
                    # bound the simulated wedge by the liveness conf: a
                    # driver that misses the kill fails the run in
                    # seconds instead of parking the worker for minutes
                    hang_bound_s=max(
                        5.0, RapidsConf(settings).get(
                            HEARTBEAT_TIMEOUT) * 3),
                    cancel_path=lc.get("cancel_path"))
                with tracer.span(
                        f"task {payload.get('task_id', '?')} "
                        f"a{payload.get('attempt', 0)}", cat="task",
                        parent_id=tctx["parent"] if tctx else None,
                        args={"kind": kind, "worker": worker_id}):
                    _TASK_KINDS[kind](payload, tracer, obs_sink)
                if kind == "map":
                    # shuffle-durability chaos (corrupt/drop/eio) fires
                    # AFTER the atomic commit: the map task reports
                    # success and only the read side can discover the
                    # committed-then-lost output
                    chaos.maybe_inject_output(
                        settings.get(INJECT_FAULTS.key, ""), worker_id,
                        task_id, attempt,
                        os.path.join(payload["shuffle_root"],
                                     f"s{payload['shuffle_id']}",
                                     f"{task_id}.mapout"))
                _flush_task_obs(root, worker_id, path, tracer, settings,
                                ctx=obs_sink.get("ctx"),
                                task_id=task_id, attempt=attempt)
                RECORDER.record("task", ev="ok", task=task_id,
                                attempt=attempt, worker=worker_id)
                _flush_task_flight(root, worker_id, path, task_id,
                                   attempt, claim_wall, failed=False)
                with open(done + ".tmp", "w") as f:
                    f.write("ok")
                os.replace(done + ".tmp", done)
            except BaseException as exc:
                tb = traceback.format_exc()
                _flush_task_obs(root, worker_id, path, tracer, settings,
                                ctx=obs_sink.get("ctx"),
                                task_id=task_id, attempt=attempt)
                RECORDER.record("task", ev="err", task=task_id,
                                attempt=attempt, worker=worker_id,
                                error=tb.strip().splitlines()[-1][:200])
                _flush_task_flight(root, worker_id, path, task_id,
                                   attempt, claim_wall, failed=True,
                                   error=tb)
                if isinstance(exc, _QueryCancelled):
                    # classified lifecycle stop (worker saw the cancel
                    # marker, its wall deadline, or its budget): a
                    # structured marker BEFORE the .err, so the driver
                    # escalates to the classified cancel path instead
                    # of burning retries on a dead query
                    _write_marker(path, "qcancel",
                                  {"reason": exc.reason,
                                   "detail": (exc.detail or "")[:400]})
                if isinstance(exc, _SpillReadError):
                    # classified spill-tier data loss: a structured
                    # marker BEFORE the .err, so the scheduler retries
                    # the task (re-execution regenerates what the disk
                    # lost) WITHOUT blaming this worker — bit rot on a
                    # spill file is not a process fault
                    _write_marker(path, "spillfail",
                                  {"kind": exc.kind, "path": exc.path,
                                   "detail": (exc.detail or "")[:500]})
                if isinstance(exc, FetchFailure):
                    # structured marker BEFORE the .err it accompanies:
                    # when the driver harvests the .err, the
                    # classification is already on disk and the failure
                    # escalates to lineage recovery instead of burning
                    # a retry against the same bad bytes
                    _write_marker(path, "fetchfail",
                                  {"shuffle_id": exc.shuffle_id,
                                   "map_task": exc.map_task,
                                   "path": exc.path, "kind": exc.kind,
                                   "detail": (exc.detail or "")[:500]})
                with open(err + ".tmp", "w") as f:
                    f.write(tb)
                os.replace(err + ".tmp", err)
            ran = True
        if not ran:
            time.sleep(poll_s)  # tpu-lint: allow[blocking-call-in-thread] rendezvous poll on the worker main loop; the driver kills wedged workers


class _WorkerPool:
    """Owns the N worker OS processes: spawn, poll, kill, respawn, and
    heartbeat-file staleness — the seam `scheduler.TaskScheduler` drives
    liveness through."""

    def __init__(self, root: str, n: int, env: Dict[str, str],
                 heartbeat_interval: float,
                 exit_timeout_s: float = 10.0):
        self.root = root
        self.n = n
        self._env = env
        self._hb_interval = heartbeat_interval
        self._exit_timeout_s = exit_timeout_s
        self._procs: List[Optional[subprocess.Popen]] = [None] * n
        self._errlogs: List[Optional[Tuple[str, object]]] = [None] * n
        self._spawn_ts = [0.0] * n
        # last observed (hb mtime, monotonic-at-observation) per worker:
        # staleness is measured on the driver's monotonic clock from the
        # moment the beat was SEEN to change, so neither a wall-clock
        # step nor a filesystem/driver clock skew can fire a respawn
        self._hb_seen: List[Optional[Tuple[float, float]]] = [None] * n
        for w in range(n):
            self.spawn(w)

    def spawn(self, w: int) -> None:
        errpath = os.path.join(self.root, f"worker-{w}.err")
        errf = open(errpath, "ab")  # append: respawns keep history
        self._errlogs[w] = (errpath, errf)
        env = self._env
        from .distributed.runtime import ENV_COORD, ENV_PID
        if ENV_COORD in env:
            # the mesh process rank IS the worker id, stamped per spawn
            # so a respawned incarnation rejoins under the same slot
            env = dict(env, **{ENV_PID: str(w)})
        # stderr goes to a file per worker, NOT a pipe: an undrained
        # pipe blocks the worker once it fills (~64 KiB of library
        # warnings is enough) — a silent cluster hang
        self._procs[w] = subprocess.Popen(
            [sys.executable, "-m", "spark_rapids_tpu.cluster",
             "--root", self.root, "--worker", str(w),
             "--heartbeat", str(self._hb_interval)],
            env=env, stdout=subprocess.DEVNULL, stderr=errf)
        # monotonic: the scheduler's first-heartbeat grace must not be
        # inflated/deflated by wall-clock steps
        self._spawn_ts[w] = time.monotonic()
        # a fresh incarnation must not look wedged through its
        # predecessor's last (stale) beat
        self._hb_seen[w] = None
        try:
            os.unlink(self._hb_path(w))
        except OSError:
            pass

    def alive(self, w: int) -> bool:
        p = self._procs[w]
        return p is not None and p.poll() is None

    def exit_info(self, w: int) -> Tuple[Optional[int], str]:
        p = self._procs[w]
        rc = p.returncode if p is not None else None
        err = ""
        if self._errlogs[w] is not None:
            try:
                with open(self._errlogs[w][0], "rb") as f:
                    err = f.read().decode(errors="replace")
            except OSError:
                pass
        return rc, err

    def kill(self, w: int) -> None:
        p = self._procs[w]
        if p is not None and p.poll() is None:
            p.kill()
            try:
                p.wait(timeout=self._exit_timeout_s)
            except subprocess.TimeoutExpired:
                pass

    def update_env(self, updates: Dict[str, str]) -> None:
        """Env for FUTURE spawns (remesh points new incarnations at a
        fresh coordinator). Running workers keep their env until
        respawned."""
        self._env = dict(self._env, **updates)

    def respawn(self, w: int) -> None:
        self.kill(w)
        if self._errlogs[w] is not None:
            try:
                self._errlogs[w][1].close()
            except OSError:
                pass
        self.spawn(w)

    def _hb_path(self, w: int) -> str:
        return os.path.join(self.root, "heartbeats", f"w{w}.hb")

    def heartbeat_age(self, w: int) -> Optional[float]:
        try:
            mtime = os.stat(self._hb_path(w)).st_mtime
        except OSError:
            return None  # no beat yet this incarnation
        seen = self._hb_seen[w]
        now = time.monotonic()
        if seen is None or seen[0] != mtime:
            self._hb_seen[w] = (mtime, now)
            return 0.0
        return now - seen[1]

    def spawn_ts(self, w: int) -> float:
        return self._spawn_ts[w]

    def shutdown(self) -> None:
        with open(os.path.join(self.root, "shutdown"), "w") as f:
            f.write("1")
        for w in range(self.n):
            p = self._procs[w]
            if p is None:
                continue
            try:
                p.wait(timeout=self._exit_timeout_s)
            except subprocess.TimeoutExpired:
                p.kill()
        for log in self._errlogs:
            if log is not None:
                try:
                    log[1].close()
                except OSError:
                    pass


class TpuProcessCluster:
    """Spawn N worker processes against a filesystem rendezvous root.
    Workers run `python -m spark_rapids_tpu.cluster --root R --worker K`
    with an isolated JAX runtime each — genuinely separate OS processes
    with nothing shared but the filesystem. The workers have run on the
    CPU backend only (``platform="cpu"``): a chip belongs to one process
    at a time and nothing here assigns one to a worker, so do not start
    a cluster from a process that holds a chip and expect it shared.
    Queries run under `scheduler.TaskScheduler`: bounded task retry,
    worker blacklisting, heartbeat liveness + respawn, and optional
    speculative execution (`spark.rapids.tpu.speculation`)."""

    def __init__(self, n_workers: int = 2, root: Optional[str] = None,
                 env: Optional[Dict[str, str]] = None,
                 platform: str = "cpu",
                 conf: Optional[RapidsConf] = None):
        self.n_workers = n_workers
        self.root = root or tempfile.mkdtemp(prefix="rapids_tpu_cluster_")
        self._own_root = root is None
        self.conf = conf or RapidsConf()
        # A reused root (driver crashed and rerun with the same path)
        # holds a previous run's task/result/shuffle artifacts; query
        # and shuffle seqs restart at 1, so the first-commit-wins
        # protocol would mistake stale files for winning siblings and
        # silently serve the old run's data. Start from a clean slate.
        import shutil as _shutil
        for sub in ("tasks", "shuffle", "results", "heartbeats", "mesh"):
            d = os.path.join(self.root, sub)
            if not self._own_root and os.path.isdir(d):
                _shutil.rmtree(d, ignore_errors=True)
            os.makedirs(d, exist_ok=True)
        wenv = dict(os.environ)
        # workers import the package by module name: make sure the dir
        # the DRIVER imported it from is importable even when the driver
        # added it via sys.path (not installed / not cwd)
        pkg_parent = os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))
        pyp = wenv.get("PYTHONPATH", "")
        if pkg_parent not in pyp.split(os.pathsep):
            wenv["PYTHONPATH"] = (pkg_parent + os.pathsep + pyp
                                  if pyp else pkg_parent)
        wenv["JAX_PLATFORMS"] = platform
        # role marker: workers must not race the driver for the
        # spark.rapids.metrics.port HTTP bind — they flush snapshots
        # through the rendezvous instead (see obs/metrics.py)
        wenv["RAPIDS_TPU_IS_WORKER"] = "1"
        if env:
            wenv.update(env)
        # multi-host mesh (spark.rapids.tpu.mesh.enabled): the spawn
        # env carries the coordinator rendezvous so every worker
        # bootstraps jax.distributed and one logical (dcn, ici) Mesh
        # spans the fleet's devices (distributed/runtime.py). The rank
        # is stamped per spawn by the pool.
        from .config import MESH_ENABLED
        self._mesh_enabled = bool(self.conf.get(MESH_ENABLED))
        self._mesh_incarnation = 0
        self._mesh_ready_state: Optional[Tuple[int, bool, str]] = None
        if self._mesh_enabled:
            wenv.update(self._mesh_env_block())
        from .config import WORKER_EXIT_TIMEOUT
        self.pool = _WorkerPool(self.root, n_workers, wenv,
                                self.conf.get(HEARTBEAT_INTERVAL),
                                self.conf.get(WORKER_EXIT_TIMEOUT))
        self._query_seq = 0
        self._sid_seq = 0
        self._quarantine_seq = 0
        self.last_scheduler: Optional[TaskScheduler] = None
        self.last_qctx = None  # lifecycle context of the last query
        self._running_qctx = None  # set only while run_query is live
        self.last_trace_path: Optional[str] = None
        self.last_incident_path: Optional[str] = None
        self.last_plan: Optional[TpuExec] = None
        self.last_opmetrics: Dict = {}
        self.last_profile_path: Optional[str] = None
        # the /metrics port belongs to the driver; the cluster driver
        # never builds an ExecCtx, so bind it here rather than lazily
        maybe_start_http_server(self.conf)
        # /status enrichment: in-flight query phase, scheduler view,
        # mesh/gang health, warehouse tail (obs/metrics.render_status)
        from .obs.metrics import set_status_provider
        set_status_provider(self._status_doc)
        # always-on flight recorder (spark.rapids.flight.*): the driver
        # ring records scheduler/shuffle/memory events passively; an
        # anomaly turns it into an incident bundle at query end
        RECORDER.configure(self.conf)
        # spill-tier orphan GC at boot (forced: this driver process may
        # already have swept for an earlier cluster/manager): namespaces
        # whose owner pid is dead — a previous crashed run's spill
        # files — are reclaimed instead of leaking disk forever
        try:
            from .config import DISK_ORPHAN_TTL, SPILL_DIR
            from .memory import sweep_orphan_spill_dirs
            sweep_orphan_spill_dirs(self.conf.get(SPILL_DIR),
                                    self.conf.get(DISK_ORPHAN_TTL),
                                    force=True)
        except Exception:  # noqa: BLE001 — GC must never fail boot
            pass

    def shutdown(self) -> None:
        from .obs.metrics import clear_status_provider
        clear_status_provider(self._status_doc)
        self.pool.shutdown()
        if self._own_root:
            import shutil
            shutil.rmtree(self.root, ignore_errors=True)

    def _status_doc(self) -> Dict:
        """The cluster's /status contribution (obs/metrics.py): live
        fleet state a scrape can read mid-query. Every field is a
        plain read of driver-side state — no locks, no device work."""
        q = self._running_qctx
        in_flight = []
        if q is not None:
            in_flight.append({
                "query_id": q.query_id, "tenant": q.tenant,
                "phase": getattr(q, "phase", "unknown"),
                "cancelled": q.token.reason})
        doc: Dict = {
            "cluster": {"n_workers": self.n_workers, "root": self.root},
            "in_flight": in_flight,
        }
        sched = self.last_scheduler
        if sched is not None and q is not None:
            try:
                doc["scheduler"] = sched.live_status()
            except Exception:  # noqa: BLE001 — status is best-effort
                pass
        last_fb = None
        if sched is not None:
            for ev in reversed(sched.events):
                if ev.get("event") == "mesh_fallback":
                    last_fb = ev.get("reason")
                    break
        doc["mesh"] = {"enabled": self._mesh_enabled,
                       "incarnation": self._mesh_incarnation,
                       "last_fallback": last_fb}
        try:
            from .obs.warehouse import (STATUS_ROWS, tail_rows,
                                        warehouse_dir)
            d = warehouse_dir(self.conf)
            if d:
                doc["warehouse_tail"] = tail_rows(
                    d, self.conf.get(STATUS_ROWS))
        except Exception:  # noqa: BLE001
            pass
        return doc

    def cancel_running(self, detail: str = "user requested") -> bool:
        """Cancel the in-flight ``run_query`` (thread-safe): flips the
        query's token; the scheduler's next poll pass publishes the
        rendezvous marker, reaps in-flight attempts, and run_query
        raises ``QueryCancelled(reason=user)``. False when no query is
        running or it already finished/cancelled."""
        q = self._running_qctx
        if q is None:
            return False
        return q.cancel(detail)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.shutdown()

    # --- query execution --------------------------------------------------

    def run_query(self, plan: TpuExec,
                  conf: Optional[RapidsConf] = None,
                  qctx=None) -> pa.Table:
        """Execute a physical plan across the worker processes: stages
        split at shuffle exchanges, map outputs exchanged as Arrow IPC
        files, final per-partition results concatenated here. Task
        failures, worker deaths/hangs, and stragglers are handled by the
        TaskScheduler; every attempt is recorded and forwarded to the
        event log when `spark.rapids.eventLog.dir` is set.

        Lifecycle (lifecycle.py, default-on): the query runs under a
        ``QueryContext`` — fair driver-side admission against the
        shared slot pool, a deadline/cancellation token the scheduler
        polls every pass and fans out to workers via a rendezvous
        ``.cancel`` marker (checked at task claim and between batches),
        and classified ``QueryCancelled`` with event-log +
        flight-recorder + incident-bundle evidence. ``cancel_running``
        cancels from another thread."""
        conf = conf or self.conf
        settings = conf.items()
        plan = copy.deepcopy(plan)
        # planner-built plans (AQE on by default) wrap exchanges in
        # TpuAQEShuffleReadExec; the adaptive reader is an in-process
        # construct (it materializes the exchange through a transport
        # handle), so strip it here — the process cluster IS the
        # exchange (ADVICE round 5)
        plan = _strip_aqe_reads(plan)
        # stable operator-instance ids ride the task pickles: every
        # worker's per-(op, task) snapshot folds back under the same
        # label (planner-built plans arrive already stamped; raw exec
        # trees get stamped here)
        from .obs.opmetrics import assign_op_ids
        assign_op_ids(plan)
        self.last_plan = plan
        self.last_opmetrics = {}
        self._query_seq += 1
        qid = self._query_seq
        from .lifecycle import (LIFECYCLE_ENABLED, QueryCancelled,
                                QueryContext)
        if qctx is None and conf.get(LIFECYCLE_ENABLED):
            qctx = QueryContext.from_conf(conf, query_id=f"q{qid}")
        self.last_qctx = qctx
        # cancel_running targets only a LIVE query: cancelling after
        # completion must be a no-op, not phantom cancel evidence
        self._running_qctx = qctx
        # telemetry warehouse bracket (obs/attribution.py): driver +
        # worker counter baselines now; ONE sealed row in the finally
        # below, whatever the outcome. cluster_root lets finish() fold
        # worker registry deltas and mine gang mesh_epoch ring events.
        from .obs.attribution import QueryAttribution
        attrib = QueryAttribution.begin(conf, cluster_root=self.root)
        tracer = tracer_from_conf(conf)
        RECORDER.configure(conf)
        sched = TaskScheduler(self.pool, os.path.join(self.root, "tasks"),
                              conf, query_id=f"q{qid}", tracer=tracer,
                              qctx=qctx)
        self.last_scheduler = sched
        self._verify_plan(plan, conf, qid, sched)
        # wall stamp filters ring events (their ts is wall clock); the
        # duration below runs on monotonic so a clock step can't skew it
        t0 = time.time()
        t0_mono = time.monotonic()
        ok = False
        err = None
        try:
            args = None
            if tracer.enabled:  # tree-walk + sha1 only when traced
                from .tools.event_log import plan_fingerprint
                args = {"fingerprint": plan_fingerprint(plan)}
            with tracer.span(f"query q{qid}", cat="query", args=args):
                # driver-side fair admission: concurrent cluster
                # queries draw from the same weighted per-tenant slot
                # pool as local collects (one slot per query while its
                # stages run). Lifecycle-managed queries only — with
                # the kill switch off (qctx None), run_query must not
                # start queueing on the device pool it never touched
                # pre-lifecycle (the driver does no device work)
                import contextlib
                from .memory import DeviceMemoryManager
                gate = DeviceMemoryManager.shared(conf).task_slot(qctx) \
                    if qctx is not None else contextlib.nullcontext()
                with gate:
                    if qctx is not None:
                        qctx.phase = "running"
                    if self._mesh_route(plan, conf, sched):
                        result = self._run_query_mesh(
                            plan, conf, settings, qid, sched)
                    else:
                        result = self._run_query_stages(
                            plan, conf, settings, qid, sched)
            ok = True
            return result
        except QueryCancelled as e:
            err = e
            # classified cancel: one scheduler event (the anomaly the
            # incident harvest keys on — the scheduler emits it on ITS
            # detection paths; admission/driver-side raises land here)
            # plus the event-log line
            if not any(ev["event"] == "query_cancelled"
                       for ev in sched.events):
                sched._event("query_cancelled",
                             reason=f"[{e.reason}] {e.detail}"[:400])
            from .obs.opmetrics import plan_source
            from .tools.event_log import log_query_cancelled
            try:
                log_query_cancelled(conf, e,
                                    time.monotonic() - t0_mono,
                                    source=plan_source(plan),
                                    cluster="process")
            except OSError:
                pass
            raise
        except BaseException as e:
            err = e  # warehouse outcome classification (finally below)
            raise
        finally:
            self._running_qctx = None
            # failed queries are exactly the ones whose attempt
            # timeline and trace the profiler needs — emit
            # unconditionally
            if tracer.enabled:
                try:
                    self.last_trace_path = tracer.write_chrome(
                        conf.get(TRACE_DIR),
                        name=f"trace-{tracer.trace_id}-q{qid}.json")
                except OSError:
                    pass  # observability must never fail the query
            wall_s = time.monotonic() - t0_mono
            self.last_wall_s = wall_s
            # fold the winning attempts' per-operator snapshots (torn/
            # missing files tolerated — a crashed worker leaves partial
            # attribution); top sinks ride the scheduler event line
            from .obs.opmetrics import top_op_sinks
            try:
                self.last_opmetrics = self._fold_opmetrics(sched)
            except Exception:  # noqa: BLE001 — attribution is
                self.last_opmetrics = {}  # best-effort, never fatal
            from .tools.event_log import log_scheduler_events
            log_scheduler_events(conf, f"q{qid}", sched, wall_s,
                                 op_sinks=top_op_sinks(
                                     self.last_opmetrics))
            # warehouse row, whatever the outcome: a crashed worker's
            # query still gets a row with outcome=failed and whatever
            # partial attribution the .opm harvest above recovered
            if attrib is not None:
                from .obs.opmetrics import plan_source
                attrib.finish(
                    root=plan, folded=self.last_opmetrics, qctx=qctx,
                    wall_s=wall_s, source=plan_source(plan),
                    cluster={"kind": "process",
                             "n_workers": self.n_workers,
                             "mesh_incarnation": self._mesh_incarnation},
                    error=err)
            if ok:
                from .obs.metrics import QUERY_DURATION
                from .obs.opmetrics import plan_source
                QUERY_DURATION.labels(plan_source(plan),
                                      "process").observe(wall_s)
                self._write_profile(plan, conf, qid, tracer, sched,
                                    wall_s)
            # flight recorder: when anything anomalous happened this
            # query (failed attempts, worker deaths, stragglers, or a
            # worker committed a flight dump), harvest every process's
            # ring into ONE incident bundle — works with tracing and
            # metrics fully disabled
            try:
                self._maybe_write_incident(conf, qid, sched, tracer, t0)
            except Exception:  # noqa: BLE001 — forensics must never
                pass           # fail (or mask) the query itself

    def _verify_plan(self, plan: TpuExec, conf: RapidsConf, qid: int,
                     sched: TaskScheduler) -> None:
        """Static contract pass before any task is scheduled
        (spark.rapids.sql.verifyPlan, analysis/plan_verifier.py). A
        rejection emits a ``plan_rejected`` scheduler event (an anomaly
        kind, so the incident-bundle harvest fires and `profiling
        triage` shows why the query never ran) plus the event-log and
        flight-recorder entries, then raises."""
        from .config import VERIFY_PLAN
        if not conf.get(VERIFY_PLAN):
            return
        from .analysis.plan_verifier import (PlanVerificationError,
                                             report_rejection,
                                             verify_plan)
        report = verify_plan(plan, conf)
        if report.ok:
            return
        sched._event("plan_rejected", reason=report.summary()[:500])
        report_rejection(conf, report, plan, query_id=f"q{qid}")
        try:
            # wall window bound for ring-event filtering (events carry
            # wall ts), not a duration
            since = time.time() - 1.0  # tpu-lint: allow[wallclock-duration] wall-ts window bound, not a duration
            self._maybe_write_incident(conf, qid, sched, NULL_TRACER,
                                       since)
        except Exception:  # noqa: BLE001 — forensics must never mask
            pass           # the rejection itself
        raise PlanVerificationError(report)

    # --- per-operator metrics: fold / profile / EXPLAIN ANALYZE -----------

    def _fold_opmetrics(self, sched: TaskScheduler) -> Dict:
        """Fold the committed (winning) attempts' ``<task>.opm.json``
        snapshots into per-operator totals + per-task max/skew. Losing
        speculative/zombie attempts are excluded so rows are counted
        exactly once; missing or torn files (crashed workers,
        opmetrics disabled) just mean partial attribution."""
        from .obs.opmetrics import fold_snapshots, read_task_opmetrics
        winners = [(e["task"], e["attempt"], e["worker"])
                   for e in sched.events if e["event"] == "task_ok"]
        snaps = read_task_opmetrics(os.path.join(self.root, "tasks"),
                                    winners)
        return fold_snapshots(snaps)

    def _write_profile(self, plan: TpuExec, conf: RapidsConf, qid: int,
                       tracer, sched: TaskScheduler,
                       wall_s: float) -> None:
        """Persist one query-profile JSON (spark.rapids.history.dir)
        with the cross-worker folded per-operator metrics."""
        from .obs.opmetrics import (HISTORY_DIR, build_profile,
                                    plan_source, write_profile)
        if not conf.get(HISTORY_DIR):
            return  # don't pay the fingerprint when history is off
        try:
            tid = tracer.trace_id \
                if getattr(tracer, "enabled", False) else None
            doc = build_profile(
                plan, self.last_opmetrics, wall_s, query=f"q{qid}",
                source=plan_source(plan), cluster="process",
                trace_id=tid, conf=conf,
                extra={"scheduler": sched.summary(),
                       "n_workers": self.n_workers})
            self.last_profile_path = write_profile(conf, doc)
        except Exception:  # noqa: BLE001 — history must never fail
            pass           # the query it records

    def last_analyzed(self, formatted: bool = False) -> str:
        """EXPLAIN ANALYZE text for the last run_query(): the executed
        plan with per-operator rows/time folded ACROSS the worker
        processes (tasks + per-task max + skew per node)."""
        if self.last_plan is None:
            raise RuntimeError("no query has run on this cluster")
        from .obs.opmetrics import render_analyzed
        return render_analyzed(self.last_plan, self.last_opmetrics,
                               wall_s=getattr(self, "last_wall_s", None),
                               formatted=formatted, cluster="process")

    def explain_analyze(self, plan: TpuExec,
                        conf: Optional[RapidsConf] = None,
                        formatted: bool = False) -> str:
        """Execute ``plan`` across the workers, then return the
        metrics-annotated plan text (the process-cluster EXPLAIN
        ANALYZE path; ``TpuSession.sql('EXPLAIN ANALYZE ...')`` routes
        here when a cluster is attached)."""
        self.run_query(plan, conf)
        return self.last_analyzed(formatted=formatted)

    def _maybe_write_incident(self, conf: RapidsConf, qid: int,
                              sched: TaskScheduler, tracer,
                              t0: float) -> None:
        """Harvest pass: driver ring + every worker incarnation's ring
        file + worker flight dumps + metrics snapshots -> one
        ``incident-<id>-<seq>.json`` under the flight dir. No-op when
        the query was clean or the recorder is disabled."""
        if not conf.get(FLIGHT_ENABLED):
            return
        from .obs.anomaly import (anomalies_from_scheduler,
                                  build_incident_bundle)
        anomalies = anomalies_from_scheduler(sched.events)
        dumps = read_flight_dumps(os.path.join(self.root, "tasks"),
                                  query_id=f"q{qid}")
        if not anomalies and not dumps:
            return
        # the incident id reuses the trace id when tracing ran (so the
        # bundle and the Chrome trace cross-reference); otherwise a
        # fresh one — the recorder never requires tracing
        import uuid
        fid = tracer.trace_id if getattr(tracer, "enabled", False) \
            else uuid.uuid4().hex[:16]
        metrics = {"driver": REGISTRY.snapshot()}
        for tag, snap in read_worker_metrics(self.root):
            metrics[tag] = snap
        # scope worker rings to this query like the driver ring: an
        # unfiltered ring file (esp. a previous query's dead
        # incarnation) would smear an earlier query's HBM occupancy
        # into this incident's timeline
        rings = []
        for tag, doc in read_worker_rings(self.root):
            evs = [e for e in doc.get("events", [])
                   if e.get("ts", 0.0) >= t0]
            if evs:
                rings.append((tag, dict(doc, events=evs)))
        bundle = build_incident_bundle(
            query_id=f"q{qid}", flight_id=fid, seq=next_incident_seq(),
            trigger_anomalies=anomalies,
            driver_events=RECORDER.snapshot(since=t0),
            worker_rings=rings,
            worker_dumps=dumps, sched_events=sched.events,
            metrics_snapshot=metrics, conf=conf,
            straggler_factor=conf.get(FLIGHT_STRAGGLER_FACTOR),
            since=t0)
        self.last_incident_path = write_incident_bundle(
            resolve_flight_dir(conf, self.root), bundle,
            max_files=conf.get(TRACE_MAX_FILES))

    def _run_stage_lineage(self, sched: TaskScheduler,
                           specs: Sequence[TaskSpec], label: str,
                           shuffle_root: str,
                           map_specs: Dict[int, List[TaskSpec]],
                           budget: List[int]) -> None:
        """Run one stage with shuffle-lineage recovery: a classified
        FetchFailure from any reading task quarantines the bad map
        output, re-executes ONLY the producing map task (recursively
        protected — regenerating it may surface an even older loss),
        and resumes the interrupted stage minus its already-committed
        tasks. ``budget`` is the query-wide rerun allowance
        (``spark.rapids.shuffle.maxStageRetries``); the attempt-
        suffixed atomic commit keeps a zombie attempt of the original
        map task from interleaving with the rerun's output."""
        pending = list(specs)
        while True:
            try:
                sched.run_stage(pending, stage_label=label)
                return
            except FetchFailedError as ff:
                lost = next((s for s in map_specs.get(ff.shuffle_id, [])
                             if s.task_id == ff.map_task), None)
                if lost is None:
                    raise RuntimeError(
                        f"{label}: shuffle {ff.shuffle_id} map output "
                        f"{ff.map_task!r} is {ff.kind} and no lineage "
                        f"is available to recompute it") from ff
                if budget[0] <= 0:
                    raise RuntimeError(
                        f"{label}: map output {ff.map_task} lost "
                        f"({ff.kind}) with the stage-rerun budget "
                        f"(spark.rapids.shuffle.maxStageRetries) "
                        f"exhausted") from ff
                budget[0] -= 1
                self._quarantine_mapout(shuffle_root, ff.shuffle_id,
                                        ff.map_task)
                _STAGE_RERUNS.inc()
                sched._event(
                    "stage_rerun", task=ff.map_task, worker=ff.worker,
                    reason=f"{label} hit fetch failure [{ff.kind}] on "
                           f"{ff.task} a{ff.attempt}; re-executing "
                           f"{ff.map_task} from lineage")
                self._run_stage_lineage(
                    sched, [lost], f"map s{ff.shuffle_id} rerun",
                    shuffle_root, map_specs, budget)
                # resume: completed tasks keep their committed output
                pending = [s for s in pending
                           if s.task_id not in ff.completed]

    def _quarantine_mapout(self, shuffle_root: str, sid: int,
                           task_key: str) -> None:
        """Fence the bad committed output out of every reader's view
        (readers only consume ``*.mapout`` dirs) while keeping the
        bytes on disk for forensics. One rename, atomic like the
        commit it undoes; already-gone output (drop-style loss) is
        fine — there is nothing to fence."""
        d = os.path.join(shuffle_root, f"s{sid}", f"{task_key}.mapout")
        self._quarantine_seq += 1
        try:
            os.rename(d, os.path.join(
                os.path.dirname(d),
                f"{task_key}.quarantine{self._quarantine_seq}"))
        except OSError:
            pass

    def prometheus_text(self) -> str:
        """One Prometheus exposition document over the driver's registry
        plus every worker snapshot flushed through the rendezvous
        (spark.rapids.metrics.enabled), each series labeled
        ``proc="driver"|"w<K>"`` — summing across processes is the
        scraper's job."""
        tagged = [("driver", REGISTRY.snapshot())]
        tagged.extend(read_worker_metrics(self.root))
        return render_merged_snapshots(tagged)

    def _run_query_stages(self, plan: TpuExec, conf: RapidsConf,
                          settings: Dict, qid: int,
                          sched: TaskScheduler) -> pa.Table:
        shuffle_root = os.path.join(self.root, "shuffle")
        # lineage: every shuffle's map TaskSpecs stay addressable for
        # the life of the query, so a later stage's FetchFailure can
        # re-execute exactly the producing map task (the RDD-lineage
        # recovery of Zaharia et al., scoped to one task)
        map_specs: Dict[int, List[TaskSpec]] = {}
        rerun_budget = [conf.get(SHUFFLE_MAX_STAGE_RETRIES)]
        # run map stages deepest-first until no exchange remains
        while True:
            exch = _deepest_exchange(plan)
            if exch is None:
                break
            self._sid_seq += 1
            sid = self._sid_seq
            slices = _split_leaf_input(exch.child, self.n_workers)
            specs = []
            for i, child_slice in enumerate(slices):
                specs.append(TaskSpec(f"q{qid}s{sid}m{i}", "map", {
                    "plan": child_slice,
                    "partitioning": exch.partitioning,
                    "shuffle_root": shuffle_root,
                    "shuffle_id": sid,
                    "map_id_base": i * 100_000,
                    "conf": settings,
                }))
            map_specs[sid] = specs
            self._run_stage_lineage(sched, specs, f"map s{sid}",
                                    shuffle_root, map_specs,
                                    rerun_budget)
            n = exch.partitioning.num_partitions
            read = ProcessShuffleReadExec(
                shuffle_root, sid, list(range(n)),
                exch.child.output_schema,
                expected_mapouts=[s.task_id for s in specs])
            # the read REPLACES the exchange in the reduce stage: give
            # it the exchange's stable op id so its reduce-side rows
            # fold under the exchange node in EXPLAIN ANALYZE/profiles
            read._op_id = getattr(exch, "_op_id", None)
            plan = _replace_node(plan, exch, read)
        # final stage: split the partition ranges of every shuffle read
        outs = []
        specs = []
        for w in range(self.n_workers):
            final = _slice_partitions(copy.deepcopy(plan), w,
                                      self.n_workers)
            if final is None:
                if w == 0:
                    final = plan  # no shuffle read: one worker runs all
                else:
                    continue
            out = os.path.join(self.root, "results",
                               f"q{qid}_r{w}.arrow")
            outs.append(out)
            specs.append(TaskSpec(f"q{qid}r{w}", "collect",
                                  {"plan": final, "out": out,
                                   "conf": settings}))
        self._run_stage_lineage(sched, specs, "final", shuffle_root,
                                map_specs, rerun_budget)
        tables = []
        for out in outs:
            with pa.OSFile(out, "rb") as f:
                tables.append(pa.ipc.open_file(f).read_all())
        from .columnar.arrow_bridge import arrow_schema
        target = arrow_schema(plan.output_schema)
        tables = [t.cast(target) for t in tables if t.num_rows] \
            or [pa.table({f.name: pa.array([], f.type) for f in target},
                         schema=target)]
        return pa.concat_tables(tables)

    # --- multi-host mesh execution ----------------------------------------

    def _mesh_env_block(self) -> Dict[str, str]:
        """The spawn-env slice for the CURRENT mesh incarnation. The
        coordinator port is fresh per incarnation (unless pinned by
        conf): a dead incarnation's coordinator state must never greet
        the next fleet."""
        from .config import (MESH_BOOTSTRAP_TIMEOUT,
                             MESH_COORDINATOR_PORT,
                             MESH_DEVICES_PER_PROCESS)
        from .distributed.runtime import mesh_env
        port = int(self.conf.get(MESH_COORDINATOR_PORT)) or _free_port()
        return mesh_env(f"127.0.0.1:{port}", self.n_workers,
                        int(self.conf.get(MESH_DEVICES_PER_PROCESS)),
                        float(self.conf.get(MESH_BOOTSTRAP_TIMEOUT)),
                        incarnation=self._mesh_incarnation)

    def _mesh_route(self, plan: TpuExec, conf: RapidsConf,
                    sched: TaskScheduler) -> bool:
        """Gate the gang path: mesh on, plan expressible as ONE SPMD
        program, and every worker's bootstrap marker in. Any 'no' is a
        recorded mesh_fallback — the classic file-shuffle path is
        always correct."""
        if not self._mesh_enabled:
            return False
        why = _mesh_ineligible(plan)
        if why is not None:
            sched._event("mesh_fallback",
                         reason=f"plan ineligible: {why}"[:400])
            return False
        ok, why = self._mesh_ready(conf)
        if not ok:
            sched._event("mesh_fallback",
                         reason=f"mesh not ready: {why}"[:400])
            return False
        return True

    def _mesh_ready(self, conf: RapidsConf) -> Tuple[bool, str]:
        """Wait (bounded by the bootstrap timeout) for every worker's
        mesh marker of the current incarnation; cached per incarnation
        so only the first query after a (re)spawn pays the wait."""
        from .config import MESH_BOOTSTRAP_TIMEOUT
        from .distributed.runtime import read_mesh_markers
        inc = self._mesh_incarnation
        st = self._mesh_ready_state
        if st is not None and st[0] == inc:
            return st[1], st[2]
        deadline = time.monotonic() \
            + float(conf.get(MESH_BOOTSTRAP_TIMEOUT)) + 5.0
        ok, why = False, "bootstrap markers never appeared"
        while time.monotonic() < deadline:
            docs = read_mesh_markers(self.root, self.n_workers, inc)
            if docs is not None:
                bad = next((d for d in docs if not d.get("ok")), None)
                if bad is not None:
                    why = (f"worker bootstrap failed: "
                           f"{(bad.get('error') or '?')[:200]}")
                else:
                    ok, why = True, ""
                break
            time.sleep(0.05)  # tpu-lint: allow[blocking-call-in-thread] bounded readiness poll before the first mesh query
        self._mesh_ready_state = (inc, ok, why)
        return ok, why

    def _remesh(self, sched: TaskScheduler, reason: str) -> None:
        """Tear the fleet down to a clean mesh: bump the incarnation,
        point future spawns at a fresh coordinator, respawn every
        worker. Kill-then-respawn is the wedge/orphan guarantee — a
        member parked inside a collective that will never complete
        does not survive the gang that created it."""
        if not self._mesh_enabled:
            return
        self._mesh_incarnation += 1
        self._mesh_ready_state = None
        self.pool.update_env(self._mesh_env_block())
        for w in range(self.n_workers):
            # the dead gang's unclaimed task files must not greet the
            # next incarnation: a respawned worker would claim them and
            # replay the failed generation instead of the retry's
            sched._clear_worker_tasks(w)
            self.pool.respawn(w)
        sched._event(
            "worker_respawn",
            reason=f"remesh i{self._mesh_incarnation}: {reason}"[:300])

    def _run_query_mesh(self, plan: TpuExec, conf: RapidsConf,
                        settings: Dict, qid: int,
                        sched: TaskScheduler) -> pa.Table:
        """Gang attempts with remesh-retry, then classic fallback. A
        cancelled gang also remeshes before the classified error
        surfaces: members stranded inside (or heading into) a
        collective must not outlive the query as wedged processes."""
        from .config import MESH_GANG_RETRIES
        from .lifecycle import QueryCancelled
        retries = max(0, int(conf.get(MESH_GANG_RETRIES)))
        g = 0
        while True:
            try:
                return self._run_gang_attempt(plan, conf, settings,
                                              qid, sched, g)
            except QueryCancelled:
                self._remesh(sched, "query cancelled mid-gang")
                raise
            except GangFailedError as gf:
                sched._event("gang_failed", task=gf.task,
                             worker=gf.worker, reason=str(gf)[:400])
                self._remesh(sched, f"gang g{g} failed")
                g += 1
                if g > retries:
                    sched._event(
                        "mesh_fallback",
                        reason=f"gang retries exhausted after {g} "
                               f"attempts; classic per-stage path")
                    return self._run_query_stages(plan, conf, settings,
                                                  qid, sched)
                ok, why = self._mesh_ready(conf)
                if not ok:
                    sched._event(
                        "mesh_fallback",
                        reason=f"remesh did not converge: {why}"[:400])
                    return self._run_query_stages(plan, conf, settings,
                                                  qid, sched)

    def _run_gang_attempt(self, plan: TpuExec, conf: RapidsConf,
                          settings: Dict, qid: int,
                          sched: TaskScheduler, g: int) -> pa.Table:
        n = self.n_workers
        xroot = os.path.join(self.root, "mesh", f"q{qid}.g{g}")
        os.makedirs(xroot, exist_ok=True)
        specs, outs = [], []
        for k in range(n):
            member = _slice_for_member(plan, k, n)
            out = os.path.join(self.root, "results",
                               f"q{qid}g{g}_m{k}.arrow")
            outs.append(out)
            specs.append(TaskSpec(f"q{qid}g{g}w{k}", "mesh", {
                "plan": member, "out": out, "conf": settings,
                "exchange_root": xroot}))
        sched.run_gang(specs, stage_label=f"mesh gang g{g}")
        tables = []
        for out in outs:
            with pa.OSFile(out, "rb") as f:
                tables.append(pa.ipc.open_file(f).read_all())
        from .columnar.arrow_bridge import arrow_schema
        target = arrow_schema(plan.output_schema)
        tables = [t.cast(target) for t in tables if t.num_rows] \
            or [pa.table({f.name: pa.array([], f.type) for f in target},
                         schema=target)]
        return pa.concat_tables(tables)


def run_process_query(plan: TpuExec, n_workers: int = 2,
                      conf: Optional[RapidsConf] = None) -> pa.Table:
    """One-shot convenience: spin a cluster up, run, tear down."""
    with TpuProcessCluster(n_workers, conf=conf) as cluster:
        return cluster.run_query(plan, conf)


# --- mesh plan gating ------------------------------------------------------

def _free_port() -> int:
    import socket
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _exchange_regions(plan: TpuExec):
    """Stage regions of a gang plan: ``[(exchange_or_None, raw_leaves,
    reads_deeper_exchange)]``. Entry 0 is the FINAL region (everything
    above the topmost exchanges); one entry per exchange covers its
    child subtree cut at deeper exchanges. The gang correctness
    argument runs per region: each member's contribution to an
    exchange must be a disjoint slice of the stage's true input, so
    each region gets exactly ONE source of distribution — the owned
    partitions of deeper exchanges, or one sliced leaf."""
    from .exec.exchange import TpuShuffleExchangeExec
    exs: List = []

    def collect(node):
        if isinstance(node, TpuShuffleExchangeExec):
            exs.append(node)
        for c in getattr(node, "children", ()):
            collect(c)

    collect(plan)

    def cut(node, leaves, deeper):
        if isinstance(node, TpuShuffleExchangeExec):
            deeper[0] = True
            return
        kids = getattr(node, "children", ())
        if not kids:
            leaves.append(node)
        for c in kids:
            cut(c, leaves, deeper)

    out = []
    leaves: List = []
    deeper = [False]
    cut(plan, leaves, deeper)
    out.append((None, leaves, deeper[0]))
    for ex in exs:
        leaves, deeper = [], [False]
        cut(ex.child, leaves, deeper)
        out.append((ex, leaves, deeper[0]))
    return out


def _mesh_ineligible(plan: TpuExec) -> Optional[str]:
    """Why this plan cannot run as ONE SPMD gang program (None = it
    can). The gang replays the whole plan on every member and merges
    every exchange through a collective, so each member's contribution
    to an exchange must be a DISJOINT slice of the stage input:

    - every leaf must sit below some exchange (final-region rows
      deduplicate by partition ownership; an un-exchanged leaf would
      be emitted once per member);
    - a stage reading a deeper exchange must have no raw leaves beside
      it (a replicated leaf is only provably safe under a join, and
      the plan shape is not inspected that deeply — fall back);
    - leaves must be splittable types, exchanges hash-partitioned over
      ICI-expressible schemas."""
    from .exec.base import HostBatchSourceExec
    from .io.scan import TpuFileScanExec
    from .shuffle.ici import _lane_spec
    from .shuffle.partitioner import HashPartitioning
    regions = _exchange_regions(plan)
    if len(regions) == 1:
        return "no shuffle exchange"
    final_leaves = regions[0][1]
    if final_leaves:
        return (f"leaf {type(final_leaves[0]).__name__} above every "
                f"exchange")
    for ex, leaves, deeper in regions[1:]:
        if not isinstance(ex.partitioning, HashPartitioning):
            return f"{type(ex.partitioning).__name__} exchange"
        try:
            _lane_spec(ex.child.output_schema)
        except NotImplementedError as e:
            return f"schema not ICI-expressible: {e}"
        if deeper and leaves:
            return "stage mixes exchange input with raw leaves"
        for lf in leaves:
            if not isinstance(lf,
                              (TpuFileScanExec, HostBatchSourceExec)):
                return f"unsplittable leaf {type(lf).__name__}"
    return None


def _slice_for_member(plan: TpuExec, k: int, n: int) -> TpuExec:
    """Gang member k's copy of the plan. Per stage region, exactly ONE
    source distributes the input across members: regions reading a
    deeper exchange distribute by partition ownership (their raw-leaf
    mix is rejected by eligibility); pure-leaf regions slice their
    most-splittable leaf k::n and replicate the rest (a join below the
    exchange distributes over the sliced side); regions with nothing
    splittable run whole on member 0 and empty elsewhere. Every member
    still executes the identical program — the collectives require it —
    an emptied scan becomes an empty host source carrying the scan's
    op id so EXPLAIN ANALYZE folding stays stable across processes."""
    from .exec.base import HostBatchSourceExec
    from .io.scan import TpuFileScanExec
    plan = copy.deepcopy(plan)
    regions = _exchange_regions(plan)
    counts: Dict[int, int] = {}
    for _, leaves, _d in regions:
        for lf in leaves:
            counts[id(lf)] = counts.get(id(lf), 0) + 1
    sliced: set = set()
    member0_only: set = set()
    for _ex, leaves, deeper in regions[1:]:
        if deeper or not leaves:
            continue
        best = None
        for lf in leaves:
            if counts[id(lf)] > 1:
                continue  # aliased (self-join): slicing the shared
                # node would slice BOTH uses and drop row pairs
            if isinstance(lf, TpuFileScanExec):
                pieces = len(lf.paths)
            elif isinstance(lf, HostBatchSourceExec):
                pieces = len(lf.batches)
            else:
                pieces = 0
            if pieces > 1 and (best is None or pieces > best[1]):
                best = (lf, pieces)
        if best is not None:
            sliced.add(id(best[0]))
        else:
            member0_only.update(id(lf) for lf in leaves)

    def rewrite(node):
        if isinstance(node, TpuFileScanExec):
            if id(node) in sliced:
                mine = node.paths[k::n]
            elif id(node) in member0_only and k:
                mine = []
            else:
                return node
            if mine:
                node.paths = list(mine)
                return node
            repl = HostBatchSourceExec([], schema=node.output_schema)
            repl._op_id = getattr(node, "_op_id", None)
            return repl
        if isinstance(node, HostBatchSourceExec):
            if id(node) in sliced:
                node.batches = list(node.batches[k::n])
            elif id(node) in member0_only and k:
                node.batches = []
            return node
        kids = getattr(node, "children", ())
        if kids:
            new = tuple(rewrite(c) for c in kids)
            if any(a is not b for a, b in zip(new, kids)):
                node = node.with_new_children(new)
        return node

    return rewrite(plan)


# --- plan surgery ----------------------------------------------------------

def _strip_aqe_reads(plan: TpuExec) -> TpuExec:
    """Replace every TpuAQEShuffleReadExec with its child exchange: the
    cluster splits stages AT exchanges, and a leftover adaptive reader
    above a ProcessShuffleReadExec would call .materialize on a node
    that has none."""
    from .exec.aqe import TpuAQEShuffleReadExec
    if isinstance(plan, TpuAQEShuffleReadExec):
        return _strip_aqe_reads(plan.child)
    kids = getattr(plan, "children", ())
    if kids:
        new = tuple(_strip_aqe_reads(c) for c in kids)
        if any(n is not o for n, o in zip(new, kids)):
            # with_new_children, not a children= mutation: nodes with
            # internal wiring (TopN's fused pipeline) rebuild over the
            # new child instead of silently executing the old one
            plan = plan.with_new_children(new)
    return plan


def _deepest_exchange(plan: TpuExec):
    """A shuffle exchange with no exchange below it (next runnable map
    stage), or None."""
    from .exec.exchange import TpuShuffleExchangeExec
    found = None

    def walk(node):
        nonlocal found
        for c in getattr(node, "children", ()):
            walk(c)
        if isinstance(node, TpuShuffleExchangeExec) and found is None:
            if not _contains_exchange(node.child):
                found = node

    walk(plan)
    return found


def _contains_exchange(plan: TpuExec) -> bool:
    from .exec.exchange import TpuShuffleExchangeExec
    if isinstance(plan, TpuShuffleExchangeExec):
        return True
    return any(_contains_exchange(c)
               for c in getattr(plan, "children", ()))


def _replace_node(plan: TpuExec, old: TpuExec, new: TpuExec) -> TpuExec:
    if plan is old:
        return new
    kids = getattr(plan, "children", ())
    if kids:
        nkids = tuple(_replace_node(c, old, new) for c in kids)
        if any(n is not o for n, o in zip(nkids, kids)):
            plan = plan.with_new_children(nkids)
    return plan


def _split_leaf_input(plan: TpuExec, n: int) -> List[TpuExec]:
    """Partition a map stage's input among n tasks: stages fed by an
    earlier shuffle split by partition range; otherwise by splitting the
    leaf (scan paths / host batches, round-robin). Un-splittable leaves
    mean one map task — still a correct stage, just not parallel."""
    from .exec.base import HostBatchSourceExec
    from .io.scan import TpuFileScanExec

    if _contains_read(plan):
        out = []
        for w in range(n):
            p = _slice_partitions(copy.deepcopy(plan), w, n)
            if p is not None:
                out.append(p)
        if out:
            return out
    # split ONE splittable leaf anywhere in the stage and replicate the
    # rest in every task. Multi-child stages (a join below the
    # exchange) split the side with the most input pieces: the join
    # distributes over the split side, so the task outputs union to
    # the full stage output — but ONLY if the other side is whole in
    # every task, which is why exactly one leaf is ever sliced.
    leaves: List[Tuple[tuple, TpuExec]] = []

    def walk(node, path):
        kids = getattr(node, "children", ())
        if not kids:
            leaves.append((path, node))
        for i, c in enumerate(kids):
            walk(c, path + (i,))

    walk(plan, ())
    # an aliased leaf (self-join holding the SAME node under both
    # parents) survives deepcopy as one shared object — slicing it
    # would slice BOTH sides and drop row pairs; leave it whole
    counts: Dict[int, int] = {}
    for _, lf in leaves:
        counts[id(lf)] = counts.get(id(lf), 0) + 1
    best = None  # (npieces, path, is_scan)
    for path, lf in leaves:
        if counts[id(lf)] > 1:
            continue
        if isinstance(lf, TpuFileScanExec) and len(lf.paths) > 1:
            pieces, is_scan = len(lf.paths), True
        elif isinstance(lf, HostBatchSourceExec) \
                and len(lf.batches) > 1:
            pieces, is_scan = len(lf.batches), False
        else:
            continue
        if best is None or pieces > best[0]:
            best = (pieces, path, is_scan)
    if best is None:
        return [plan]  # un-splittable stage: one map task
    _, path, is_scan = best
    out = []
    for i in range(n):
        p = copy.deepcopy(plan)
        node = p
        for j in path:
            node = node.children[j]
        pieces = (node.paths if is_scan else node.batches)[i::n]
        if not pieces:
            continue
        if is_scan:
            node.paths = list(pieces)
        else:
            node.batches = list(pieces)
        out.append(p)
    return out or [plan]


def _contains_read(plan: TpuExec) -> bool:
    if isinstance(plan, ProcessShuffleReadExec):
        return True
    return any(_contains_read(c) for c in getattr(plan, "children", ()))


def _slice_partitions(plan: TpuExec, w: int, n: int):
    """Restrict every ProcessShuffleReadExec to worker w's share of its
    partitions; None when w gets no partitions anywhere."""
    reads: List[ProcessShuffleReadExec] = []
    seen = set()

    def walk(node):
        if isinstance(node, ProcessShuffleReadExec) \
                and id(node) not in seen:
            # dedupe: an aliased subtree (self-join) holds the SAME
            # read node under both parents — slicing it twice would
            # leave partitions no worker reads
            seen.add(id(node))
            reads.append(node)
        for c in getattr(node, "children", ()):
            walk(c)

    walk(plan)
    if not reads:
        return None
    any_parts = False
    for r in reads:
        mine = r.partitions[w::n]
        # joins: both sides must see the SAME partition slice (they
        # were hash-partitioned by the same key count)
        r.partitions = mine
        if mine:
            any_parts = True
    return plan if any_parts else None


def _main(argv: Sequence[str]) -> None:
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--root", required=True)
    ap.add_argument("--worker", type=int, required=True)
    ap.add_argument("--heartbeat", type=float, default=0.5)
    args = ap.parse_args(argv)
    # multi-host mesh bootstrap (distributed/runtime.py): join the
    # driver's coordinator and build the global Mesh BEFORE this
    # process's first device touch (XLA_FLAGS are read at backend
    # init), then publish the readiness marker the driver gates gang
    # scheduling on. No-op without the mesh env; a failed bootstrap
    # degrades this worker to classic file-shuffle tasks.
    from .distributed import bootstrap_from_env
    bootstrap_from_env(args.root, args.worker)
    # lock-order watchdog rides the inherited env into every worker:
    # chaos/tier-1 runs under RAPIDS_TPU_LOCKWATCH=1 verify the
    # declared hierarchy against REAL worker-side acquisition orders.
    # Installed after module import, so worker-side coverage starts
    # with runtime-created locks (transports/batches/windows) — the
    # import-time singletons are covered by the driver-side conftest
    # bootstrap, which installs before the package imports. Reports
    # flush at clean shutdown only (an os._exit chaos crash loses its
    # report; the driver-side run still covers shared paths).
    from .analysis import lockwatch
    if lockwatch.env_enabled():
        lockwatch.install()
    try:
        worker_main(args.root, args.worker,
                    heartbeat_interval=args.heartbeat)
    finally:
        if lockwatch.installed():
            out = os.environ.get(lockwatch.ENV_OUT)
            if out:
                lockwatch.write_report(
                    f"{out}.w{args.worker}-{os.getpid()}")


if __name__ == "__main__":
    _main(sys.argv[1:])
