"""Device memory runtime (L2).

TPU analog of the reference's memory/scheduling stack (SURVEY.md §2.2-A:
GpuDeviceManager / GpuSemaphore / RapidsBufferCatalog +
RapidsDeviceMemoryStore / RapidsHostMemoryStore / SpillableColumnarBatch /
RmmRapidsRetryIterator; §5.3 layered OOM defense; reference mount empty —
built from the capability description). OOM on TPU is a hard crash
(SURVEY.md §7.3.5), so the defense is:

1. admission control — a task semaphore
   (``spark.rapids.sql.concurrentGpuTasks``),
2. a byte ledger against the HBM budget; registered batches are
   *spillable*: under pressure the catalog downloads them to host Arrow
   (device buffers dropped, XLA frees) and re-uploads on access,
3. split-and-retry — ``with_retry`` halves the input batch on device OOM
   (real RESOURCE_EXHAUSTED or injected via
   ``spark.rapids.sql.test.injectRetryOOM``) and processes the halves
   sequentially, up to ``spark.rapids.sql.oomRetry.maxSplits`` times.

Operators opt in at their memory cliffs (sort's global merge, aggregate's
partial merge) — the same integration points the reference uses.
"""
from __future__ import annotations

import os
import threading
import time
import uuid
from collections import OrderedDict
from typing import Callable, List, Optional

from .config import (ALLOC_FRACTION, CONCURRENT_TPU_TASKS,
                     DISK_ORPHAN_TTL, DISK_READ_RETRIES,
                     DISK_READ_RETRY_WAIT_MS, DISK_SPILL_LIMIT,
                     OOM_MAX_SPLITS, OOM_RETRY_BLOCKING,
                     OOM_RETRY_ENABLED, RapidsConf, TEST_DISK_FULL,
                     TEST_RETRY_OOM_INJECT, TEST_RETRY_OOM_STORM,
                     TEST_SLOW_DISK, TEST_SPILL_FAULT,
                     register, _bytes_conv)
from .lifecycle import FairAdmissionController, LADDER_EXCLUSIVE_TIMEOUT
from .obs.metrics import REGISTRY as _METRICS
from .obs.recorder import RECORDER as _FLIGHT

__all__ = ["DeviceMemoryManager", "SpillableBatch", "SpillReadError",
           "TpuRetryOOM", "QueryBudgetExceeded", "resolve_device_budget",
           "split_batch", "spill_namespace", "sweep_orphan_spill_dirs"]

DEVICE_BUDGET = register(
    "spark.rapids.memory.device.budgetBytes", 0,
    "Device HBM byte budget for the spillable-batch catalog; 0 = auto "
    "(allocFraction x the device's reported memory; a CPU backend, "
    "which reports none, counts as 6GiB). "
    "Tests set this low to force spill.", conv=_bytes_conv)

# Live ledger state (gauges follow the shared manager; processes with
# several isolated managers — OOM-injection tests — report the last
# writer) plus monotonic pressure counters, scrapeable mid-query.
_MEM_DEVICE_IN_USE = _METRICS.gauge(
    "rapids_memory_device_bytes_in_use",
    "Device bytes the spillable-batch ledger currently charges "
    "against the HBM budget.")
_MEM_DEVICE_BUDGET = _METRICS.gauge(
    "rapids_memory_device_budget_bytes",
    "Device HBM budget the ledger evicts against "
    "(spark.rapids.memory.device.budgetBytes, resolved).")
_MEM_HOST_IN_USE = _METRICS.gauge(
    "rapids_memory_host_bytes_in_use",
    "Host-tier bytes held by spilled batches.")
_MEM_SPILL_BYTES = _METRICS.counter(
    "rapids_memory_spill_bytes_total",
    "Total bytes ever spilled device -> host.")
_MEM_DISK_SPILL_BYTES = _METRICS.counter(
    "rapids_memory_disk_spill_bytes_total",
    "Total bytes ever tiered host -> disk.")
_MEM_OOM_RETRIES = _METRICS.counter(
    "rapids_memory_oom_retries_total",
    "Device OOM events answered by split-and-retry (each splits one "
    "batch in half and reruns).")
_DISK_IN_USE = _METRICS.gauge(
    "rapids_disk_spill_in_use_bytes",
    "LIVE disk-tier spill residency (bytes of committed spill files "
    "not yet read back or released) — returns to zero when every "
    "query's batches are released.")
_SPILL_READ_BYTES = _METRICS.counter(
    "rapids_spill_read_bytes_total",
    "Total bytes read back (and CRC-verified) from the disk spill "
    "tier. With the write counters this closes the spill byte "
    "ledger per query for the telemetry warehouse.")
_SPILL_READ_FAILURES = _METRICS.counter(
    "rapids_spill_read_failures_total",
    "Spill-file read-backs that failed verification, classified: "
    "missing (file gone), corrupt (CRC mismatch), torn (truncated "
    "trailer / size disagreement), io (persistently unreadable after "
    "the bounded in-place retries).", ("kind",))
_SPILL_WRITE_FAILURES = _METRICS.counter(
    "rapids_spill_write_failures_total",
    "Disk-spill writes that could not commit, classified: enospc "
    "(the filesystem is full — real or injected), budget (the live "
    "disk residency budget spark.rapids.memory.disk.limit could not "
    "fit the file even after evicting old disk entries), io (any "
    "other OSError). The batch stays host-resident in every case — "
    "a failed spill never loses data or crashes the eviction "
    "cascade.", ("kind",))


class TpuRetryOOM(RuntimeError):
    """Device OOM surfaced to the retry framework (GpuRetryOOM analog).

    ``ladder_exhausted`` marks the classified terminal form: the
    degradation ladder walked halve -> spill -> width1 and still hit
    OOM — the collect root answers it with the per-operator CPU
    fallback rung instead of failing the query."""

    ladder_exhausted = False


class QueryBudgetExceeded(TpuRetryOOM):
    """A per-query memory budget (spark.rapids.query.memoryBudgetBytes)
    would be exceeded — a query-local OOM: it feeds the same
    split-and-retry/degradation ladder as a real RESOURCE_EXHAUSTED,
    but its terminal rung is QueryCancelled(reason=budget), not CPU
    fallback."""


class SpillReadError(RuntimeError):
    """A disk-tier spill file failed its verified read-back, classified
    like a shuffle FetchFailure (``kind in (missing, corrupt, torn,
    io)``). On a cluster worker this escalates through the task path
    with a structured ``.spillfail`` marker, so the scheduler retries
    the task WITHOUT blaming the reading worker — re-execution
    regenerates the data the disk lost."""

    KINDS = ("missing", "corrupt", "torn", "io")

    def __init__(self, kind: str, path: str, detail: str = ""):
        self.kind = kind if kind in self.KINDS else "io"
        self.path = path
        self.detail = detail
        super().__init__(
            f"spill file unreadable [{self.kind}] at {path}"
            + (f": {detail}" if detail else ""))


# --- incarnation-scoped spill namespaces + orphan GC -------------------------

#: sticky disk-pressure window: how long a refused disk write keeps the
#: manager classifying follow-on memory pressure as budget-terminal
#: (self-expiring so a transiently full disk can't poison the manager)
_DISK_PRESSURE_WINDOW_S = 30.0

#: one token per process lifetime: a respawned worker with a recycled
#: pid still gets a fresh namespace, so its predecessor's files can
#: never be mistaken for its own
_INCARNATION = uuid.uuid4().hex[:8]
#: spill roots this process has already swept (the manager-construction
#: sweep runs once per root per process; cluster boot forces a pass)
_SWEPT_ROOTS: set = set()
_SWEEP_LOCK = threading.Lock()


def _hostname() -> str:
    import platform
    return (platform.node() or "localhost").split(".")[0]


def spill_namespace(base: str) -> str:
    """This process's incarnation-scoped spill directory under the
    configured spill root: ``<base>/<host>-<pid>-<incarnation>``.
    Every spill file this process ever writes lives here, so a crash
    leaks at most one attributable directory — which the next
    process's sweep reclaims."""
    return os.path.join(
        base, f"{_hostname()}-{os.getpid()}-{_INCARNATION}")


def _pid_alive(pid: int) -> bool:
    if pid <= 1:
        return False  # never a spiller; parse artifact at worst
    try:
        os.kill(pid, 0)
    except ProcessLookupError:
        return False
    except PermissionError:
        return True  # exists, owned by someone else
    except OSError:
        return True  # can't prove death: leave it to the age fallback
    return True


def sweep_orphan_spill_dirs(base: str, ttl_s: float = 86400.0,
                            force: bool = False) -> List[str]:
    """Reclaim spill namespaces whose owner process is gone: same-host
    directories whose pid is provably dead go immediately; foreign-host
    (or unparseable-owner) directories fall back to the ``ttl_s`` age
    bound, because a pid from another machine proves nothing. Same-host
    directories whose pid is ALIVE are deliberately exempt from the age
    fallback: an mtime-based TTL cannot tell a crashed namespace whose
    pid the OS recycled from a long-running worker whose oldest spill
    file simply aged past the TTL, and deleting live spill data loses a
    query — a recycled-pid leak is bounded and ends with the usurping
    process, so the safe side is to leave it. Runs
    once per root per process at manager construction (``force`` for
    cluster boot, which must reclaim even when this driver process
    already swept for an earlier cluster). Returns the removed paths;
    never raises — reclamation must not fail the startup it rides."""
    import re
    import shutil
    with _SWEEP_LOCK:
        key = os.path.abspath(base)
        if not force and key in _SWEPT_ROOTS:
            return []
        _SWEPT_ROOTS.add(key)
    removed: List[str] = []
    own = os.path.basename(spill_namespace(base))
    host = _hostname()
    pat = re.compile(r"^(?P<host>.+)-(?P<pid>\d+)-[0-9a-f]{8}$")
    try:
        names = os.listdir(base)
    except OSError:
        return removed
    now = time.time()
    for n in names:
        p = os.path.join(base, n)
        try:
            m = pat.match(n)
            if n == own:
                continue
            if m is not None and os.path.isdir(p):
                if m.group("host") == host:
                    dead = not _pid_alive(int(m.group("pid")))
                else:  # foreign host: only age can prove abandonment
                    # tpu-lint: allow[wallclock-duration] compared against file MTIMES, which are wall clock — monotonic cannot be
                    dead = now - os.path.getmtime(p) > ttl_s
                if dead:
                    shutil.rmtree(p, ignore_errors=True)
                    removed.append(p)
            elif n.startswith("spill-") and n.endswith(".arrow") \
                    and os.path.isfile(p) \
                    and now - os.path.getmtime(p) > ttl_s:  # tpu-lint: allow[wallclock-duration] file-mtime age, wall clock by nature
                # pre-namespace flat files from older builds: age-only
                os.unlink(p)
                removed.append(p)
        except OSError:
            continue
    if removed:
        _FLIGHT.record("mem", ev="orphan_sweep", bytes=0,
                       removed=len(removed), base=base)
    return removed


def resolve_device_budget(conf: Optional[RapidsConf] = None) -> int:
    """The HBM byte budget the spillable-batch ledger enforces —
    spark.rapids.memory.device.budgetBytes, or allocFraction x the
    device's reported memory when unset (6GiB on a CPU backend, which
    reports none; a TPU that reports none is an error). Factored out
    so the static plan verifier checks footprint estimates against the
    SAME number the runtime ledger evicts against."""
    conf = conf or RapidsConf()
    budget = conf.get(DEVICE_BUDGET)
    if not budget:
        budget = int(DeviceMemoryManager._device_memory()
                     * conf.get(ALLOC_FRACTION))
    return budget


def _is_oom_error(e: BaseException) -> bool:
    """Only the runtime's own errors count as device OOM — arbitrary
    exceptions whose message happens to contain the markers must not be
    silently split-and-retried (they'd mask the real failure). What a
    v5e raises on exhaustion (jax 0.9, chip run of PR 21): a program
    that cannot get its buffers fails with ``JaxRuntimeError:
    RESOURCE_EXHAUSTED: Error allocating device buffer: Attempting to
    allocate 64.00G. That was not possible. There are 15.75G free.``;
    an eager array creation that cannot fails with the same status
    text, but as a plain ``ValueError``."""
    if isinstance(e, TpuRetryOOM):
        return True
    from jax.errors import JaxRuntimeError
    s = str(e)
    if isinstance(e, JaxRuntimeError):
        return "RESOURCE_EXHAUSTED" in s or "out of memory" in s.lower()
    return isinstance(e, ValueError) and s.startswith("RESOURCE_EXHAUSTED:")


def split_batch(batch):
    """Split a device batch at the capacity midpoint into two half-capacity
    batches (the GpuSplitAndRetryOOM halving). Fixed-width lanes are static
    slices; string chars/offsets stay shared (offsets are absolute), so the
    split itself allocates only the halved fixed-width lanes.

    Known limitation: the split is positional, not selection-aware — a
    lazy-filter batch whose live rows all fall in one half retries that
    half with the same live footprint (the halving still shrinks the
    STATIC capacity, which is what bounds the retried program's
    allocations, so the retry remains productive)."""
    from .columnar.batch import TpuBatch
    import jax.numpy as jnp
    cap = batch.capacity
    if cap < 2:
        raise TpuRetryOOM("cannot split a 1-row batch further")
    h = cap // 2
    rc = batch.row_count

    def halves(c):
        if c.data is not None:
            return (c.with_arrays(data=c.data[:h], validity=c.validity[:h]),
                    c.with_arrays(data=c.data[h:], validity=c.validity[h:]))
        if c.offsets is not None:  # strings/arrays: payload stays shared
            return (c.with_arrays(offsets=c.offsets[:h + 1],
                                  validity=c.validity[:h]),
                    c.with_arrays(offsets=c.offsets[h:],
                                  validity=c.validity[h:]))
        if c.children is not None:  # struct: halve children with the rows
            pairs = [halves(ch) for ch in c.children]
            return (c.with_arrays(validity=c.validity[:h],
                                  children=[p[0] for p in pairs]),
                    c.with_arrays(validity=c.validity[h:],
                                  children=[p[1] for p in pairs]))
        return (c.with_arrays(validity=c.validity[:h]),
                c.with_arrays(validity=c.validity[h:]))

    pairs = [halves(c) for c in batch.columns]
    rc1 = jnp.minimum(rc, jnp.int32(h))
    rc2 = jnp.maximum(rc - h, 0)
    sel1 = batch.selection[:h] if batch.selection is not None else None
    sel2 = batch.selection[h:] if batch.selection is not None else None
    b1 = TpuBatch([p[0] for p in pairs], batch.schema, rc1, selection=sel1)
    b2 = TpuBatch([p[1] for p in pairs], batch.schema, rc2, selection=sel2)
    return b1, b2


class SpillableBatch:
    """A catalog-registered device batch that tiers device -> host Arrow
    -> disk Arrow IPC (SpillableColumnarBatch over the reference's
    device/host/disk store ladder — SURVEY.md:143)."""

    def __init__(self, mgr: "DeviceMemoryManager", batch):
        self._mgr = mgr
        self._device = batch
        self._host = None
        self._disk_path = None
        self._disk_size = 0       # committed spill-file bytes (w/ footer)
        self._no_disk_until = 0.0  # barred from re-tiering after a
        #                            budget-driven promotion (anti-churn)
        self._promote_bad = False  # terminal read-back failure seen by
        #                            budget eviction: skip as a victim
        self._schema = batch.schema
        self.nbytes = batch.device_size_bytes()
        self.host_nbytes = 0
        self.spill_count = 0
        # serializes THIS batch's tier transitions (spill / to-disk /
        # read-back) against concurrent tasks, without holding the
        # manager's ledger lock across device/disk IO
        self._state_lock = threading.RLock()

    @property
    def on_device(self) -> bool:
        return self._device is not None

    @property
    def on_disk(self) -> bool:
        return self._disk_path is not None

    def spill(self, cascade: bool = True, best_effort: bool = False):
        """Download to host Arrow, drop the device buffers (XLA frees),
        and credit the ledger; host pressure cascades to the disk tier.

        Lock order: this batch's _state_lock, then (briefly) the ledger
        lock. Eviction paths pass ``best_effort=True``: the state lock is
        only try-acquired, so a thread that already holds ANOTHER batch's
        state lock (get()/register mid-flight) can never enter a
        hold-and-wait cycle across batches — a busy batch is simply a
        poor spill victim and is skipped (ADVICE r3 #1)."""
        acquired = self._state_lock.acquire(blocking=not best_effort)
        if not acquired:
            return
        try:
            if self._device is None:
                return
            from .columnar.arrow_bridge import device_to_arrow
            host = device_to_arrow(self._device)
            with self._mgr._lock:
                if id(self) not in self._mgr._catalog:
                    return  # released concurrently; drop the download
                self._host = host
                self._device = None
                self.spill_count += 1
                self.host_nbytes = host.nbytes
                self._mgr.device_bytes -= self.nbytes
                self._mgr.spill_bytes += self.nbytes
                self._mgr.host_bytes += self.host_nbytes
            _MEM_SPILL_BYTES.inc(self.nbytes)
            self._mgr._sync_gauges()
            self._mgr._flight_mem("spill", self.nbytes)
        finally:
            self._state_lock.release()
        if cascade:
            self._mgr._evict_host_to_disk()

    def spill_to_disk(self, best_effort: bool = False) -> bool:
        """Host Arrow -> sealed (CRC32C+length trailer) Arrow IPC file
        under the process's incarnation spill namespace, committed via
        tmp+rename so a crash mid-write can never publish a torn file
        (disk tier, SURVEY.md:143; same sealed format as shuffle
        blocks, shuffle/integrity.py). A write the disk cannot take —
        real/injected ENOSPC, or a live-residency budget
        (spark.rapids.memory.disk.limit) that stays breached after
        evicting the oldest unpinned disk entries back to host —
        cleans up its partial file, records classified disk pressure,
        and leaves the batch host-resident: a full disk degrades the
        tiering, it never throws OSError into another query's eviction
        cascade. Returns True only when the file committed.
        best_effort: see spill()."""
        acquired = self._state_lock.acquire(blocking=not best_effort)
        if not acquired:
            return False
        try:
            if self._host is None or self._disk_path is not None:
                return False
            if time.monotonic() < self._no_disk_until:
                # just promoted off disk to make budget room: re-tiering
                # immediately would ping-pong the same bytes
                return False
            with self._mgr._lock:
                # released concurrently: don't write an orphan spill file
                if id(self) not in self._mgr._catalog:
                    return False
            import pyarrow as pa
            from .shuffle.integrity import FOOTER_LEN, write_sealed_file
            mgr = self._mgr
            sink = pa.BufferOutputStream()
            with pa.ipc.new_file(sink, self._host.schema) as w:
                w.write_batch(self._host)
            payload = sink.getvalue()
            fsize = len(payload) + FOOTER_LEN
            # tpu-lint: allow[blocking-under-lock] disk-budget eviction rides the (accepted) IO-under-state-lock spill design; victim locks are only try-acquired
            if not mgr._disk_budget_admit(fsize):
                return False  # classified budget pressure; stays on host
            # admitted: fsize is now RESERVED in disk_in_use_bytes —
            # released below on every path that does not commit
            committed = False
            try:
                os.makedirs(mgr.spill_dir, exist_ok=True)
                path = os.path.join(mgr.spill_dir,
                                    f"spill-{uuid.uuid4().hex}.arrow")
                for retry in (False, True):
                    try:
                        if mgr._slow_disk_s > 0:
                            # tpu-lint: allow[blocking-under-lock] slow_disk chaos models the real (accepted) IO-under-state-lock spill design
                            time.sleep(mgr._slow_disk_s)
                        # sealed write (CRC32C+length trailer) committed
                        # via tmp+rename; a failure — injected or real —
                        # unlinks the partial tmp before raising
                        # tpu-lint: allow[blocking-under-lock] the sealed spill write IS the documented IO-under-state-lock design (see baseline note on spill_to_disk)
                        write_sealed_file(
                            path, payload,
                            fail_hook=mgr._maybe_inject_disk_full)
                        break
                    except OSError as e:
                        import errno as _errno
                        enospc = getattr(e, "errno", None) == _errno.ENOSPC
                        if enospc and not retry:
                            # disk-pressure response rung 1: evict the
                            # oldest unpinned disk entries back to host
                            # (frees OUR files), then one retry
                            # tpu-lint: allow[blocking-under-lock] accepted IO-under-state-lock spill design; victim locks are only try-acquired
                            mgr._evict_disk_to_host(fsize)
                            continue
                        # tpu-lint: allow[blocking-under-lock] best-effort classified-evidence append (accepted IO-under-state-lock spill design)
                        mgr._note_disk_pressure(
                            "enospc" if enospc else "io", path, str(e))
                        return False
                # tpu-lint: allow[blocking-under-lock] post-commit chaos damage, test-only seam of the accepted IO-under-state-lock spill design
                mgr._maybe_damage_spill_file(path, len(payload))
                committed = True
            finally:
                if not committed:
                    with mgr._lock:
                        mgr.disk_in_use_bytes -= fsize
                    mgr._sync_gauges()
            mgr._clear_disk_pressure()
            self._disk_path = path
            self._disk_size = fsize
            self._promote_bad = False  # fresh committed file
            self._host = None
            with mgr._lock:
                mgr.host_bytes -= self.host_nbytes
                mgr.disk_spill_bytes += self.host_nbytes
            _MEM_DISK_SPILL_BYTES.inc(self.host_nbytes)
            mgr._sync_gauges()
            mgr._flight_mem("disk_spill", self.host_nbytes)
            return True
        finally:
            self._state_lock.release()

    def _promote_to_host(self) -> int:
        """Disk -> host promotion (the 'evict oldest unpinned disk
        entries' rung of the disk-pressure response): verified
        read-back, file unlinked, host tier re-charged. Try-acquire
        only — the caller already holds another batch's state lock.
        Returns the disk bytes freed (0 when busy, not on disk, or the
        read-back failed classification — a bad file is left for the
        real consumer to classify, never silently dropped)."""
        if not self._state_lock.acquire(blocking=False):
            return 0
        try:
            if self._disk_path is None or self._host is not None \
                    or self._promote_bad:
                return 0
            freed = self._disk_size
            try:
                # tpu-lint: allow[blocking-under-lock] verified read-back rides the (accepted) IO-under-state-lock spill design
                host = self._read_disk()
            except SpillReadError:
                # consumer raises the classified error later; a bad
                # victim must not be re-scanned — its failure
                # re-counted and (for persistent EIO) the full retry
                # ladder re-slept under another batch's spill — by
                # every subsequent eviction pass. Consumer reads are
                # unaffected; a healed entry merely stops being an
                # eviction victim until it re-commits
                self._promote_bad = True
                return 0
            self._host = host
            self._no_disk_until = time.monotonic() + 5.0
            with self._mgr._lock:
                self._mgr.host_bytes += self.host_nbytes
            self._mgr._sync_gauges()
            return freed
        finally:
            self._state_lock.release()

    def _read_disk(self):
        """Verified read-back of the committed spill file: footer +
        CRC checked, transient IO retried in place (EIO sidecars
        included — same grammar as shuffle fetches), every failure a
        classified :class:`SpillReadError`. Failure leaves the batch's
        tier state untouched (the bad file stays referenced so a later
        consumer — or release() — sees the same classified state, not
        an inconsistent one)."""
        import pyarrow as pa
        from .shuffle import integrity
        mgr = self._mgr
        path = self._disk_path
        if mgr._slow_disk_s > 0:
            # tpu-lint: allow[blocking-under-lock] slow_disk chaos models the real (accepted) IO-under-state-lock spill design
            time.sleep(mgr._slow_disk_s)
        try:
            # tpu-lint: allow[blocking-under-lock] spill read-back IS the documented IO-under-state-lock design (see baseline note on spill_to_disk)
            payload = integrity.read_sealed_file(
                path, lambda kind, detail: SpillReadError(kind, path,
                                                          detail),
                max_retries=mgr.disk_read_retries,
                retry_wait_s=mgr.disk_read_wait_s,
                on_retry=lambda n, e: mgr._flight_mem(
                    "spill_read_retry", 0, n=n, error=str(e)[:120]),
                missing_detail="committed spill file is gone")
            _SPILL_READ_BYTES.inc(len(payload))
            table = pa.ipc.open_file(
                pa.BufferReader(payload)).read_all().combine_chunks()
        except SpillReadError as e:
            mgr._note_spill_read_failure(e)
            raise
        import contextlib
        with contextlib.suppress(OSError):
            # the verified read SUCCEEDED: a failing unlink (EACCES,
            # ro-remount) must not escape as an unclassified OSError
            # that discards the table and blames the reading worker —
            # the stale file is a bounded leak the next incarnation's
            # orphan sweep reclaims
            os.unlink(self._disk_path)
        # tpu-lint: allow[unlocked-shared-mutation] private helper: only reached from get_host/_promote_to_host, which hold this batch's _state_lock
        self._disk_path = None
        with mgr._lock:
            mgr.disk_in_use_bytes -= self._disk_size
        # tpu-lint: allow[unlocked-shared-mutation] same _state_lock guarantee as _disk_path above
        self._disk_size = 0
        mgr._sync_gauges()
        rbs = table.to_batches()
        if rbs:
            return rbs[0]
        # 0-row tables yield no batches: rebuild an empty RecordBatch
        return pa.RecordBatch.from_arrays(
            [pa.array([], type=fld.type) for fld in table.schema],
            schema=table.schema)

    def get_host(self):
        """Host Arrow view (spills if still on device; reads back —
        and verifies — the disk tier if spilled further). A disk
        read-back that fails classification raises
        :class:`SpillReadError`; the event-log line is written outside
        this method's own lock scope — though a :meth:`get` caller
        still holds its outer (reentrant) acquisition, so that path
        stays IO-under-lock like the rest of the accepted spill
        design."""
        try:
            with self._state_lock:
                if self._host is None and self._disk_path is not None:
                    # tpu-lint: allow[blocking-under-lock] verified disk read-back (incl. the slow_disk chaos sleep) rides the (accepted) IO-under-state-lock spill design
                    self._host = self._read_disk()
                    with self._mgr._lock:
                        self._mgr.host_bytes += self.host_nbytes
                if self._host is None:
                    from .columnar.arrow_bridge import device_to_arrow
                    self._host = device_to_arrow(self._device)
                return self._host
        except SpillReadError as e:
            self._mgr._log_spill_read_failure(e)
            raise

    def get(self):
        """The device batch, re-uploading (and re-charging the ledger) if
        spilled."""
        with self._state_lock:
            if self._device is None:
                from .columnar.arrow_bridge import arrow_to_device
                # tpu-lint: allow[blocking-under-lock] verified disk read-back rides the (accepted) IO-under-state-lock spill design
                host = self.get_host()
                self._mgr._charge(self, self.nbytes)
                try:
                    device = arrow_to_device(host, self._schema)
                except BaseException:
                    # unwind the charge: a failed re-upload must not
                    # strand device_bytes on a batch whose _device
                    # stays None (the batch is still host-resident and
                    # retryable) [PR 12 satellite: ledger leak]
                    self._mgr._uncharge(self, self.nbytes)
                    raise
                self._device = device
                self._host = None
                with self._mgr._lock:
                    self._mgr.host_bytes -= self.host_nbytes
            self._mgr._touch(self)
            return self._device

    def pin(self):
        """Keep resident (refcounted) — route through the owning manager,
        which may differ from the current query's."""
        self._mgr.pin(self)

    def unpin(self):
        self._mgr.unpin(self)

    def release(self):
        # under the state lock: a concurrent spill()/spill_to_disk() must
        # not write files or move tiers while the batch is being dropped
        # (ADVICE r3 #2)
        with self._state_lock:
            self._mgr._release(self)
            if self._disk_path is not None:
                import contextlib
                with contextlib.suppress(OSError):
                    os.unlink(self._disk_path)
                self._disk_path = None
                if self._disk_size:
                    with self._mgr._lock:
                        self._mgr.disk_in_use_bytes -= self._disk_size
                    self._disk_size = 0
                    self._mgr._sync_gauges()
            self._device = None
            self._host = None


class DeviceMemoryManager:
    """Budget ledger + spill catalog + task semaphore + retry framework.

    Use ``DeviceMemoryManager.shared(conf)`` in execution paths: the
    reference's GpuSemaphore/RapidsBufferCatalog are process-wide
    singletons, so concurrent queries must draw admission slots and HBM
    budget from ONE ledger. Direct construction is for tests that need an
    isolated manager."""

    _shared: dict = {}
    _shared_lock = threading.Lock()

    @classmethod
    def shared(cls, conf: Optional[RapidsConf] = None) \
            -> "DeviceMemoryManager":
        """Process-level manager keyed by the memory-relevant conf values
        (one per distinct memory configuration; all default-conf queries
        share one instance). OOM-injection confs always get a fresh
        instance — the injection counter is per-test state."""
        conf = conf or RapidsConf()
        if conf.get(TEST_RETRY_OOM_INJECT) \
                or conf.get(TEST_RETRY_OOM_STORM) \
                or conf.get(TEST_DISK_FULL) \
                or conf.get(TEST_SPILL_FAULT) \
                or conf.get(TEST_SLOW_DISK):
            # spill/disk fault injections carry per-test countdown
            # state (or, for slow_disk, a construction-time delay that
            # must neither bleed into nor be masked by a cached
            # manager), exactly like the OOM injections
            return cls(conf)
        from .config import (HOST_SPILL_LIMIT, INJECT_FAULTS, LEAK_DEBUG,
                             MEM_DEBUG, SPILL_DIR)
        from .lifecycle import (ADMISSION_MAX_QUEUE, ADMISSION_TIMEOUT,
                                ADMISSION_WEIGHTS)
        key = (conf.get(DEVICE_BUDGET), conf.get(ALLOC_FRACTION),
               conf.get(CONCURRENT_TPU_TASKS), conf.get(OOM_RETRY_ENABLED),
               conf.get(OOM_MAX_SPLITS), conf.get(OOM_RETRY_BLOCKING),
               conf.get(HOST_SPILL_LIMIT), conf.get(SPILL_DIR),
               conf.get(DISK_SPILL_LIMIT), conf.get(DISK_READ_RETRIES),
               conf.get(DISK_READ_RETRY_WAIT_MS), conf.get(DISK_ORPHAN_TTL),
               conf.get(MEM_DEBUG), conf.get(LEAK_DEBUG),
               # admission policy rides the manager (the controller is
               # its slot owner); chaos specs fragment managers only in
               # tests that set them
               conf.get(ADMISSION_TIMEOUT), conf.get(ADMISSION_MAX_QUEUE),
               conf.get(ADMISSION_WEIGHTS), conf.get(INJECT_FAULTS))
        with cls._shared_lock:
            mgr = cls._shared.get(key)
            if mgr is None:
                # tpu-lint: allow[blocking-under-lock] once-per-process-per-root orphan-GC sweep rides manager construction, same acceptance as the gauge/flight publishes at this level
                mgr = cls(conf)
                cls._shared[key] = mgr
            return mgr

    def __init__(self, conf: Optional[RapidsConf] = None):
        self.conf = conf or RapidsConf()
        self.budget = resolve_device_budget(self.conf)
        self._lock = threading.RLock()
        self._catalog: "OrderedDict[int, SpillableBatch]" = OrderedDict()
        self._pin_counts: dict = {}  # id -> refcount (shared consumers)
        self.device_bytes = 0
        self.spill_bytes = 0  # total bytes ever spilled (metric)
        from .config import HOST_SPILL_LIMIT, SPILL_DIR
        self.host_bytes = 0          # host-tier residency
        self.disk_spill_bytes = 0    # total bytes ever tiered to disk
        self.disk_in_use_bytes = 0   # LIVE disk-tier residency
        self.host_limit = self.conf.get(HOST_SPILL_LIMIT)
        self.spill_root = self.conf.get(SPILL_DIR)
        # every file this process writes lands in its incarnation
        # namespace; a crash leaks one attributable dir, reclaimed by
        # the next process's sweep below
        self.spill_dir = spill_namespace(self.spill_root)
        self.disk_limit = self.conf.get(DISK_SPILL_LIMIT)
        self.disk_read_retries = self.conf.get(DISK_READ_RETRIES)
        self.disk_read_wait_s = \
            self.conf.get(DISK_READ_RETRY_WAIT_MS) / 1e3
        self._disk_pressure_until = 0.0  # monotonic; sticky window
        self._spill_fault = self.conf.get(TEST_SPILL_FAULT)
        self._disk_full_countdown = self.conf.get(TEST_DISK_FULL)
        self._slow_disk_s = self.conf.get(TEST_SLOW_DISK)
        sweep_orphan_spill_dirs(self.spill_root,
                                self.conf.get(DISK_ORPHAN_TTL))
        # fair admission over the GpuSemaphore seats (lifecycle.py):
        # bounded per-tenant queues + weighted grants + queue-time
        # deadline; legacy task_slot() callers get the old FIFO
        # semantics through the default tenant
        self.admission = FairAdmissionController(
            self.conf.get(CONCURRENT_TPU_TASKS), self.conf)
        self._retry_enabled = self.conf.get(OOM_RETRY_ENABLED)
        self._retry_blocking = self.conf.get(OOM_RETRY_BLOCKING)
        self.max_splits = self.conf.get(OOM_MAX_SPLITS)
        self._inject_after = self.conf.get(TEST_RETRY_OOM_INJECT)
        self._inject_storm = self.conf.get(TEST_RETRY_OOM_STORM)
        self._op_count = 0
        from .config import LEAK_DEBUG, MEM_DEBUG
        self._mem_debug = self.conf.get(MEM_DEBUG) == "STDOUT"
        self._leak_debug = self.conf.get(LEAK_DEBUG)
        self._alloc_sites: dict = {}  # id -> traceback summary
        _MEM_DEVICE_BUDGET.set(self.budget)
        self._sync_gauges()
        self._flight_mem("budget")

    def _sync_gauges(self):
        """Publish the ledger to the process registry — plain attribute
        writes, cheap enough to run on every transition."""
        _MEM_DEVICE_IN_USE.set(self.device_bytes)
        _MEM_HOST_IN_USE.set(self.host_bytes)
        _DISK_IN_USE.set(self.disk_in_use_bytes)

    def _flight_mem(self, ev: str, nbytes: int = 0, **extra):
        """Flight-recorder tap: every ledger transition lands in the
        always-on ring with the in-use bytes AFTER it — the per-process
        HBM timeline an incident bundle replays (high-water tracking is
        derived at harvest, obs/recorder.memory_timeline). The budget
        rides on EVERY event (one int): an incident harvest scopes
        rings to its query window, which would otherwise drop the lone
        construction-time budget record of a long-lived manager."""
        _FLIGHT.record("mem", ev=ev, bytes=int(nbytes),
                       device=self.device_bytes, host=self.host_bytes,
                       budget=self.budget, **extra)

    def _debug(self, event: str, sb: "SpillableBatch"):
        if self._mem_debug:
            print(f"[rapids-mem] {event} id={id(sb):#x} "
                  f"bytes={sb.nbytes} device={self.device_bytes} "
                  f"host={self.host_bytes}")

    def leak_report(self) -> str:
        """Catalog entries never released, with their registration sites
        (spark.rapids.refcount.debug — SURVEY.md §5.2)."""
        with self._lock:
            live = [(id(sb), sb.nbytes,
                     self._alloc_sites.get(id(sb), "<site untracked>"))
                    for sb in self._catalog.values()]
        if not live:
            return "no leaked catalog entries"
        lines = [f"{len(live)} catalog entr"
                 f"{'y' if len(live) == 1 else 'ies'} never released:"]
        for key, nbytes, site in live:
            lines.append(f"  id={key:#x} bytes={nbytes}\n    {site}")
        return "\n".join(lines)

    @staticmethod
    def _device_memory() -> int:
        """The device's own ``bytes_limit``. A CPU backend reports no
        memory stats and gets a 6 GiB stand-in (the test mesh); a TPU
        that reports none is an error, not a quietly smaller chip."""
        import jax
        dev = jax.local_devices()[0]  # addressable from THIS process
        stats = dev.memory_stats() or {}
        if stats.get("bytes_limit"):
            return int(stats["bytes_limit"])
        if dev.platform == "tpu":
            raise RuntimeError(
                f"{dev.device_kind} reports no memory_stats()"
                f"['bytes_limit'] ({sorted(stats)}): set "
                "spark.rapids.memory.device.budgetBytes explicitly")
        return 6 << 30

    # --- catalog / ledger -------------------------------------------------

    def register(self, batch, pinned: bool = False) -> SpillableBatch:
        """Add a device batch to the catalog. With ``pinned`` the new
        batch is pinned BEFORE eviction runs, so a consumer about to use
        it (join build side) doesn't watch it get spilled and pay a
        pointless download+re-upload at peak pressure."""
        sb = SpillableBatch(self, batch)
        with self._lock:
            self._catalog[id(sb)] = sb
            if pinned:
                self._pin_counts[id(sb)] = \
                    self._pin_counts.get(id(sb), 0) + 1
            self.device_bytes += sb.nbytes
            if self._leak_debug:
                import traceback
                # drop only the register() frame itself: the caller is
                # the allocation site being reported
                self._alloc_sites[id(sb)] = "".join(
                    traceback.format_stack(limit=6)[:-1]).strip()
        self._evict_to_fit(exclude=id(sb) if pinned else None)
        self._sync_gauges()
        self._flight_mem("reserve", sb.nbytes)
        self._debug("register", sb)
        return sb

    def _charge(self, sb: SpillableBatch, nbytes: int):
        with self._lock:
            self.device_bytes += nbytes
            self._catalog[id(sb)] = sb
        # exclude this batch from BOTH eviction tiers: the caller
        # (get()) holds its state lock, and a same-thread best-effort
        # acquire on an RLock would succeed — the batch would tier
        # itself to disk mid-re-upload and skew the host ledger
        self._evict_to_fit(exclude=id(sb))
        self._sync_gauges()
        self._flight_mem("readback", nbytes)

    def _uncharge(self, sb: SpillableBatch, nbytes: int):
        """Undo a _charge whose re-upload failed: the batch is still
        catalog-resident on its host/disk tier, only the device bytes
        come back off the ledger."""
        with self._lock:
            self.device_bytes -= nbytes
        self._sync_gauges()
        self._flight_mem("readback_undo", nbytes)

    def _touch(self, sb: SpillableBatch):
        with self._lock:
            if id(sb) in self._catalog:
                self._catalog.move_to_end(id(sb))

    def _release(self, sb: SpillableBatch):
        with self._lock:
            if self._catalog.pop(id(sb), None) is not None:
                if sb.on_device:
                    self.device_bytes -= sb.nbytes
                elif sb._host is not None:
                    self.host_bytes -= sb.host_nbytes
            self._pin_counts.pop(id(sb), None)
            self._alloc_sites.pop(id(sb), None)
        self._sync_gauges()
        self._flight_mem("release", sb.nbytes)
        self._debug("release", sb)

    def _evict_host_to_disk(self, exclude: Optional[int] = None):
        """Cascade the host tier to disk when past
        spark.rapids.memory.host.spillStorageSize (the reference's
        host-store overflow-to-disk ladder). Victim state locks are only
        try-acquired (see SpillableBatch.spill lock-order note);
        ``exclude`` shields the batch the calling thread itself holds."""
        with self._lock:
            if self.host_bytes <= self.host_limit:
                return
            victims = [sb for sb in self._catalog.values()
                       if sb._host is not None and not sb.on_device
                       and id(sb) != exclude]
        for sb in victims:
            if self.host_bytes <= self.host_limit:
                break
            window_before = self._disk_pressure_until
            if not sb.spill_to_disk(best_effort=True) \
                    and self._disk_pressure_until > window_before:
                # the disk refused THIS write (full / over budget —
                # every refusal restamps the window, so a fresh
                # refusal strictly advances it): hammering the
                # remaining victims in this pass would fail the same
                # way. A False under a merely STALE window (lost
                # try-acquire, anti-churn bar) keeps going — the disk
                # may have healed, and only a new write attempt can
                # clear the window
                break

    # --- disk tier: budget, pressure, fault injection ---------------------

    def disk_pressure_active(self) -> bool:
        """True inside the sticky window after a disk write was
        refused (ENOSPC or budget). Self-heals: a later successful
        write clears it immediately, and the window expires on its
        own — a transiently full disk must not poison the manager
        forever."""
        return time.monotonic() < self._disk_pressure_until

    def _clear_disk_pressure(self) -> None:
        if self._disk_pressure_until:
            self._disk_pressure_until = 0.0

    def _note_disk_pressure(self, kind: str, path: str,
                            detail: str) -> None:
        """Classified record of a refused disk write: metric + flight
        ring + event-log line — and, for ``enospc``/``budget``, the
        sticky pressure window the degradation ladder's terminal rung
        consults (a query OOMing while the spill tier has nowhere to
        go is cancelled reason=budget instead of walking to a CPU
        fallback that could not spill either). A transient ``io``
        write error is evidence, not pressure: one flaky EIO must not
        pause eviction or flip ladder terminals for a disk that has
        room and is healthy again."""
        pressure = kind in ("enospc", "budget")
        if pressure:
            self._disk_pressure_until = \
                time.monotonic() + _DISK_PRESSURE_WINDOW_S
        _SPILL_WRITE_FAILURES.labels(kind).inc()
        # the flight event name matches the classification (the
        # anomaly detector keys on it): pressure fires the
        # disk_pressure anomaly, a transient io write error the
        # spill_failure one
        self._flight_mem(
            "disk_pressure" if pressure else "spill_write_failed",
            0, fail_kind=kind, path=path, detail=detail[:160])
        from .tools.event_log import log_spill_event
        try:
            # tpu-lint: allow[blocking-under-lock] classified-evidence append rides the (accepted) IO-under-state-lock spill design; best-effort
            log_spill_event(
                self.conf,
                "disk_pressure" if pressure else "spill_write_failed",
                kind=kind, path=path, detail=detail[:300])
        except Exception:  # noqa: BLE001 — evidence is best-effort
            pass

    def _note_spill_read_failure(self, e: "SpillReadError") -> None:
        """Metric + flight-ring evidence at the point of failure (the
        event-log line is written by get_host, outside the state
        lock)."""
        _SPILL_READ_FAILURES.labels(e.kind).inc()
        self._flight_mem("spill_read_failed", 0, fail_kind=e.kind,
                         path=e.path, detail=e.detail[:160])

    def _log_spill_read_failure(self, e: "SpillReadError") -> None:
        from .tools.event_log import log_spill_event
        try:
            log_spill_event(self.conf, "spill_read_failed",
                            kind=e.kind, path=e.path,
                            detail=e.detail[:300])
        except Exception:  # noqa: BLE001 — evidence is best-effort
            pass

    def _disk_budget_admit(self, fsize: int) -> bool:
        """Live-residency budget gate for one spill write: over-budget
        writes first evict the oldest unpinned disk entries back to
        host; a budget still breached after that is classified disk
        pressure and the write is refused (the batch stays on host).
        Admission RESERVES ``fsize`` in ``disk_in_use_bytes`` under
        the ledger lock — check-then-act would let two concurrent
        eviction cascades both pass the check and breach the limit
        together. The caller releases the reservation if the write
        does not commit (:meth:`SpillableBatch.spill_to_disk`)."""
        if not self.disk_limit:
            with self._lock:
                self.disk_in_use_bytes += fsize
            return True
        with self._lock:
            if self.disk_in_use_bytes + fsize <= self.disk_limit:
                self.disk_in_use_bytes += fsize
                return True
            over = self.disk_in_use_bytes + fsize - self.disk_limit
        self._evict_disk_to_host(over)
        with self._lock:
            if self.disk_in_use_bytes + fsize <= self.disk_limit:
                self.disk_in_use_bytes += fsize
                return True
        self._note_disk_pressure(
            "budget", self.spill_dir,
            f"disk spill residency {self.disk_in_use_bytes} + {fsize} "
            f"> limit {self.disk_limit}")
        return False

    def _evict_disk_to_host(self, need: int) -> int:
        """Promote the oldest unpinned disk entries back to the host
        tier until ``need`` disk bytes are freed (verified read-backs;
        files unlinked). Victim state locks are only try-acquired, and
        promoted batches are briefly barred from re-tiering so budget
        evictions can't ping-pong the same bytes."""
        with self._lock:
            victims = [sb for key, sb in self._catalog.items()
                       if sb.on_disk
                       and self._pin_counts.get(key, 0) <= 0]
        freed = 0
        for sb in victims:
            if freed >= need:
                break
            freed += sb._promote_to_host()
        if freed:
            self._sync_gauges()
            self._flight_mem("disk_evict", freed)
        return freed

    def _maybe_inject_disk_full(self) -> None:
        """spark.rapids.memory.test.injectDiskFull: the first N disk
        writes raise ENOSPC mid-write (after the payload bytes, before
        the commit) — exercising exactly the partial-file-cleanup path
        a really-full filesystem exercises."""
        if self._disk_full_countdown <= 0:
            return
        with self._lock:
            if self._disk_full_countdown <= 0:
                return
            self._disk_full_countdown -= 1
        import errno as _errno
        raise OSError(
            _errno.ENOSPC,
            "injected ENOSPC (spark.rapids.memory.test.injectDiskFull)")

    def _maybe_damage_spill_file(self, path: str, payload_len: int) -> None:
        """spark.rapids.memory.test.injectSpillFault: damage the
        COMMITTED spill file — 'corrupt' flips bytes mid-payload (the
        trailer stays intact, so only the CRC can catch it), 'torn'
        truncates into the trailer. The write-side mirror of the chaos
        grammar's post-commit shuffle damage."""
        if not self._spill_fault:
            return
        try:
            if self._spill_fault == "corrupt":
                at = max(0, min(payload_len // 2, payload_len - 8))
                with open(path, "r+b") as f:
                    f.seek(at)
                    chunk = f.read(8)
                    f.seek(at)
                    f.write(bytes(b ^ 0xFF for b in chunk))
            elif self._spill_fault == "torn":
                with open(path, "r+b") as f:
                    f.truncate(max(0, os.path.getsize(path) - 8))
        except OSError:
            pass

    def _select_victims(self, exclude: Optional[int] = None) \
            -> List[SpillableBatch]:
        """Pick LRU device->host spill victims. Called under the ledger
        lock; the spills themselves (device downloads) run OUTSIDE it via
        _spill_victims — holding the ledger lock across device IO both
        serialized unrelated tasks and inverted the lock order against
        get()/_charge (ADVICE r3 #1)."""
        victims: List[SpillableBatch] = []
        projected = self.device_bytes
        if projected <= self.budget:
            return victims
        for key, sb in self._catalog.items():
            if projected <= self.budget:
                break
            if key == exclude or self._pin_counts.get(key, 0) > 0:
                continue
            if sb.on_device:
                victims.append(sb)
                projected -= sb.nbytes
        return victims

    @staticmethod
    def _spill_victims(victims: List[SpillableBatch]):
        for v in victims:
            # best_effort: skip victims whose state lock is held by a
            # concurrent task (they are being used right now anyway)
            v.spill(cascade=False, best_effort=True)

    def _evict_to_fit(self, exclude: Optional[int] = None):
        """The eviction protocol: select under the ledger lock, spill
        outside it, cascade host->disk. Shared by register/_charge and
        direct pressure-relief callers."""
        with self._lock:
            victims = self._select_victims(exclude)
        self._spill_victims(victims)
        self._evict_host_to_disk(exclude=exclude)

    def transient_reservation(self, nbytes: int):
        """Context manager: ledger charge for short-lived device staging
        — the scan's encoded-blob upload while a fused decode dispatch
        is in flight. The blob is NOT spillable (it is consumed by the
        very next program), so it gets no catalog entry; but the bytes
        are real HBM occupancy, and without the charge eviction pressure
        and the flight-recorder HBM timeline under-count the scan by a
        whole staging arena per feeder thread. Charged across the
        device_put + dispatch; the XLA runtime owns the buffer after."""
        import contextlib

        @contextlib.contextmanager
        def _ctx():
            n = int(nbytes)
            with self._lock:
                self.device_bytes += n
            try:
                # inside the try: an eviction/spill failure here must
                # still release the charge below, or the ledger stays
                # inflated by a phantom blob for the session's lifetime
                self._evict_to_fit()
                self._sync_gauges()
                self._flight_mem("staging_reserve", n)
                yield
            finally:
                with self._lock:
                    self.device_bytes -= n
                self._sync_gauges()
                self._flight_mem("staging_release", n)
        return _ctx()

    def pin(self, sb: SpillableBatch):
        """Refcounted: a batch shared by several consumers (a broadcast
        feeding two joins) stays pinned until the LAST unpin."""
        with self._lock:
            self._pin_counts[id(sb)] = self._pin_counts.get(id(sb), 0) + 1

    def unpin(self, sb: SpillableBatch):
        with self._lock:
            c = self._pin_counts.get(id(sb), 0) - 1
            if c <= 0:
                self._pin_counts.pop(id(sb), None)
            else:
                self._pin_counts[id(sb)] = c

    # --- admission --------------------------------------------------------

    def task_slot(self, qctx=None):
        """Context manager gating concurrent device work — the
        GpuSemaphore seat behind the fair admission controller. With a
        ``QueryContext`` the wait is tenant-queued, weighted,
        deadline-bounded, and cancellable; without one it degrades to
        the legacy FIFO semantics."""
        return self.admission.slot(qctx)

    # --- forced spill (degradation-ladder `spill` rung) -------------------

    def spill_all_unpinned(self) -> int:
        """Spill every unpinned device-resident catalog entry to host
        (cascading host->disk), regardless of budget headroom — the
        ladder's pressure-relief rung. Returns bytes spilled. Victim
        state locks are only try-acquired (same hold-and-wait shield
        as eviction); busy batches are skipped."""
        with self._lock:
            victims = [sb for key, sb in self._catalog.items()
                       if sb.on_device
                       and self._pin_counts.get(key, 0) <= 0]
        freed = 0
        for sb in victims:
            before = sb.on_device
            sb.spill(cascade=False, best_effort=True)
            if before and not sb.on_device:
                freed += sb.nbytes
        self._evict_host_to_disk()
        self._flight_mem("forced_spill", freed)
        return freed

    # --- OOM retry --------------------------------------------------------

    def _maybe_inject_oom(self):
        if self._inject_after or self._inject_storm:
            with self._lock:
                self._op_count += 1
                n = self._op_count
            if self._inject_after and n == self._inject_after:
                raise TpuRetryOOM(
                    f"injected OOM at op {n} "
                    "(spark.rapids.sql.test.injectRetryOOM)")
            if self._inject_storm and n <= self._inject_storm:
                raise TpuRetryOOM(
                    f"injected OOM storm op {n}/{self._inject_storm} "
                    "(spark.rapids.sql.test.injectRetryOOM.storm)")

    def _check_query_budget(self, batch, qctx) -> None:
        """Per-query budget gate (lifecycle.py): the HBM occupancy this
        query is driving (process ledger + the batch in hand — per-query
        byte attribution doesn't exist below the ledger) must fit its
        budget. action=cancel classifies immediately; action=degrade
        raises the budget-flavored OOM into the ladder."""
        if qctx is None or not qctx.budget_bytes:
            return
        occupancy = self.device_bytes + batch.device_size_bytes()
        if occupancy <= qctx.budget_bytes:
            return
        detail = (f"query memory budget exceeded: {occupancy} > "
                  f"{qctx.budget_bytes} bytes")
        if qctx.budget_action == "cancel":
            qctx.token.cancel("budget", detail)
            raise qctx.token.error()
        raise QueryBudgetExceeded(detail)

    def with_retry(self, batch, fn: Callable, depth: int = 0,
                   qctx=None) -> List:
        """Run ``fn(batch) -> result`` with split-and-retry on device OOM:
        on failure the batch is halved and both halves processed
        sequentially (results concatenated as a list), recursively up to
        ``maxSplits`` (RmmRapidsRetryIterator.withRetry analog). With a
        ``QueryContext`` the per-query memory budget is enforced here
        and, once the halving budget is spent, the degradation ladder
        escalates: forced spill -> width-1 admission -> classified
        terminal (CPU-fallback OOM, or QueryCancelled(reason=budget)
        when the pressure was budget-driven).

        When ``oomRetry.blocking`` is on (default) the result is forced to
        completion inside the try: dispatch is async, so otherwise a real
        device RESOURCE_EXHAUSTED would surface at a later sync point
        outside any retry scope. Blocking is RISK-SCALED on total HBM
        occupancy (ledger bytes + this batch): when the device is far
        from the budget an OOM cannot plausibly happen, and a per-batch
        sync drains the dispatch stream (0.6 ms per dispatch+block round
        trip on the v5e, chip run of PR 21; what unconditional blocking
        costs a whole query there is not measured); near the budget the
        sync is cheap insurance."""
        try:
            self._maybe_inject_oom()
            self._check_query_budget(batch, qctx)
            out = fn(batch)
            if self._retry_enabled and self._retry_blocking \
                    and (self.device_bytes + batch.device_size_bytes()
                         > self.budget // 2):
                import jax
                jax.block_until_ready(out)
            return [out]
        except Exception as e:  # noqa: BLE001 — filtered below
            if not self._retry_enabled or not _is_oom_error(e):
                raise
            ladder = qctx.ladder if qctx is not None else None
            if depth < self.max_splits and batch.capacity >= 2:
                _MEM_OOM_RETRIES.inc()
                self._flight_mem("oom_retry", batch.device_size_bytes(),
                                 depth=depth)
                if ladder is not None:
                    ladder.note_halve()
                b1, b2 = split_batch(batch)
                out = self.with_retry(b1, fn, depth + 1, qctx)
                out.extend(self.with_retry(b2, fn, depth + 1, qctx))
                return out
            if ladder is None:
                # ladder-less contexts (cluster workers) still owe the
                # budget its classification: splits were this side's
                # whole ladder, so exhaustion under a budget-driven OOM
                # is QueryCancelled(budget) — the worker's .qcancel
                # marker carries it to the driver. Real device OOM
                # stays a retryable task failure.
                if isinstance(e, QueryBudgetExceeded) \
                        and qctx is not None:
                    qctx.token.cancel("budget", str(e))
                    raise qctx.token.error() from e
                raise
            return self._climb_ladder(batch, fn, depth, qctx, e)

    def _climb_ladder(self, batch, fn: Callable, depth: int, qctx,
                      cause: BaseException) -> List:
        """Halving budget spent: enter the next rung and retry (the
        retry's own failure re-enters here one rung higher — the walk
        terminates at ``cpu``)."""
        disk_starved = self.disk_pressure_active()
        rung = qctx.ladder.escalate(
            cause="disk_pressure" if disk_starved else "oom")
        if rung == "spill":
            self.spill_all_unpinned()
            return self.with_retry(batch, fn, depth, qctx)
        if rung == "width1":
            self.admission.await_exclusive(
                qctx, self.conf.get(LADDER_EXCLUSIVE_TIMEOUT))
            return self.with_retry(batch, fn, depth, qctx)
        # terminal rung: budget-driven pressure is a classified cancel
        # (CPU fallback can't honor a device budget that small any
        # better than the device path the user asked to bound). Disk
        # pressure terminates the same way: with the spill tier full,
        # neither forced spill nor a CPU island can relieve anything —
        # the resource budget (this time the disk's) is unsatisfiable.
        if isinstance(cause, QueryBudgetExceeded) or disk_starved:
            detail = str(cause)
            if disk_starved:
                detail = ("memory pressure with the disk spill tier "
                          "refusing writes (full disk or "
                          "spark.rapids.memory.disk.limit): " + detail)
            qctx.token.cancel("budget", detail)
            raise qctx.token.error() from cause
        exc = TpuRetryOOM(
            "degradation ladder exhausted (halve -> spill -> width1): "
            + str(cause))
        exc.ladder_exhausted = True
        raise exc from cause
