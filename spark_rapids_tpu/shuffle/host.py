"""Host Arrow-IPC shuffle transport — fallback-ladder rung 1.

TPU analog of the reference's host/file shuffle path with its
multithreaded codec writers (SURVEY.md §2.2-D "Cached writer/reader",
"Serialization/compression codecs", "Multithreaded shuffle mode",
§5.8 ladder rungs 1-2; reference mount empty — capability-built):
map batches are downloaded once (whole, with their partition-id lane),
split host-side, and written as compressed Arrow IPC files, one per
(map, partition); reads stream them back through the upload bridge.

Two modes behind one class, mirroring the reference's
`spark.rapids.shuffle.mode`:

- HOST          — synchronous serialize on the writer's thread.
- MULTITHREADED — a shared thread pool downloads/compresses map batches
  while the map side keeps producing; readers wait on the shuffle's
  outstanding writes (`spark.rapids.shuffle.multiThreaded.writer.threads`).

Compression codecs ride Arrow IPC's built-in buffer compression
(`spark.rapids.shuffle.compression.codec` = none | lz4 | zstd — the
codecs Arrow IPC defines; snappy is not an IPC codec and is rejected).
"""
from __future__ import annotations

import concurrent.futures
import errno
import logging
import os
import shutil
import tempfile
import threading
from typing import Dict, List, Optional

import numpy as np
import pyarrow as pa

from ..config import (RapidsConf, SHUFFLE_CLOSE_JOIN_TIMEOUT,
                      SHUFFLE_COMPRESSION, SHUFFLE_FETCH_MAX_RETRIES,
                      SHUFFLE_FETCH_RETRY_WAIT_MS, SHUFFLE_THREADS)
from ..columnar.batch import TpuBatch
from ..obs.metrics import REGISTRY as _METRICS
from ..obs.recorder import RECORDER as _FLIGHT
from . import integrity
from .transport import FetchFailure, ShuffleTransport, ShuffleWriteHandle

__all__ = ["HostShuffleTransport", "SHUF_PARTS_WRITTEN",
           "SHUF_BYTES_WRITTEN", "SHUF_PARTS_FETCHED",
           "SHUF_BYTES_FETCHED", "SHUF_FETCH_WAIT",
           "SHUF_FETCH_FAILURES"]

_LOG = logging.getLogger(__name__)

_IPC_CODECS = ("none", "lz4", "zstd")

# Live shuffle health, shared by every transport through a `transport`
# label (host = in-process file shuffle, process = the cluster's
# ProcessShuffleReadExec, ici = the device-mesh collective). The
# per-query TpuMetric surface is mined after the fact; these are
# scrapeable mid-query via obs.metrics.
SHUF_PARTS_WRITTEN = _METRICS.counter(
    "rapids_shuffle_partitions_written_total",
    "Shuffle partition files (or collective blocks) written.",
    ("transport",))
SHUF_BYTES_WRITTEN = _METRICS.counter(
    "rapids_shuffle_bytes_written_total",
    "Bytes of shuffle output written (serialized size).",
    ("transport",))
SHUF_PARTS_FETCHED = _METRICS.counter(
    "rapids_shuffle_partitions_fetched_total",
    "Shuffle partitions fetched by the read side.", ("transport",))
SHUF_BYTES_FETCHED = _METRICS.counter(
    "rapids_shuffle_bytes_fetched_total",
    "Bytes of shuffle input fetched (deserialized size).",
    ("transport",))
SHUF_FETCH_WAIT = _METRICS.histogram(
    "rapids_shuffle_fetch_wait_seconds",
    "Time the read side blocked waiting for shuffle data (file reads "
    "or collective realization).", ("transport",))
SHUF_FETCH_FAILURES = _METRICS.counter(
    "rapids_shuffle_fetch_failures_total",
    "Classified shuffle fetch failures by kind: missing (block or "
    "committed map output gone), corrupt (CRC mismatch), torn "
    "(malformed integrity footer/manifest), io (transient OSError "
    "that survived the in-place retries).", ("kind",))


class _HostWriter(ShuffleWriteHandle):
    def __init__(self, transport: "HostShuffleTransport", shuffle_id: int,
                 map_id: int, subdir: Optional[str] = None):
        self._t = transport
        self._sid = shuffle_id
        self._mid = map_id
        self._subdir = subdir

    def write(self, partition_id: int, batch: TpuBatch) -> None:
        self._t._submit(self._sid,
                        lambda: self._t._write_one(self._sid, self._mid,
                                                   partition_id, batch,
                                                   self._subdir))

    def write_unsplit(self, batch: TpuBatch, pids) -> None:
        self._t._submit(self._sid,
                        lambda: self._t._write_map_batch(
                            self._sid, self._mid, batch, pids,
                            self._subdir))


class HostShuffleTransport(ShuffleTransport):
    supports_unsplit = True

    def __init__(self, conf: Optional[RapidsConf] = None,
                 threads: Optional[int] = None,
                 root: Optional[str] = None):
        conf = conf or RapidsConf()
        self.codec = conf.get(SHUFFLE_COMPRESSION)
        if self.codec not in _IPC_CODECS:
            raise ValueError(
                f"unsupported host-shuffle codec {self.codec!r}; Arrow "
                f"IPC supports {_IPC_CODECS}")
        self._conf = conf
        if threads is None:
            threads = conf.get(SHUFFLE_THREADS)
        self._pool = concurrent.futures.ThreadPoolExecutor(
            max_workers=threads, thread_name_prefix="shuffle-write") \
            if threads > 0 else None
        # backpressure: an unbounded queue would pin every pending map
        # batch's device buffers in HBM; the producer blocks once 2x the
        # pool is outstanding
        self._slots = threading.BoundedSemaphore(threads * 2) \
            if threads > 0 else None
        self.root = root or tempfile.mkdtemp(prefix="rapids_tpu_shuffle_")
        self._own_root = root is None
        self._futures: Dict[int, List] = {}
        self._schemas: Dict[int, object] = {}
        # sticky per-shuffle writer error: a failed async write must
        # surface on EVERY subsequent read of that shuffle, not just the
        # one that happened to drain the failed future
        self._failed: Dict[int, BaseException] = {}
        # per-staging-dir (size, crc) entries for the commit manifest
        self._manifests: Dict[str, Dict[str, Dict]] = {}
        # free AQE stats: per-partition decoded byte counts recorded at
        # WRITE time — the writer already downloaded and split the map
        # batch, so the numbers cost nothing and partition_stats can
        # serve them without ever touching device memory
        self._nparts: Dict[int, int] = {}
        self._pstats: Dict[int, Dict[int, int]] = {}
        self._fetch_retries = conf.get(SHUFFLE_FETCH_MAX_RETRIES)
        self._fetch_wait_s = conf.get(SHUFFLE_FETCH_RETRY_WAIT_MS) / 1e3
        self._lock = threading.Lock()

    # --- write side -------------------------------------------------------

    def _ipc_options(self):
        codec = None if self.codec == "none" else self.codec
        return pa.ipc.IpcWriteOptions(compression=codec)

    def _sdir(self, shuffle_id: int) -> str:
        return os.path.join(self.root, f"s{shuffle_id}")

    def _path(self, sid: int, mid: int, pid: int,
              subdir: Optional[str] = None) -> str:
        d = subdir if subdir is not None else self._sdir(sid)
        return os.path.join(d, f"m{mid:05d}_p{pid}.arrow")

    def _submit(self, sid: int, fn):
        if self._pool is None:
            fn()
            return
        self._slots.acquire()

        def run():
            try:
                fn()
            finally:
                self._slots.release()
        with self._lock:
            self._futures.setdefault(sid, []).append(self._pool.submit(run))

    def _write_rb(self, sid: int, mid: int, pid: int,
                  rb: pa.RecordBatch,
                  subdir: Optional[str] = None) -> None:
        path = self._path(sid, mid, pid, subdir)
        sink = pa.BufferOutputStream()
        with pa.ipc.new_file(sink, rb.schema,
                             options=self._ipc_options()) as w:
            w.write_batch(rb)
        size, crc = integrity.write_block(path,
                                          sink.getvalue().to_pybytes())
        with self._lock:
            # "raw" (decoded bytes) rides the manifest so a FRESH
            # transport over an existing root can rebuild partition
            # stats from committed manifests alone
            self._manifests.setdefault(os.path.dirname(path), {})[
                os.path.basename(path)] = {"size": size, "crc": crc,
                                           "raw": int(rb.nbytes)}
            if subdir is None:
                # direct (non-attempt) writes are immediately visible to
                # readers, so they credit the stats now; attempt-staged
                # writes credit at COMMIT — an in-flight speculative
                # duplicate must never transiently double-count a
                # partition for a concurrent AQE stats read
                ps = self._pstats.setdefault(sid, {})
                ps[pid] = ps.get(pid, 0) + int(rb.nbytes)
        SHUF_PARTS_WRITTEN.labels("host").inc()
        SHUF_BYTES_WRITTEN.labels("host").inc(rb.nbytes)

    def _write_one(self, sid: int, mid: int, pid: int,
                   batch: TpuBatch, subdir: Optional[str] = None) -> None:
        from ..columnar.arrow_bridge import device_to_arrow
        rb = device_to_arrow(batch)  # compacts lazy selections
        with self._lock:
            self._schemas.setdefault(sid, batch.schema)
        if rb.num_rows:
            self._write_rb(sid, mid, pid, rb, subdir)

    def _write_map_batch(self, sid: int, mid: int, batch: TpuBatch,
                         pids, subdir: Optional[str] = None) -> None:
        """ONE download for the whole map batch: the pid lane rides as an
        extra column (so download compaction keeps alignment), then the
        host split is a numpy take per partition."""
        import jax.numpy as jnp
        from .. import datatypes as dt
        from ..columnar.arrow_bridge import device_to_arrow
        from ..columnar.column import TpuColumnVector
        ext_schema = dt.Schema(
            list(batch.schema.fields)
            + [dt.StructField("__pid__", dt.INT32, False)])
        pidcol = TpuColumnVector(
            dt.INT32, data=pids.astype(jnp.int32),
            validity=jnp.ones((batch.capacity,), jnp.bool_))
        ext = TpuBatch(list(batch.columns) + [pidcol], ext_schema,
                       batch.row_count, selection=batch.selection)
        rb = device_to_arrow(ext)
        with self._lock:
            self._schemas.setdefault(sid, batch.schema)
        from ..columnar.arrow_bridge import arrow_schema
        pid_np = np.asarray(rb.column(rb.num_columns - 1))
        core = pa.RecordBatch.from_arrays(
            [rb.column(i) for i in range(rb.num_columns - 1)],
            schema=arrow_schema(batch.schema))
        for p in np.unique(pid_np):
            idx = np.nonzero(pid_np == p)[0]
            part = core.take(pa.array(idx, pa.int64()))
            self._write_rb(sid, mid, int(p), part, subdir)

    # --- task-attempt commit protocol --------------------------------------
    #
    # Retried/speculated map tasks need atomic, all-or-nothing output:
    # a zombie attempt must never interleave its partition files with
    # the winner's. Each attempt writes into a private staging dir and
    # commits with ONE os.rename onto `<task>.mapout`; POSIX rename
    # fails when the destination exists non-empty, so exactly one
    # attempt wins and the loser's files vanish (Spark's
    # shuffle-output-coordinator / v1 commit-protocol analog).

    def begin_task_attempt(self, shuffle_id: int, task_key: str,
                           attempt: int) -> str:
        """Create and return this attempt's private staging dir."""
        d = os.path.join(self._sdir(shuffle_id),
                         f"{task_key}.a{attempt}.staging")
        os.makedirs(d, exist_ok=True)
        # POSIX rename() succeeds onto an existing EMPTY directory, so a
        # zero-row map output would let a zombie sibling "win" a second
        # time — a sentinel keeps a committed .mapout non-empty (readers
        # only list *_p<N>.arrow, so it is invisible to them)
        with open(os.path.join(d, ".attempt"), "w") as f:
            f.write(f"{task_key} a{attempt}")
        return d

    def _credit_stats(self, shuffle_id: int, entries: Dict) -> None:
        """Fold a COMMITTED attempt's per-partition byte counts into
        the writer-side stats (staged writes defer to here, so losing
        and aborted attempts never touch the stats at all)."""
        if not entries:
            return
        with self._lock:
            ps = self._pstats.setdefault(shuffle_id, {})
            for name, meta in entries.items():
                m = integrity._PID_RE.search(name)
                if m is None:
                    continue
                pid = int(m.group(1))
                ps[pid] = ps.get(pid, 0) + int((meta or {}).get("raw", 0))

    def commit_task_attempt(self, shuffle_id: int, task_key: str,
                            attempt: int) -> bool:
        """Atomically publish the attempt's output; False = a sibling
        attempt already committed (this attempt was a zombie/loser and
        its staging dir has been discarded)."""
        self._drain(shuffle_id)  # settle any outstanding pool writes
        staging = os.path.join(self._sdir(shuffle_id),
                               f"{task_key}.a{attempt}.staging")
        final = os.path.join(self._sdir(shuffle_id), f"{task_key}.mapout")
        # the manifest (expected files + sizes + crcs) commits with the
        # SAME rename that publishes the files: readers can then prove a
        # block is missing, not just corrupt
        with self._lock:
            entries = self._manifests.pop(staging, {})
        try:
            integrity.write_manifest(staging, task_key, attempt, entries)
        except OSError:
            pass  # staging already gone: the rename below settles it
        try:
            os.rename(staging, final)
            self._credit_stats(shuffle_id, entries)
            return True
        except OSError as e:
            # lost the race (destination committed by a sibling) or the
            # driver already aborted this attempt (staging gone) — any
            # other rename failure is real data loss, not a lost race;
            # the loser never credited the stats, so nothing to undo
            if e.errno in (errno.EEXIST, errno.ENOTEMPTY) \
                    or not os.path.exists(staging):
                shutil.rmtree(staging, ignore_errors=True)
                return False
            raise

    def abort_task_attempt(self, shuffle_id: int, task_key: str,
                           attempt: int) -> None:
        staging = os.path.join(self._sdir(shuffle_id),
                               f"{task_key}.a{attempt}.staging")
        with self._lock:
            self._manifests.pop(staging, None)
        shutil.rmtree(staging, ignore_errors=True)

    @staticmethod
    def committed_partition_files(sdir: str, partition_id: int):
        """Paths of one partition's blocks: legacy flat files plus
        every committed attempt dir's manifest-listed files — staging
        dirs are invisible by construction. Thin path-only view over
        ``integrity.expected_partition_files`` so there is exactly ONE
        definition of "a committed block"."""
        return [p for p, _ in integrity.expected_partition_files(
            sdir, partition_id)]

    # --- transport interface ----------------------------------------------

    def register_shuffle(self, shuffle_id: int, num_partitions: int):
        os.makedirs(self._sdir(shuffle_id), exist_ok=True)
        with self._lock:
            self._nparts[shuffle_id] = num_partitions

    # --- free AQE statistics ----------------------------------------------

    def partition_stats(self, shuffle_id: int, free_only: bool = False):
        """Approximate decoded bytes per partition, recorded at WRITE
        time (the writer downloads and splits every map batch anyway,
        so the counts are free) — valid under free_only: serving them
        touches no device memory and issues no device sync, so
        adaptive coalesce/skew never stalls the dispatch stream.
        A transport instance that did not write the shuffle (separate
        process over a shared root) rebuilds the counts from the
        committed manifests' ``raw`` entries."""
        self._drain(shuffle_id)  # writer-side counts must be settled
        with self._lock:
            n = self._nparts.get(shuffle_id)
            ps = dict(self._pstats.get(shuffle_id, {}))
        if not ps:
            idx = integrity.expected_partition_index(
                self._sdir(shuffle_id), shuffle_id=shuffle_id)
            for pid, blocks in idx.items():
                for _, meta in blocks:
                    if not meta or "raw" not in meta:
                        # a legacy/direct-write block with no recorded
                        # byte count: partial stats would misreport its
                        # partition as empty and mis-plan coalescing —
                        # withhold rather than mislead
                        return None
                ps[pid] = sum(meta["raw"] for _, meta in blocks)
            if not any(ps.values()):
                return None  # nothing written: no stats
        if n is None:
            n = max(ps) + 1 if ps else 0
        return [int(ps.get(p, 0)) for p in range(n)]

    def stage_bytes(self, shuffle_id: int):
        """Stage size from the same write-time counts — the AQE
        join-strategy switch's input; no device sync. None when this
        instance has no record of the shuffle."""
        stats = self.partition_stats(shuffle_id, free_only=True)
        return sum(stats) if stats is not None else None

    def writer(self, shuffle_id: int, map_id: int,
               subdir: Optional[str] = None) -> ShuffleWriteHandle:
        return _HostWriter(self, shuffle_id, map_id, subdir)

    def _drain(self, sid: int):
        """Settle outstanding pool writes for one shuffle. A writer
        error is STICKY: every future is drained (not just up to the
        first failure), the first error is remembered per shuffle, and
        every subsequent drain — each read_partition, every commit —
        re-raises it. Popping the futures list used to deliver the
        error to exactly one reader and let later partitions silently
        read partial data."""
        with self._lock:
            futs = self._futures.pop(sid, [])
        first: Optional[BaseException] = None
        for f in futs:
            try:
                # tpu-lint: allow[blocking-call-in-thread] drain must settle EVERY outstanding write; close() bounds a wedged writer separately
                f.result()
            except BaseException as e:  # noqa: BLE001 — writer errors
                if first is None:      # of any type must reach readers
                    first = e
        if first is not None:
            with self._lock:
                self._failed.setdefault(sid, first)
        with self._lock:
            err = self._failed.get(sid)
        if err is not None:
            raise RuntimeError(
                f"shuffle {sid} had a failed async write; its output "
                f"is incomplete") from err

    @staticmethod
    def _record_fetch_failure(ff: FetchFailure, partition_id: int,
                              transport: str = "host") -> None:
        from .transport import record_fetch_failure
        record_fetch_failure(ff, partition_id, transport)

    def read_partition(self, shuffle_id: int, partition_id: int):
        import time as _time
        from ..columnar.arrow_bridge import arrow_to_device
        from ..pipeline import pipelined_map
        t0 = _time.perf_counter()
        self._drain(shuffle_id)  # the multithreaded-writer wait
        schema = self._schemas.get(shuffle_id)
        try:
            blocks = integrity.expected_partition_files(
                self._sdir(shuffle_id), partition_id,
                shuffle_id=shuffle_id)
        except FetchFailure as ff:
            self._record_fetch_failure(ff, partition_id)
            raise
        drain_s = _time.perf_counter() - t0
        SHUF_FETCH_WAIT.labels("host").observe(drain_s)
        SHUF_PARTS_FETCHED.labels("host").inc()
        # flight-recorder tap: the read side's writer-drain wait is the
        # shuffle stall an incident bundle wants on its timeline
        _FLIGHT.record("shuffle", ev="drain_wait", sid=int(shuffle_id),
                       part=int(partition_id), wait_s=round(drain_s, 6))

        from ..memory import DeviceMemoryManager
        mgr = DeviceMemoryManager.shared(self._conf)
        inflight = set()  # ledger entries not yet handed to the consumer
        ilock = threading.Lock()
        closed = [False]

        def load(block):
            path, meta = block
            try:
                payload = integrity.read_block(
                    path, meta, shuffle_id=shuffle_id,
                    max_retries=self._fetch_retries,
                    retry_wait_s=self._fetch_wait_s,
                    on_retry=lambda n, e: _FLIGHT.record(
                        "shuffle", ev="fetch_retry", sid=int(shuffle_id),
                        part=int(partition_id), n=n, error=str(e)[:120]))
            except FetchFailure as ff:
                self._record_fetch_failure(ff, partition_id)
                raise
            table = pa.ipc.open_file(pa.BufferReader(payload)).read_all()
            batches = [arrow_to_device(rb, schema)
                       for rb in table.combine_chunks().to_batches()
                       if rb.num_rows]
            # in-flight uploads are ledger-visible until delivered, like
            # the scan's upload tunnel (eviction pressure must see them).
            # Registered one by one with a partial-release guard: a
            # raising registration (eviction runs disk IO) must not
            # strand the earlier, already-pinned entries in the
            # process-shared catalog [ledger-leak-path]
            sbs = []
            try:
                for b in batches:
                    sbs.append(mgr.register(b, pinned=True))
            except BaseException:
                for sb in sbs:
                    sb.release()
                raise
            with ilock:
                if closed[0]:
                    for sb in sbs:
                        sb.release()
                    return table.nbytes, batches, []
                inflight.update(sbs)
            return table.nbytes, batches, sbs

        # fetch->upload overlap, same shape as the scan's upload tunnel:
        # file N+1 is read, decompressed, and uploaded on a feeder
        # thread while the consumer computes on N's batches; the window
        # bounds in-flight (uploaded, unconsumed) partition files — one
        # RecordBatch per file by the writer's construction.
        gen = pipelined_map(load, blocks, threads=1, window=2)
        try:
            while True:
                t1 = _time.perf_counter()
                try:
                    nbytes, batches, sbs = next(gen)
                except StopIteration:
                    break
                SHUF_FETCH_WAIT.labels("host").observe(
                    _time.perf_counter() - t1)
                SHUF_BYTES_FETCHED.labels("host").inc(nbytes)
                with ilock:
                    inflight.difference_update(sbs)
                for sb in sbs:
                    sb.release()  # the consumer owns them now
                yield from batches
        finally:
            gen.close()
            with ilock:
                closed[0] = True
                leftovers = list(inflight)
                inflight.clear()
            for sb in leftovers:
                sb.release()

    def unregister_shuffle(self, shuffle_id: int):
        """Cleanup-safe: the shuffle dir and bookkeeping are released
        even when a writer failed — THEN the sticky error is re-raised
        so a caller tearing down after a silent async failure still
        hears about it (and cannot leak the dir by raising early)."""
        err: Optional[BaseException] = None
        try:
            self._drain(shuffle_id)
        except BaseException as e:  # noqa: BLE001 — re-raised below
            err = e
        sdir = self._sdir(shuffle_id)
        with self._lock:
            self._schemas.pop(shuffle_id, None)
            self._failed.pop(shuffle_id, None)
            self._nparts.pop(shuffle_id, None)
            self._pstats.pop(shuffle_id, None)
            for d in [d for d in self._manifests
                      if d == sdir or d.startswith(sdir + os.sep)]:
                del self._manifests[d]
        shutil.rmtree(sdir, ignore_errors=True)
        if err is not None:
            raise err

    def close(self):
        """Bounded teardown: a wedged writer thread (stuck codec /
        filesystem call) must not hang close() forever behind
        ``shutdown(wait=True)`` — wait up to
        ``spark.rapids.shuffle.close.joinTimeout`` for outstanding
        writes, then abandon them with a log line."""
        join_s = self._conf.get(SHUFFLE_CLOSE_JOIN_TIMEOUT)
        if self._pool is not None:
            with self._lock:
                futs = [f for fs in self._futures.values() for f in fs]
                self._futures.clear()
            self._pool.shutdown(wait=False)
            if futs:
                _, pending = concurrent.futures.wait(
                    futs, timeout=join_s)
                if pending:
                    _LOG.warning(
                        "HostShuffleTransport.close: abandoning %d "
                        "outstanding shuffle write(s) still running "
                        "after %.0fs", len(pending), join_s)
                    # keep interpreter exit from joining the wedged
                    # threads too (the atexit hook would re-hang there)
                    try:
                        from concurrent.futures import thread as _cft
                        for t in getattr(self._pool, "_threads", ()):
                            _cft._threads_queues.pop(t, None)
                    except Exception:  # noqa: BLE001 — best effort
                        pass
        if self._own_root:
            shutil.rmtree(self.root, ignore_errors=True)
