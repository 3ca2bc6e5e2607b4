"""The read schedule of the device-decode Parquet scan
(``io/scan.py::planned``): row groups are FETCHED wide on the reader pool
(I/O, in task order, at most ``numThreads + prefetchBatches`` ahead) and
WALKED one at a time, in task order, on the feeders' source thread. The
fetch and the walk are wrapped here to record when and where each ran."""
import os
import threading
import time

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_tpu.columnar import device_to_arrow
from spark_rapids_tpu.config import RapidsConf
from spark_rapids_tpu.exec.base import ExecCtx
from spark_rapids_tpu.io import TpuFileScanExec

THREADS = "spark.rapids.sql.format.parquet.multiThreadedRead.numThreads"
PREFETCH = "spark.rapids.sql.scan.prefetchBatches"
ONE_DISPATCH_A_ROW_GROUP = {"spark.rapids.sql.scan.coalesceTargetBytes": "0"}


def _conf(**more):
    return RapidsConf(dict(ONE_DISPATCH_A_ROW_GROUP, **more))


def _ints(tmp_path, files=2, rows=4000, row_group=1000, seed=3):
    """``files`` x ``rows / row_group`` row groups of two plain columns."""
    rng = np.random.default_rng(seed)
    paths = []
    for i in range(files):
        t = pa.table({
            "k": pa.array(rng.integers(0, 1 << 40, rows)),
            "x": pa.array(rng.uniform(0, 1, rows))})
        paths.append(os.path.join(str(tmp_path), f"t-{i:02d}.parquet"))
        pq.write_table(t, paths[-1], row_group_size=row_group)
    return paths


class _Recorder:
    """Wraps the scan's fetch and walk: ``(kind, file, rg, thread, t0,
    t1)`` per call, ``t1`` None while it runs; either may sleep first or
    raise at one row group."""

    def __init__(self, monkeypatch, fetch_sleep=0.0, walk_sleep=0.0,
                 fail=None):
        self.calls = []
        self.lock = threading.Lock()
        real_fetch = TpuFileScanExec._fetch_row_group
        real_walk = TpuFileScanExec._plan_row_group
        rec = self

        def around(kind, sleep, path, g, call):
            entry = [kind, os.path.basename(path), g,
                     threading.current_thread().name, time.monotonic(),
                     None]
            with rec.lock:
                rec.calls.append(entry)
            try:
                if sleep:
                    time.sleep(sleep)
                if fail == (kind, entry[1], g):
                    raise OSError(f"{kind} of {entry[1]}:{g} failed")
                return call()
            finally:
                entry[5] = time.monotonic()

        def fetch(self, path, g):
            return around("fetch", fetch_sleep, path, g,
                          lambda: real_fetch(self, path, g))

        def walk(self, path, g, fetched):
            return around("walk", walk_sleep, path, g,
                          lambda: real_walk(self, path, g, fetched))

        monkeypatch.setattr(TpuFileScanExec, "_fetch_row_group", fetch)
        monkeypatch.setattr(TpuFileScanExec, "_plan_row_group", walk)

    def of(self, kind):
        with self.lock:
            return [tuple(c) for c in self.calls if c[0] == kind]

    def running(self):
        with self.lock:
            return [tuple(c) for c in self.calls if c[5] is None]


def _most_at_once(calls):
    edges = sorted([(c[4], 1) for c in calls] + [(c[5], -1) for c in calls],
                   key=lambda e: (e[0], e[1]))
    most = now = 0
    for _, step in edges:
        now += step
        most = max(most, now)
    return most


def _in_task_order(calls, paths, row_groups):
    want = [(os.path.basename(p), g) for p in paths
            for g in range(row_groups)]
    return [(c[1], c[2]) for c in calls] == want[:len(calls)]


def _scan_metric(ctx, name):
    return sum(m[name].value for m in ctx.metrics.values() if name in m)


def _no_scan_thread_left(timeout=10.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        left = [t.name for t in threading.enumerate() if t.is_alive()
                and t.name.startswith(("scan-fetch", "scan-upload"))]
        if not left:
            return True
        time.sleep(0.02)
    return False


# (a) the walks: one at a time, in task order, on one thread, and only as
# far ahead as the consumer has taken
@pytest.mark.parametrize("upload_threads", [3, 0])
def test_walks_run_in_task_order_one_at_a_time(tmp_path, monkeypatch,
                                               upload_threads):
    paths = _ints(tmp_path)  # 2 files x 4 row groups
    rec = _Recorder(monkeypatch)
    conf = _conf(**{"spark.rapids.sql.scan.uploadThreads":
                    str(upload_threads)})
    n = sum(b.num_rows for b in TpuFileScanExec(
        paths, conf=conf).execute(ExecCtx(conf)))
    assert n == 8000
    walks, fetches = rec.of("walk"), rec.of("fetch")
    assert len(walks) == len(fetches) == 8
    assert _in_task_order(walks, paths, 4)
    assert _in_task_order(fetches, paths, 4)  # submitted in task order
    assert _most_at_once(walks) == 1
    assert all(a[5] <= b[4] for a, b in zip(walks, walks[1:]))
    assert len({w[3] for w in walks}) == 1
    assert all(f[3].startswith("scan-fetch") for f in fetches)
    assert not {w[3] for w in walks} & {f[3] for f in fetches}


def test_a_consumer_that_stops_after_one_item_saw_at_most_two_walks(
        tmp_path, monkeypatch):
    # uploadThreads 0: the consumer's own thread pulls planned(), so
    # what it has not asked for has not been walked
    paths = _ints(tmp_path)
    rec = _Recorder(monkeypatch)
    conf = _conf(**{"spark.rapids.sql.scan.uploadThreads": "0"})
    gen = TpuFileScanExec(paths, conf=conf).execute(ExecCtx(conf))
    assert next(gen).num_rows == 1000
    gen.close()
    assert 1 <= len(rec.of("walk")) <= 2
    assert _in_task_order(rec.of("walk"), paths, 4)
    assert _no_scan_thread_left()


# ... and a coalesced group is handed on as soon as the NEXT row group's
# row count (its footer is in) says it cannot join: a walk earlier
def test_a_full_group_is_handed_on_before_the_next_walk():
    scan = TpuFileScanExec.__new__(TpuFileScanExec)
    item = lambda n: (n, {}, None, None, ())  # noqa: E731
    rows = [400, 400, 400, 400, 300]
    pulled = []

    def planned(hints):
        for i, n in enumerate(rows):
            pulled.append(i)
            nxt = rows[i + 1] if hints and i + 1 < len(rows) else None
            yield item(n), nxt

    def run(hints):
        del pulled[:]
        return [([it[0] for it in group], len(pulled)) for group in
                scan._coalesced_groups(planned(hints), 1 << 30, 1000)]

    # the groups are the same; with the hint each is out before the
    # row group that does not fit has been pulled (walked)
    assert run(hints=False) == [([400, 400], 3), ([400, 400], 5),
                                ([300], 5)]
    assert run(hints=True) == [([400, 400], 2), ([400, 400], 4),
                               ([300], 5)]


def test_the_first_dispatch_does_not_wait_for_the_walk_behind_its_group(
        tmp_path, monkeypatch):
    paths = _ints(tmp_path)  # 8 row groups of 1000 rows
    rec = _Recorder(monkeypatch, walk_sleep=0.02)  # the fetches get ahead
    conf = RapidsConf({"spark.rapids.sql.batchSizeRows": "2500",
                       "spark.rapids.sql.scan.uploadThreads": "0"})
    gen = TpuFileScanExec(paths, conf=conf).execute(ExecCtx(conf))
    assert next(gen).num_rows == 2000  # two row groups fit, a third not
    assert len(rec.of("walk")) == 2
    assert [b.num_rows for b in gen] == [2000, 2000, 2000]


# (b) the same table, byte for byte, whatever the pool's width
def _nulls_later(tmp_path):
    """No null in the first two row groups, then nulls in ``a``, then in
    ``b`` too: the ``seen_nulls`` flags rise in task order."""
    rng = np.random.default_rng(5)
    n, rg = 4000, 1000
    a = rng.integers(0, 50, n).astype(np.int32)
    b = rng.uniform(0, 1, n)
    row = np.arange(n)
    t = pa.table({
        "a": pa.array(a, mask=(row >= 2 * rg) & (row % 7 == 0)),
        "b": pa.array(b, mask=(row >= 3 * rg) & (row % 5 == 0))})
    p = os.path.join(str(tmp_path), "nulls.parquet")
    pq.write_table(t, p, row_group_size=rg)
    return [p], {}


def _strings(tmp_path):
    rng = np.random.default_rng(6)
    paths = []
    for i in range(2):
        n = 3000
        t = pa.table({
            "s": pa.array([f"brand-{v}" if v % 11 else None
                           for v in rng.integers(0, 400, n)]),
            "u": pa.array([f"row {i} {j} " + "x" * int(w) for j, w in
                           enumerate(rng.integers(0, 9, n))]),
            "v": pa.array(rng.integers(0, 9, n).astype(np.int64))})
        paths.append(os.path.join(str(tmp_path), f"s-{i}.parquet"))
        pq.write_table(t, paths[-1], row_group_size=1000,
                       use_dictionary=["s"])
    return paths, {}


def _host_fallback(tmp_path):
    """``z`` is written under a codec outside the decoder's envelope and
    ``l`` is nested: both are read by pyarrow in the walk, and neither
    is fetched."""
    rng = np.random.default_rng(8)
    n = 4000
    t = pa.table({
        "a": pa.array(rng.integers(0, 1 << 30, n)),
        "z": pa.array(rng.uniform(0, 1, n)),
        "l": pa.array([[int(v), int(v) + 1] for v in
                       rng.integers(0, 9, n)], pa.list_(pa.int64()))})
    p = os.path.join(str(tmp_path), "fb.parquet")
    pq.write_table(t, p, row_group_size=1000,
                   compression={"a": "snappy", "z": "lz4", "l": "snappy"})
    return [p], {}


def _sliced(tmp_path):
    return _ints(tmp_path, files=3, rows=4000, row_group=1000), \
        {"slice": (1, 4)}


def _expected(paths, slice_=None):
    """pyarrow's own read of the scan's row groups, in task order."""
    tasks = [(p, g) for p in paths
             for g in range(pq.ParquetFile(p).metadata.num_row_groups)]
    if slice_:
        k, n = slice_
        tasks = tasks[k * len(tasks) // n:(k + 1) * len(tasks) // n]
    return [pq.ParquetFile(p).read_row_group(g) for p, g in tasks]


def _collect(paths, conf, slice_=None):
    scan = TpuFileScanExec(paths, conf=conf)
    if slice_:
        scan = scan.sliced(*slice_)
    ctx = ExecCtx(conf)
    return [device_to_arrow(b) for b in scan.execute(ctx)], ctx


def _ipc_bytes(batches):
    sink = pa.BufferOutputStream()
    with pa.ipc.new_stream(sink, batches[0].schema) as w:
        for rb in batches:
            w.write_batch(rb)
    return sink.getvalue().to_pybytes()


@pytest.mark.parametrize("make", [_nulls_later, _strings, _host_fallback,
                                  _sliced], ids=lambda f: f.__name__[1:])
def test_batches_are_the_same_bytes_at_any_pool_width(tmp_path, monkeypatch,
                                                      make):
    paths, opts = make(tmp_path)
    slice_ = opts.get("slice")
    want = _expected(paths, slice_)
    rec = _Recorder(monkeypatch)
    streams = {}
    for threads in ("1", "8", None):
        conf = _conf(**({THREADS: threads} if threads else {}))
        got, ctx = _collect(paths, conf, slice_)
        # one batch a row group, in task order, each what the file holds
        assert [rb.num_rows for rb in got] == [t.num_rows for t in want]
        for rb, t in zip(got, want):
            assert pa.Table.from_batches([rb]).equals(
                t.cast(rb.schema).combine_chunks())
        streams[threads] = _ipc_bytes(got)
        if make is _nulls_later:
            # the flags rose in task order: the same variants every time
            assert _scan_metric(ctx, "nullFreeChunks") == 2 + 2 + 1 + 0
        if make is _host_fallback:
            assert _scan_metric(ctx, "fallbackChunks") == 2 * len(want)
            assert _scan_metric(ctx, "deviceChunks") == len(want)
    assert streams["1"] == streams["8"] == streams[None]
    assert _most_at_once(rec.of("walk")) == 1
    assert len(rec.of("walk")) == len(rec.of("fetch")) == 3 * len(want)


def test_chunks_the_footer_sends_to_the_host_are_not_fetched(tmp_path):
    (path,), _ = _host_fallback(tmp_path)
    scan = TpuFileScanExec([path], conf=_conf())
    fetched = scan._fetch_row_group(path, 1)
    rg = pq.ParquetFile(path).metadata.row_group(1)
    assert fetched.nbytes == rg.column(0).total_compressed_size
    n_rows, plans, host_rb, _, reasons = scan._plan_row_group(path, 1,
                                                              fetched)
    assert n_rows == 1000 and list(plans) == ["a"]
    assert host_rb.schema.names == ["z", "l"]
    assert sorted(reasons) == ["codec", "nested"]


# (c) storage that waits: the fetches overlap up to the conf's count, the
# walks still never do
def test_slow_fetches_overlap_and_the_walks_do_not(tmp_path, monkeypatch):
    paths = _ints(tmp_path, files=3)  # 12 row groups
    rec = _Recorder(monkeypatch, fetch_sleep=0.05, walk_sleep=0.01)
    conf = _conf(**{THREADS: "4", PREFETCH: "2"})
    ctx = ExecCtx(conf)
    n = sum(b.num_rows for b in TpuFileScanExec(
        paths, conf=conf).execute(ctx))
    assert n == 12_000
    fetches, walks = rec.of("fetch"), rec.of("walk")
    assert 2 <= _most_at_once(fetches) <= 4
    assert _most_at_once(walks) == 1
    assert _in_task_order(walks, paths, 4)
    assert 0 < _scan_metric(ctx, "fetchAheadMax") <= 4 + 2
    # fetchTime is the fetches' own seconds, summed over the pool
    assert _scan_metric(ctx, "fetchTime") >= 12 * 0.05
    # a walk never started before its own fetch had ended
    end_of = {(f[1], f[2]): f[5] for f in fetches}
    assert all(end_of[(w[1], w[2])] <= w[4] for w in walks)


def test_fetches_run_at_most_depth_ahead_of_the_walk(tmp_path, monkeypatch):
    paths = _ints(tmp_path, files=3)
    rec = _Recorder(monkeypatch, walk_sleep=0.03)
    conf = _conf(**{THREADS: "2", PREFETCH: "1"})
    ctx = ExecCtx(conf)
    assert sum(b.num_rows for b in TpuFileScanExec(
        paths, conf=conf).execute(ctx)) == 12_000
    walks, fetches = rec.of("walk"), rec.of("fetch")
    for i, w in enumerate(walks):
        # fetched or in flight, this one's own fetch among them, when
        # walk i began: at most depth beyond the i already walked
        started = sum(f[4] <= w[4] for f in fetches)
        assert started <= i + 1 + 3, (i, started)
    # fetching never limits: every slot but the one just submitted is in
    assert 2 <= _scan_metric(ctx, "fetchAheadMax") <= 3


# (d) a consumer that leaves after one batch leaves nothing running
def test_early_close_leaves_no_fetch_no_walk_and_no_pool(tmp_path,
                                                         monkeypatch):
    paths = _ints(tmp_path, files=4)  # 16 row groups
    rec = _Recorder(monkeypatch, fetch_sleep=0.02, walk_sleep=0.02)
    conf = _conf(**{"spark.rapids.sql.scan.inFlightBatches": "1"})
    gen = TpuFileScanExec(paths, conf=conf).execute(ExecCtx(conf))
    assert next(gen).num_rows == 1000
    t0 = time.monotonic()
    gen.close()
    assert time.monotonic() - t0 < 5.0
    assert _no_scan_thread_left()
    assert not rec.running()
    done = len(rec.calls)
    time.sleep(0.2)
    assert len(rec.calls) == done  # and nothing starts afterwards
    assert len(rec.of("walk")) < 16
    assert _most_at_once(rec.of("walk")) == 1


# (e) a failure surfaces at its row group, after every earlier batch
@pytest.mark.parametrize("kind", ["fetch", "walk"])
def test_a_failure_is_raised_at_its_row_group(tmp_path, monkeypatch, kind):
    paths = _ints(tmp_path)
    rec = _Recorder(monkeypatch, fail=(kind, "t-01.parquet", 1))
    conf = _conf()
    got = []
    with pytest.raises(OSError, match=f"{kind} of t-01.parquet:1 failed"):
        for b in TpuFileScanExec(paths, conf=conf).execute(ExecCtx(conf)):
            got.append(device_to_arrow(b))
    want = _expected(paths)[:5]  # file 0's four, file 1's first
    assert len(got) == 5
    for rb, t in zip(got, want):
        assert pa.Table.from_batches([rb]).equals(t.combine_chunks())
    # no row group behind the failed one was walked
    assert _in_task_order(rec.of("walk"), paths, 4)
    assert len(rec.of("walk")) == (5 if kind == "fetch" else 6)
    assert _no_scan_thread_left()


# (f) a file shorter than its footer says
def test_a_truncated_chunk_fails_as_the_open_file_did(tmp_path):
    rng = np.random.default_rng(0)
    n = 8000
    t = pa.table({"a": pa.array(rng.integers(0, 1 << 60, n)),
                  "b": pa.array(rng.uniform(0, 1, n))})
    whole = os.path.join(str(tmp_path), "whole.parquet")
    pq.write_table(t, whole, row_group_size=2000, use_dictionary=False)
    with open(whole, "rb") as f:
        data = f.read()
    tail = int.from_bytes(data[-8:-4], "little") + 8  # footer + its frame
    missing = tail + 100  # the last chunk now ends 100 bytes past the file
    cut = os.path.join(str(tmp_path), "cut.parquet")
    with open(cut, "wb") as f:
        f.write(data[:-tail - missing] + data[-tail:])
    last = pq.ParquetFile(cut).metadata.row_group(3).column(1)
    assert last.data_page_offset + last.total_compressed_size \
        == os.path.getsize(cut) + 100
    conf = _conf()
    scan = TpuFileScanExec([cut], conf=conf)
    # the fetch hands on what the file gave: the short chunk, short
    fetched = scan._fetch_row_group(cut, 3)
    fetched.seek(last.data_page_offset)
    assert len(fetched.read(last.total_compressed_size)) \
        == last.total_compressed_size - 100
    # ... and the scan fails at that row group as it did over the open
    # file (the page's codec refuses the short page), not with the short
    # buffer walked as if it were whole
    got = 0
    with pytest.raises(OSError, match="[Cc]orrupt"):
        for b in scan.execute(ExecCtx(conf)):
            got += 1
    assert got == 3
