"""Upload-pipeline robustness: the shared ordered feeder
(`spark_rapids_tpu.pipeline.pipelined_map`) and the device-decode scan
path built on it — feeder exception propagation, early close without
deadlock, and the bounded in-flight device-residency window (the legacy
arrow feeder's guarantees, now for the device-decode tunnel)."""
import os
import threading
import time

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_tpu.config import RapidsConf
from spark_rapids_tpu.exec.base import ExecCtx
from spark_rapids_tpu.io import TpuFileScanExec
from spark_rapids_tpu.pipeline import pipelined_map


# --- pipelined_map unit tests ----------------------------------------------

def test_order_and_results():
    out = list(pipelined_map(lambda x: x * x, range(50), threads=4,
                             window=8))
    assert out == [x * x for x in range(50)]


def test_serial_degrade():
    # threads<=0 or window<=0 is the kill switch: same results, no pool
    for threads, window in ((0, 4), (2, 0)):
        out = list(pipelined_map(lambda x: x + 1, range(5),
                                 threads=threads, window=window))
        assert out == [1, 2, 3, 4, 5]


def test_worker_exception_at_its_position():
    def fn(x):
        if x == 3:
            raise ValueError("boom3")
        return x

    got = []
    with pytest.raises(ValueError, match="boom3"):
        for v in pipelined_map(fn, range(6), threads=3, window=4):
            got.append(v)
    # every result BEFORE the failing item was delivered, in order
    assert got == [0, 1, 2]


def test_source_exception_propagates():
    def src():
        yield 1
        yield 2
        raise RuntimeError("src died")

    gen = pipelined_map(lambda x: x * 10, src(), threads=2, window=2)
    assert next(gen) == 10
    assert next(gen) == 20
    with pytest.raises(RuntimeError, match="src died"):
        next(gen)


def test_early_close_no_deadlock_on_full_window():
    produced = []

    def src():
        for i in range(10_000):
            produced.append(i)
            yield i

    gen = pipelined_map(lambda x: x, src(), threads=1, window=2)
    assert next(gen) == 0
    t0 = time.monotonic()
    gen.close()  # the feeder is parked on a full window right now
    assert time.monotonic() - t0 < 5.0
    with pytest.raises(StopIteration):
        next(gen)
    # the feeder stopped near the window, not after draining the source
    assert len(produced) < 100


def test_bounded_inflight_under_slow_consumer():
    lock = threading.Lock()
    state = {"started": 0, "consumed": 0, "max_excess": 0}

    def fn(x):
        with lock:
            state["started"] += 1
            state["max_excess"] = max(
                state["max_excess"],
                state["started"] - state["consumed"])
        return x

    for _ in pipelined_map(fn, range(30), threads=4, window=3):
        time.sleep(0.002)  # slow consumer
        with lock:
            state["consumed"] += 1
    # at most `window` undelivered results + the one being handed over
    assert state["max_excess"] <= 3 + 1, state


def test_weight_bounded_inflight():
    """With a weigher, the in-flight window is bounded in summed weight
    too: heavy items (the widened envelope's string blobs) must not
    stack up to `window` at once; a single over-budget item still
    admits alone (progress, not deadlock)."""
    lock = threading.Lock()
    state = {"inflight": 0, "max_w": 0}
    weights = [10, 10, 100, 10, 250, 10, 10, 10]  # 250 > max_weight

    def fn(i):
        with lock:
            state["inflight"] += weights[i]
            state["max_w"] = max(state["max_w"], state["inflight"])
        time.sleep(0.003)
        return i

    out = []
    for i in pipelined_map(fn, range(len(weights)), threads=4, window=8,
                           weigher=lambda i: weights[i],
                           max_weight=120):
        with lock:
            state["inflight"] -= weights[i]
        out.append(i)
    assert out == list(range(len(weights)))
    # admitted weight never exceeds budget + one in-hand-over item,
    # except the single over-budget item which runs alone
    assert state["max_w"] <= 250 + 10, state


def test_weigher_exception_is_source_exception():
    def bad_weigher(i):
        if i == 2:
            raise RuntimeError("weigher boom")
        return 1

    got = []
    gen = pipelined_map(lambda x: x, range(5), threads=2, window=2,
                        weigher=bad_weigher, max_weight=10)
    with pytest.raises(RuntimeError, match="weigher boom"):
        for v in gen:
            got.append(v)
    assert got == [0, 1]


# --- device-decode scan pipeline -------------------------------------------

def _write_rg_file(tmp_path, n=8000, rg=2000, name="f.parquet"):
    rng = np.random.default_rng(0)
    t = pa.table({
        "a": pa.array(rng.integers(0, 9, n).astype(np.int32)),
        "b": pa.array(rng.uniform(0, 1, n)),
    })
    p = os.path.join(str(tmp_path), name)
    pq.write_table(t, p, row_group_size=rg)
    return p


def test_device_decode_feeder_exception_propagates(tmp_path, monkeypatch):
    """A planner failure on the feeder side must surface in the
    consumer as the original exception, not a hang or a truncated
    stream."""
    p = _write_rg_file(tmp_path)
    orig = TpuFileScanExec._plan_row_group

    def boom(self, path, g, fetched):
        if g >= 2:
            raise OSError("disk gone")
        return orig(self, path, g, fetched)

    monkeypatch.setattr(TpuFileScanExec, "_plan_row_group", boom)
    scan = TpuFileScanExec([p])
    with pytest.raises(OSError, match="disk gone"):
        list(scan.execute(ExecCtx()))


def test_device_decode_early_close_no_deadlock(tmp_path):
    """Closing the scan generator with a full in-flight window must not
    deadlock the feeder, and must release every in-flight ledger
    charge."""
    from spark_rapids_tpu.memory import DeviceMemoryManager
    conf = RapidsConf({
        "spark.rapids.sql.scan.coalesceTargetBytes": "0",
        "spark.rapids.sql.scan.inFlightBatches": "1",
    })
    mgr = DeviceMemoryManager.shared(conf)
    p = _write_rg_file(tmp_path, n=16_000, rg=1000)
    scan = TpuFileScanExec([p], conf=conf)
    before = mgr.device_bytes
    gen = scan.execute(ExecCtx(conf))
    batch = next(gen)
    assert batch.num_rows == 1000
    t0 = time.monotonic()
    gen.close()
    assert time.monotonic() - t0 < 10.0
    # stragglers release on their own thread; give them a moment
    for _ in range(100):
        if mgr.device_bytes <= before:
            break
        time.sleep(0.02)
    assert mgr.device_bytes <= before


def test_device_decode_bounded_inflight_and_ledger(tmp_path, monkeypatch):
    """Under a slow consumer the feeder may run at most
    inFlightBatches assembled-but-unconsumed batches ahead, every one
    registered with (and then released from) the device memory
    ledger."""
    from spark_rapids_tpu.memory import DeviceMemoryManager
    conf = RapidsConf({
        "spark.rapids.sql.scan.coalesceTargetBytes": "0",
        "spark.rapids.sql.scan.inFlightBatches": "2",
        "spark.rapids.sql.scan.uploadThreads": "2",
    })
    window = 2
    mgr = DeviceMemoryManager.shared(conf)
    p = _write_rg_file(tmp_path, n=16_000, rg=1000)  # 16 row groups
    lock = threading.Lock()
    state = {"started": 0, "consumed": 0, "max_excess": 0}
    registered = []
    orig_assemble = TpuFileScanExec._assemble_device_batch
    orig_register = DeviceMemoryManager.register

    def counting_assemble(self, *a, **kw):
        with lock:
            state["started"] += 1
            state["max_excess"] = max(
                state["max_excess"],
                state["started"] - state["consumed"])
        return orig_assemble(self, *a, **kw)

    def spy_register(self, batch, pinned=False):
        sb = orig_register(self, batch, pinned=pinned)
        registered.append(sb)
        return sb

    monkeypatch.setattr(TpuFileScanExec, "_assemble_device_batch",
                        counting_assemble)
    monkeypatch.setattr(DeviceMemoryManager, "register", spy_register)
    before = mgr.device_bytes
    scan = TpuFileScanExec([p], conf=conf)
    n_rows = n_batches = 0
    for b in scan.execute(ExecCtx(conf)):
        time.sleep(0.01)  # slow consumer
        with lock:
            state["consumed"] += 1
        n_rows += b.num_rows
        n_batches += 1
    assert n_rows == 16_000
    assert n_batches == 16
    assert len(registered) == 16  # one ledger entry per batch
    assert mgr.device_bytes == before  # all in-flight charges released
    assert state["max_excess"] <= window + 1, state
