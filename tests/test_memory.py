"""Memory runtime tests: budget ledger, spill, semaphore, split-retry,
out-of-core sort and aggregate merge (reference:
RapidsDeviceMemoryStoreSuite / RmmSparkRetrySuiteBase / out-of-core sort —
SURVEY.md §4.2, §5.3, §5.7)."""
import threading
import time

import pyarrow as pa
import pytest

from spark_rapids_tpu import datatypes as dt
from spark_rapids_tpu.config import RapidsConf
from spark_rapids_tpu.columnar.arrow_bridge import arrow_to_device
from spark_rapids_tpu.exec import HostBatchSourceExec
from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec
from spark_rapids_tpu.exec.base import ExecCtx, collect_arrow, \
    collect_arrow_cpu
from spark_rapids_tpu.exec.sort import SortOrder, TpuSortExec
from spark_rapids_tpu.expr import Alias, UnresolvedColumn as col
from spark_rapids_tpu.expr.aggregates import Count, Max, Min, Sum
from spark_rapids_tpu.memory import (DeviceMemoryManager, TpuRetryOOM,
                                     split_batch)

from data_gen import (DoubleGen, IntegerGen, LongGen, StringGen, gen_table)


def _rb(n, seed=1, gens=None, names=None):
    gens = gens or [IntegerGen(min_val=0, max_val=50), LongGen()]
    return gen_table(gens, n, seed, names)


def _norm(table):
    """NaN-safe pydict for exact-order comparison."""
    import math
    out = {}
    for name, colvals in table.to_pydict().items():
        out[name] = ["NaN" if isinstance(v, float) and math.isnan(v) else v
                     for v in colvals]
    return out


def _sorted_rows(table):
    rows = zip(*[table.column(i).to_pylist()
                 for i in range(table.num_columns)])
    return sorted(rows, key=lambda r: tuple(
        (v is None, str(type(v)), v if v is not None else 0) for v in r))


# --- ledger / spill -------------------------------------------------------

def test_catalog_spills_lru_under_budget():
    conf = RapidsConf({"spark.rapids.memory.device.budgetBytes": 1 << 14})
    mm = DeviceMemoryManager(conf)
    sbs = []
    for i in range(8):
        b = arrow_to_device(_rb(256, seed=i))
        sbs.append(mm.register(b))
    assert mm.device_bytes <= mm.budget
    assert any(not sb.on_device for sb in sbs)  # older ones spilled
    assert mm.spill_bytes > 0
    # spilled batch round-trips through host Arrow intact
    spilled = next(sb for sb in sbs if not sb.on_device)
    again = spilled.get()
    assert again.num_rows == 256
    for sb in sbs:
        sb.release()
    assert mm.device_bytes == 0


def test_spillable_roundtrip_preserves_strings():
    conf = RapidsConf({"spark.rapids.memory.device.budgetBytes": 1})
    mm = DeviceMemoryManager(conf)
    rb = _rb(64, gens=[StringGen(max_len=10), IntegerGen()])
    sb = mm.register(arrow_to_device(rb))
    assert not sb.on_device or mm.device_bytes > mm.budget
    mm._evict_to_fit()
    from spark_rapids_tpu.columnar.arrow_bridge import device_to_arrow
    assert device_to_arrow(sb.get()).equals(rb)


# --- semaphore ------------------------------------------------------------

def test_semaphore_limits_concurrency():
    conf = RapidsConf({"spark.rapids.sql.concurrentGpuTasks": 1})
    mm = DeviceMemoryManager(conf)
    active = []
    peak = []

    def task():
        with mm.task_slot():
            active.append(1)
            peak.append(len(active))
            time.sleep(0.02)
            active.pop()

    threads = [threading.Thread(target=task) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert max(peak) == 1


# --- split-and-retry ------------------------------------------------------

def test_split_batch_halves_rows():
    rb = _rb(300, gens=[IntegerGen(), StringGen(max_len=6)])
    b = arrow_to_device(rb)
    b1, b2 = split_batch(b)
    from spark_rapids_tpu.columnar.arrow_bridge import device_to_arrow
    t = pa.Table.from_batches([device_to_arrow(b1), device_to_arrow(b2)])
    assert t.to_pydict() == pa.Table.from_batches([rb]).to_pydict()


def test_injected_oom_split_retry_aggregate():
    """spark.rapids.sql.test.injectRetryOOM forces an OOM inside the fused
    stage; split-and-retry halves the batch and the result is unchanged."""
    rb = _rb(512, seed=3)
    plan = TpuHashAggregateExec(
        [col("c0")], [Alias(Sum(col("c1")), "s"), Alias(Count(), "n")],
        HostBatchSourceExec([rb]))
    want = _sorted_rows(collect_arrow_cpu(plan))
    ctx = ExecCtx(RapidsConf({"spark.rapids.sql.test.injectRetryOOM": 1}))
    got = _sorted_rows(collect_arrow(plan, ctx))
    assert got == want


def test_injected_oom_exhausts_splits():
    conf = RapidsConf({"spark.rapids.sql.oomRetry.enabled": False})
    mm = DeviceMemoryManager(conf)

    def boom(_):
        raise TpuRetryOOM("RESOURCE_EXHAUSTED: fake")

    b = arrow_to_device(_rb(64))
    with pytest.raises(TpuRetryOOM):
        mm.with_retry(b, boom)


def test_non_oom_errors_not_retried():
    mm = DeviceMemoryManager(RapidsConf())
    calls = []

    def boom(_):
        calls.append(1)
        raise ValueError("not an oom")

    b = arrow_to_device(_rb(64))
    with pytest.raises(ValueError):
        mm.with_retry(b, boom)
    assert len(calls) == 1


# --- out-of-core sort and aggregate --------------------------------------

@pytest.mark.parametrize("gens,names", [
    ([LongGen(), DoubleGen(null_frac=0.1)], None),
    ([StringGen(max_len=8), IntegerGen(null_frac=0.1)], None),
])
def test_out_of_core_sort_forced_spill(gens, names):
    """Sort at data size >> device budget: external merge with host spill
    produces exactly the oracle's ordering."""
    rbs = [_rb(500, seed=s, gens=gens, names=names) for s in range(6)]
    plan = TpuSortExec([SortOrder(col("c0")), SortOrder(col("c1"))],
                       HostBatchSourceExec(rbs))
    conf = RapidsConf({"spark.rapids.memory.device.budgetBytes": 1 << 13})
    ctx = ExecCtx(conf)
    got = collect_arrow(plan, ctx)
    want = collect_arrow_cpu(plan)
    assert _norm(got) == _norm(want)
    assert ctx.mm.spill_bytes > 0  # really went out-of-core


def test_out_of_core_aggregate_bounded_merge():
    rbs = [_rb(400, seed=s) for s in range(8)]
    plan = TpuHashAggregateExec(
        [col("c0")],
        [Alias(Sum(col("c1")), "s"), Alias(Min(col("c1")), "lo"),
         Alias(Max(col("c1")), "hi"), Alias(Count(), "n")],
        HostBatchSourceExec(rbs))
    conf = RapidsConf({"spark.rapids.memory.device.budgetBytes": 1 << 13})
    got = _sorted_rows(collect_arrow(plan, ExecCtx(conf)))
    want = _sorted_rows(collect_arrow_cpu(plan))
    assert got == want


def test_sort_small_input_stays_in_core():
    rbs = [_rb(100, seed=s) for s in range(2)]
    plan = TpuSortExec([SortOrder(col("c1"))], HostBatchSourceExec(rbs))
    ctx = ExecCtx()
    got = collect_arrow(plan, ctx)
    want = collect_arrow_cpu(plan)
    assert _norm(got) == _norm(want)
    assert ctx.mm.spill_bytes == 0


# --- disk spill tier + debug surfaces --------------------------------------

def test_host_tier_cascades_to_disk(tmp_path):
    """Host-tier pressure tiers spilled batches to Arrow IPC files and
    reads them back on access (SURVEY.md:143 device/host/disk ladder)."""
    import pyarrow as pa
    from spark_rapids_tpu.columnar.arrow_bridge import arrow_to_device
    conf = RapidsConf({
        "spark.rapids.memory.device.budgetBytes": 1 << 12,
        "spark.rapids.memory.host.spillStorageSize": 1 << 12,
        "spark.rapids.memory.spillDir": str(tmp_path)})
    mm = DeviceMemoryManager(conf)
    import numpy as np
    rng = np.random.default_rng(0)
    sbs = []
    for i in range(6):
        rb = pa.record_batch({"v": pa.array(
            rng.integers(0, 1000, 512), pa.int64())})
        sbs.append(mm.register(arrow_to_device(rb)))
    # device budget forced host spills; host limit forced disk spills
    assert mm.spill_bytes > 0
    assert mm.disk_spill_bytes > 0
    assert mm.disk_in_use_bytes > 0  # live residency tracked
    assert any(sb.on_disk for sb in sbs)
    import os
    # files land in this process's incarnation namespace, not the root
    assert os.path.dirname(mm.spill_dir) == str(tmp_path)
    assert os.listdir(mm.spill_dir)
    # read-back restores values through all tiers
    for sb in sbs:
        host = sb.get_host()
        assert host.num_rows == 512
    for sb in sbs:
        sb.release()
    # disk files cleaned on release; live residency back to zero
    assert os.listdir(mm.spill_dir) == []
    assert mm.disk_in_use_bytes == 0


# --- spill durability: sealed files, classified read-back, disk budget -----

def _disk_mgr(tmp_path, extra=None):
    conf = {"spark.rapids.memory.device.budgetBytes": 1 << 22,
            "spark.rapids.memory.spillDir": str(tmp_path)}
    conf.update(extra or {})
    return DeviceMemoryManager(RapidsConf(conf))


def _spill_to_disk(mm, n=256, seed=1):
    """One batch walked device -> host -> committed sealed disk file."""
    rb = _rb(n, seed=seed)
    sb = mm.register(arrow_to_device(rb))
    sb.spill(cascade=False)
    assert sb.spill_to_disk(), "spill file did not commit"
    assert sb.on_disk and sb._host is None
    return sb, rb


def test_spill_file_is_sealed_and_verified_roundtrip(tmp_path):
    """The committed spill file carries the shuffle tier's CRC32C+length
    trailer and read-back verifies it (same sealed format — PR 12)."""
    from spark_rapids_tpu.shuffle.integrity import read_sealed_file
    mm = _disk_mgr(tmp_path)
    sb, rb = _spill_to_disk(mm)
    # independently verifiable with the shuffle-side reader
    payload = read_sealed_file(sb._disk_path, RuntimeError)
    assert len(payload) == sb._disk_size - 16  # FOOTER_LEN
    host = sb.get_host()  # verified read-back
    assert pa.Table.from_batches([host]).to_pydict() \
        == pa.Table.from_batches([rb]).to_pydict()
    assert not sb.on_disk and mm.disk_in_use_bytes == 0
    sb.release()


@pytest.mark.parametrize("damage,kind", [
    ("torn", "torn"), ("corrupt", "corrupt"), ("missing", "missing")])
def test_spill_read_failure_classified(tmp_path, damage, kind):
    """Torn trailer / flipped payload bytes / deleted file each classify
    as SpillReadError(kind=...) — never a raw OSError/ArrowInvalid."""
    import os
    from spark_rapids_tpu.memory import SpillReadError
    mm = _disk_mgr(tmp_path)
    sb, _ = _spill_to_disk(mm)
    path = sb._disk_path
    if damage == "torn":
        with open(path, "r+b") as f:
            f.truncate(os.path.getsize(path) - 8)
    elif damage == "corrupt":
        with open(path, "r+b") as f:
            f.seek(os.path.getsize(path) // 2)
            chunk = f.read(4)
            f.seek(os.path.getsize(path) // 2)
            f.write(bytes(b ^ 0xFF for b in chunk))
    else:
        os.unlink(path)
    with pytest.raises(SpillReadError) as ei:
        sb.get_host()
    assert ei.value.kind == kind
    # tier state untouched: a later consumer sees the SAME classified
    # state, and release still cleans the ledger
    assert sb.on_disk
    sb.release()
    assert mm.disk_in_use_bytes == 0


def test_spill_write_side_chaos_injections(tmp_path):
    """spark.rapids.memory.test.injectSpillFault damages the COMMITTED
    file exactly like the chaos modes spill_corrupt/spill_torn do."""
    from spark_rapids_tpu.memory import SpillReadError
    for fault, kind in (("corrupt", "corrupt"), ("torn", "torn")):
        mm = _disk_mgr(tmp_path / fault, {
            "spark.rapids.memory.test.injectSpillFault": fault})
        sb, _ = _spill_to_disk(mm)
        with pytest.raises(SpillReadError) as ei:
            sb.get_host()
        assert ei.value.kind == kind
        sb.release()


def test_spill_read_eio_retries_in_place(tmp_path):
    """A transient EIO (countdown sidecar — the shuffle tier's chaos
    grammar) is retried in place and the read succeeds."""
    mm = _disk_mgr(tmp_path, {
        "spark.rapids.memory.disk.readRetryWaitMs": 1})
    sb, rb = _spill_to_disk(mm)
    with open(sb._disk_path + ".eio", "w") as f:
        f.write("2")  # first two reads fail transiently
    host = sb.get_host()
    assert host.num_rows == rb.num_rows
    sb.release()


def test_spill_read_eio_exhausted_classifies_io(tmp_path):
    from spark_rapids_tpu.memory import SpillReadError
    mm = _disk_mgr(tmp_path, {
        "spark.rapids.memory.disk.readRetries": 1,
        "spark.rapids.memory.disk.readRetryWaitMs": 1})
    sb, _ = _spill_to_disk(mm)
    with open(sb._disk_path + ".eio", "w") as f:
        f.write("99")  # more failures than the retry budget
    with pytest.raises(SpillReadError) as ei:
        sb.get_host()
    assert ei.value.kind == "io"
    sb.release()


def test_zero_row_batch_spill_roundtrip(tmp_path):
    """A 0-live-row batch survives the full device->host->disk->host
    walk (0-row Arrow IPC tables yield no batches on read — the
    read-back must rebuild an empty RecordBatch, not crash)."""
    mm = _disk_mgr(tmp_path)
    rb = pa.record_batch({"a": pa.array([], pa.int64()),
                          "b": pa.array([], pa.string())})
    sb = mm.register(arrow_to_device(rb))
    sb.spill(cascade=False)
    assert sb.spill_to_disk()
    host = sb.get_host()
    assert host.num_rows == 0
    assert host.schema.names == ["a", "b"]
    sb.release()


def test_enospc_mid_write_classified_and_no_partial_file(tmp_path):
    """Injected ENOSPC mid-write (after payload, before commit): the
    partial tmp is unlinked, the batch stays host-resident, the refusal
    is classified disk pressure — no OSError escapes, nothing leaks."""
    import os
    from spark_rapids_tpu.memory import _SPILL_WRITE_FAILURES
    before = _SPILL_WRITE_FAILURES.labels("enospc").value
    mm = _disk_mgr(tmp_path, {
        "spark.rapids.memory.test.injectDiskFull": 2})  # both attempts
    rb = _rb(256)
    sb = mm.register(arrow_to_device(rb))
    sb.spill(cascade=False)
    assert sb.spill_to_disk() is False  # refused, not raised
    assert sb._host is not None and not sb.on_disk  # data survives
    assert mm.disk_pressure_active()
    assert _SPILL_WRITE_FAILURES.labels("enospc").value == before + 1
    leftovers = os.listdir(mm.spill_dir) if os.path.isdir(mm.spill_dir) \
        else []
    assert leftovers == [], f"partial files leaked: {leftovers}"
    # countdown spent: the next attempt commits and clears the pressure
    assert sb.spill_to_disk() is True
    assert not mm.disk_pressure_active()
    sb.release()
    assert mm.disk_in_use_bytes == 0


def test_io_write_failure_is_evidence_not_pressure(tmp_path, monkeypatch):
    """A transient non-ENOSPC write error classifies as spill_write_failed
    evidence (metric + flight ring) but does NOT open the sticky
    disk-pressure window: one flaky EIO must not pause host->disk
    eviction or flip the ladder's terminal rung to a budget cancel for
    a disk that has room and is healthy again."""
    import errno
    from spark_rapids_tpu.memory import _SPILL_WRITE_FAILURES
    from spark_rapids_tpu.shuffle import integrity
    mm = _disk_mgr(tmp_path)
    rb = _rb(256)
    sb = mm.register(arrow_to_device(rb))
    sb.spill(cascade=False)
    before = _SPILL_WRITE_FAILURES.labels("io").value

    def flaky(path, payload, fail_hook=None):
        raise OSError(errno.EIO, "flaky disk")

    monkeypatch.setattr(integrity, "write_sealed_file", flaky)
    from spark_rapids_tpu.obs.recorder import RECORDER
    ring_before = len(RECORDER.snapshot())
    assert sb.spill_to_disk() is False  # refused, not raised
    assert sb._host is not None and not sb.on_disk  # data survives
    assert _SPILL_WRITE_FAILURES.labels("io").value == before + 1
    assert not mm.disk_pressure_active()  # evidence, not pressure
    # the flight event matches: spill_write_failed (spill_failure
    # anomaly), NOT disk_pressure (which would emit a disk-pressure
    # incident bundle for one flaky EIO)
    new = [e for e in RECORDER.snapshot()[ring_before:]
           if e.get("kind") == "mem" and e.get("fail_kind") == "io"]
    assert [e["ev"] for e in new] == ["spill_write_failed"]
    monkeypatch.undo()
    assert sb.spill_to_disk() is True  # healthy again: commits
    sb.release()
    assert mm.disk_in_use_bytes == 0


def test_slow_disk_injection_gets_fresh_manager(tmp_path):
    """spark.rapids.memory.test.injectSlowDisk bypasses the shared()
    cache like every other spill/disk fault injection: the delay must
    neither silently no-op (default-conf manager built first, then
    shared by the injected task) nor bleed into later non-injected
    tasks that hash to the same key."""
    base = {"spark.rapids.memory.device.budgetBytes": 1 << 22,
            "spark.rapids.memory.spillDir": str(tmp_path)}
    plain = DeviceMemoryManager.shared(RapidsConf(base))
    slow = DeviceMemoryManager.shared(RapidsConf(
        {**base, "spark.rapids.memory.test.injectSlowDisk": 50}))
    assert slow is not plain
    assert slow._slow_disk_s > 0 and plain._slow_disk_s == 0
    # and a second default-conf resolve still shares the plain one
    assert DeviceMemoryManager.shared(RapidsConf(base)) is plain


def test_disk_read_policy_confs_fragment_shared_cache(tmp_path):
    """The disk read-retry/orphan-TTL knobs are part of the shared()
    cache key: a query setting readRetries=0 for fail-fast reads must
    get a manager that honors it, not the cached default-policy one
    (DISK_SPILL_LIMIT already fragments the cache; these ride the same
    rule)."""
    base = {"spark.rapids.memory.device.budgetBytes": 1 << 22,
            "spark.rapids.memory.spillDir": str(tmp_path)}
    plain = DeviceMemoryManager.shared(RapidsConf(base))
    fast = DeviceMemoryManager.shared(RapidsConf(
        {**base, "spark.rapids.memory.disk.readRetries": 0,
         "spark.rapids.memory.disk.readRetryWaitMs": 500}))
    assert fast is not plain
    assert fast.disk_read_retries == 0 and plain.disk_read_retries == 3
    assert DeviceMemoryManager.shared(RapidsConf(base)) is plain


def test_budget_eviction_skips_terminally_bad_victim(tmp_path):
    """A victim whose read-back fails terminally (corrupt) is skipped by
    later budget-eviction passes: its classified failure is counted once
    for the eviction probe, not once per over-budget spill, and the bad
    file stays referenced for the real consumer to classify."""
    from spark_rapids_tpu.memory import SpillReadError, \
        _SPILL_READ_FAILURES
    mm = _disk_mgr(tmp_path)
    sb1, _ = _spill_to_disk(mm, seed=1)
    with open(sb1._disk_path, "r+b") as f:
        f.seek(3)
        f.write(b"\xff")
    mm.disk_limit = sb1._disk_size  # any further spill is over budget
    before = _SPILL_READ_FAILURES.labels("corrupt").value
    spills = []
    for seed in (2, 3, 4):  # three eviction passes over the bad victim
        sb = mm.register(arrow_to_device(_rb(256, seed=seed)))
        sb.spill(cascade=False)
        assert sb.spill_to_disk() is False  # budget refusal, classified
        spills.append(sb)
    assert _SPILL_READ_FAILURES.labels("corrupt").value == before + 1
    assert sb1.on_disk  # never silently dropped
    with pytest.raises(SpillReadError) as ei:  # consumer still classifies
        sb1.get_host()
    assert ei.value.kind == "corrupt"
    for sb in (sb1, *spills):
        sb.release()
    assert mm.disk_in_use_bytes == 0


def test_budget_eviction_skips_persistent_eio_victim(tmp_path):
    """A victim whose read-back exhausts the EIO retry budget (kind=io)
    is marked bad exactly like corrupt/torn victims: later
    budget-eviction passes must neither re-sleep the full retry ladder
    under another batch's spill nor re-count the classified failure
    once per over-budget write."""
    from spark_rapids_tpu.memory import _SPILL_READ_FAILURES
    mm = _disk_mgr(tmp_path, {
        "spark.rapids.memory.disk.readRetries": 1,
        "spark.rapids.memory.disk.readRetryWaitMs": 1})
    sb1, _ = _spill_to_disk(mm, seed=1)
    with open(sb1._disk_path + ".eio", "w") as f:
        f.write("9999")  # persistent: every read attempt fails
    mm.disk_limit = sb1._disk_size  # any further spill is over budget
    before = _SPILL_READ_FAILURES.labels("io").value
    spills = []
    for seed in (2, 3, 4):  # three eviction passes over the bad victim
        sb = mm.register(arrow_to_device(_rb(256, seed=seed)))
        sb.spill(cascade=False)
        assert sb.spill_to_disk() is False  # budget refusal, classified
        spills.append(sb)
    assert _SPILL_READ_FAILURES.labels("io").value == before + 1
    assert sb1.on_disk  # never silently dropped
    for sb in (sb1, *spills):
        sb.release()
    assert mm.disk_in_use_bytes == 0


def test_disk_budget_admission_reserves_not_check_then_act(tmp_path):
    """Admission RESERVES the file size in disk_in_use_bytes under the
    ledger lock: two concurrent spills that each fit alone must not
    both pass the check and breach spark.rapids.memory.disk.limit
    together — the second admit sees the first's reservation and
    refuses classified."""
    from spark_rapids_tpu.memory import _SPILL_WRITE_FAILURES
    mm = _disk_mgr(tmp_path)
    mm.disk_limit = 100
    before = _SPILL_WRITE_FAILURES.labels("budget").value
    assert mm._disk_budget_admit(60) is True
    assert mm.disk_in_use_bytes == 60  # reserved before the write lands
    # check-then-act would admit this too (60 <= 100); the reservation
    # makes it see 120 > 100 with nothing on disk to evict
    assert mm._disk_budget_admit(60) is False
    assert mm.disk_in_use_bytes == 60  # a refusal reserves nothing
    assert _SPILL_WRITE_FAILURES.labels("budget").value == before + 1
    assert mm.disk_pressure_active()
    with mm._lock:  # the caller's non-commit path releases its hold
        mm.disk_in_use_bytes -= 60
    assert mm.disk_in_use_bytes == 0


def test_unlink_failure_after_verified_read_not_classified(tmp_path,
                                                           monkeypatch):
    """An unlink that fails AFTER the verified read succeeded (EACCES,
    ro-remount) must not escape as an unclassified OSError that
    discards the table and blames the reading worker: the data is
    returned, the residency ledger drops the bytes, and the stale file
    is a bounded leak the next incarnation's orphan sweep reclaims."""
    import errno
    import os
    mm = _disk_mgr(tmp_path)
    sb, rb = _spill_to_disk(mm)
    path = sb._disk_path
    real_unlink = os.unlink

    def ro_unlink(p, *a, **k):
        if p == path:
            raise OSError(errno.EACCES, "read-only remount")
        return real_unlink(p, *a, **k)

    monkeypatch.setattr(os, "unlink", ro_unlink)
    host = sb.get_host()  # returns the data, does not raise
    assert host.num_rows == rb.num_rows
    assert not sb.on_disk
    assert mm.disk_in_use_bytes == 0
    assert os.path.exists(path)  # the bounded leak, swept next boot
    monkeypatch.undo()
    sb.release()


def test_stale_pressure_window_does_not_abort_eviction_pass(tmp_path):
    """_evict_host_to_disk stops a pass only on a FRESH disk refusal
    (every refusal restamps the sticky window, so a fresh one strictly
    advances it) — a victim losing its try-acquire or sitting behind
    the anti-churn bar while a stale 30s window from a healed ENOSPC
    is still open must not strand the rest of the host tier over its
    limit for the remainder of the window."""
    mm = _disk_mgr(tmp_path)
    sb1 = mm.register(arrow_to_device(_rb(256, seed=1)))
    sb1.spill(cascade=False)
    sb2 = mm.register(arrow_to_device(_rb(256, seed=2)))
    sb2.spill(cascade=False)
    sb1._no_disk_until = time.monotonic() + 60  # anti-churn: False,
    # without restamping the window
    mm._disk_pressure_until = time.monotonic() + 60  # stale (healed)
    mm.host_limit = 0
    mm._evict_host_to_disk()
    assert sb2.on_disk, "stale window aborted the pass at first False"
    assert not sb1.on_disk
    for sb in (sb1, sb2):
        sb.release()
    assert mm.disk_in_use_bytes == 0


def test_get_charge_unwind_on_failed_reupload(tmp_path, monkeypatch):
    """Regression (PR 12 satellite): a re-upload that raises after
    _charge must not strand device_bytes on a batch whose _device stays
    None — the charge unwinds and a later get() still works."""
    import spark_rapids_tpu.columnar.arrow_bridge as bridge
    mm = _disk_mgr(tmp_path)
    rb = _rb(128)
    sb = mm.register(arrow_to_device(rb))
    sb.spill(cascade=False)
    baseline = mm.device_bytes
    real = bridge.arrow_to_device

    def boom(*a, **k):
        raise RuntimeError("upload exploded")

    monkeypatch.setattr(bridge, "arrow_to_device", boom)
    with pytest.raises(RuntimeError):
        sb.get()
    assert mm.device_bytes == baseline, "stranded device charge"
    assert sb._host is not None and sb._device is None  # still retryable
    monkeypatch.setattr(bridge, "arrow_to_device", real)
    assert sb.get().num_rows == 128  # the retry succeeds
    sb.release()


def test_disk_budget_evicts_oldest_then_refuses_classified(tmp_path):
    """spark.rapids.memory.disk.limit: an over-budget spill first
    promotes the oldest unpinned disk entry back to host; if the budget
    STILL can't fit (victims pinned), the write is refused classified
    as budget pressure."""
    from spark_rapids_tpu.memory import _SPILL_WRITE_FAILURES
    mm = _disk_mgr(tmp_path)
    sb1, _ = _spill_to_disk(mm, seed=1)
    size = sb1._disk_size
    mm.disk_limit = int(size * 1.5)  # room for one file, not two
    sb2, _ = _spill_to_disk(mm, seed=2)  # evicts sb1 to make room
    assert sb2.on_disk
    assert not sb1.on_disk and sb1._host is not None  # promoted back
    assert mm.disk_in_use_bytes <= mm.disk_limit
    # pinned disk entries are not eviction victims: now the budget is
    # genuinely unsatisfiable and the refusal classifies as 'budget'
    sb2.pin()
    before = _SPILL_WRITE_FAILURES.labels("budget").value
    sb3 = mm.register(arrow_to_device(_rb(256, seed=3)))
    sb3.spill(cascade=False)
    sb1._no_disk_until = 0.0  # not the victim under test
    assert sb3.spill_to_disk() is False
    assert _SPILL_WRITE_FAILURES.labels("budget").value == before + 1
    assert mm.disk_pressure_active()
    sb2.unpin()
    for sb in (sb1, sb2, sb3):
        sb.release()
    assert mm.disk_in_use_bytes == 0


def test_disk_pressure_feeds_ladder_terminal_as_budget_cancel(tmp_path):
    """A query OOMing while the disk tier refuses writes walks the
    ladder and terminates QueryCancelled(reason=budget) — CPU fallback
    cannot spill either when the disk is full."""
    from spark_rapids_tpu.lifecycle import QueryCancelled, QueryContext
    mm = _disk_mgr(tmp_path, {"spark.rapids.sql.oomRetry.maxSplits": 0,
                              "spark.rapids.query.admission.timeout": 1})
    mm._disk_pressure_until = time.monotonic() + 60  # sticky pressure
    qctx = QueryContext(mm.conf, query_id="qdisk")

    def boom(_):
        raise TpuRetryOOM("RESOURCE_EXHAUSTED: fake")

    b = arrow_to_device(_rb(64))
    with pytest.raises(QueryCancelled) as ei:
        mm.with_retry(b, boom, qctx=qctx)
    assert ei.value.reason == "budget"
    assert "disk spill tier" in ei.value.detail


def test_orphan_sweep_reclaims_dead_incarnations(tmp_path):
    """Namespaces whose same-host owner pid is dead are reclaimed
    immediately; foreign-host dirs only via the age fallback; the live
    process's own namespace is never touched."""
    import os
    import subprocess
    from spark_rapids_tpu.memory import (_hostname, spill_namespace,
                                         sweep_orphan_spill_dirs)
    base = str(tmp_path)
    host = _hostname()
    p = subprocess.Popen(["true"])
    p.wait()  # reaped: the pid is provably dead
    dead = os.path.join(base, f"{host}-{p.pid}-{'a' * 8}")
    os.makedirs(dead)
    open(os.path.join(dead, "spill-x.arrow"), "w").close()
    old_foreign = os.path.join(base, f"elsewhere-4242-{'b' * 8}")
    os.makedirs(old_foreign)
    os.utime(old_foreign, (1.0, 1.0))  # ancient
    young_foreign = os.path.join(base, f"elsewhere-4243-{'c' * 8}")
    os.makedirs(young_foreign)
    own = spill_namespace(base)
    os.makedirs(own)
    removed = sweep_orphan_spill_dirs(base, ttl_s=3600.0, force=True)
    assert dead in removed and old_foreign in removed
    assert not os.path.exists(dead) and not os.path.exists(old_foreign)
    assert os.path.exists(young_foreign)  # can't prove abandonment yet
    assert os.path.exists(own)  # never sweep the live namespace


def test_manager_construction_sweeps_once(tmp_path):
    """Manager construction runs the orphan sweep for its root (and a
    dead namespace planted there is gone before the first spill)."""
    import os
    import subprocess
    from spark_rapids_tpu.memory import _hostname
    p = subprocess.Popen(["true"])
    p.wait()
    dead = os.path.join(str(tmp_path), f"{_hostname()}-{p.pid}-{'d' * 8}")
    os.makedirs(dead)
    # force=False path is once-per-root-per-process; force guarantees
    # this test is order-independent under pytest
    from spark_rapids_tpu.memory import sweep_orphan_spill_dirs
    sweep_orphan_spill_dirs(str(tmp_path), force=True)
    assert not os.path.exists(dead)
    mm = _disk_mgr(tmp_path)
    sb, _ = _spill_to_disk(mm)
    sb.release()


def test_leak_report(tmp_path):
    import pyarrow as pa
    from spark_rapids_tpu.columnar.arrow_bridge import arrow_to_device
    conf = RapidsConf({"spark.rapids.refcount.debug": True,
                       "spark.rapids.memory.device.budgetBytes": 1 << 20,
                       "spark.rapids.memory.spillDir": str(tmp_path)})
    mm = DeviceMemoryManager(conf)
    rb = pa.record_batch({"v": pa.array([1, 2, 3], pa.int64())})
    sb = mm.register(arrow_to_device(rb))
    rep = mm.leak_report()
    assert "never released" in rep
    assert "test_memory" in rep  # the alloc site traceback names us
    sb.release()
    assert mm.leak_report() == "no leaked catalog entries"


# what a TPU v5e raised on exhaustion (jax 0.9.0, chip run of PR 21)
_V5E_PROGRAM_OOM = ("RESOURCE_EXHAUSTED: Error allocating device buffer: "
                    "Attempting to allocate 64.00G. That was not possible. "
                    "There are 15.75G free.; (0x0x0_HBM0)")


@pytest.mark.parametrize("exc,is_oom", [
    (TpuRetryOOM("injected"), True),
    # a program that cannot get its buffers: the runtime's own error type
    ("runtime:" + _V5E_PROGRAM_OOM, True),
    # an eager array creation that cannot: same status text, ValueError
    (ValueError(_V5E_PROGRAM_OOM.replace("64.00G", "3.00G")), True),
    ("runtime:INVALID_ARGUMENT: something else entirely", False),
    # marker text inside an unrelated error must not be split-and-retried
    (ValueError("bad conf: RESOURCE_EXHAUSTED is not a valid mode"), False),
    (RuntimeError(_V5E_PROGRAM_OOM), False),
])
def test_is_oom_error_recognises_what_the_v5e_raises(exc, is_oom):
    from jax.errors import JaxRuntimeError
    from spark_rapids_tpu.memory import _is_oom_error
    if isinstance(exc, str):
        exc = JaxRuntimeError(exc.split(":", 1)[1])
    assert _is_oom_error(exc) is is_oom


def test_device_memory_comes_from_the_device_or_fails_on_a_tpu(monkeypatch):
    """The budget derives from the device's own bytes_limit; only a CPU
    backend (which reports none) gets the 6 GiB stand-in."""
    import jax

    class _Dev:
        def __init__(self, platform, stats):
            self.platform, self._stats = platform, stats
            self.device_kind = "TPU v5 lite" if platform == "tpu" else "cpu"

        def memory_stats(self):
            return self._stats

    def with_device(dev):
        monkeypatch.setattr(jax, "local_devices", lambda: [dev])
        return DeviceMemoryManager._device_memory()
    assert with_device(_Dev("cpu", None)) == 6 << 30
    assert with_device(_Dev("tpu", {"bytes_limit": 16909336064})) \
        == 16909336064
    with pytest.raises(RuntimeError, match="bytes_limit"):
        with_device(_Dev("tpu", {"bytes_in_use": 1}))
