"""Query lifecycle tests (spark_rapids_tpu/lifecycle.py): classified
cancellation (user / deadline / budget / admission), fair per-tenant
admission, the cancel-aware upload pipeline, the memory-pressure
degradation ladder, the query-scoped chaos modes — and the
process-cluster cancel paths, asserting zero ledger/slot leakage after
every cancel. Run with ``RAPIDS_TPU_LOCKWATCH=1`` every path here is
also a lock-order witness (tests/conftest.py)."""
import json
import os
import threading
import time

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.config import RapidsConf
from spark_rapids_tpu.lifecycle import (CancellationToken,
                                        FairAdmissionController,
                                        QueryCancelled, QueryContext,
                                        read_cancel_marker)
from spark_rapids_tpu.memory import DeviceMemoryManager
from spark_rapids_tpu.pipeline import pipelined_map
from spark_rapids_tpu.session import TpuSession


# --- token ------------------------------------------------------------------

def test_token_first_cancel_wins_and_classifies():
    tok = CancellationToken("q1")
    assert not tok.cancelled and tok.poll() is None
    assert tok.cancel("deadline", "too slow")
    assert not tok.cancel("user", "late loser")  # first wins
    assert tok.reason == "deadline" and tok.detail == "too slow"
    with pytest.raises(QueryCancelled) as ei:
        tok.check()
    assert ei.value.reason == "deadline" and ei.value.query_id == "q1"
    with pytest.raises(ValueError, match="unknown cancel reason"):
        tok.cancel("meteor")


def test_token_deadline_fires():
    tok = CancellationToken("q2", deadline_s=0.01)
    time.sleep(0.03)
    assert tok.poll() == "deadline"
    assert tok.cancelled


def test_cancel_marker_roundtrip(tmp_path):
    p = str(tmp_path / "q.cancel")
    with open(p, "w") as f:
        f.write("budget over the line")
    assert read_cancel_marker(p) == ("budget", "over the line")
    with open(p, "w") as f:
        f.write("garbage-content")
    r, _ = read_cancel_marker(p)
    assert r == "user"  # foreign content degrades, never crashes
    tok = CancellationToken("q3", cancel_file=p)
    with open(p, "w") as f:
        f.write("user bye")
    tok._next_poll = 0.0
    assert tok.poll() == "user"


# --- fair admission ---------------------------------------------------------

def _qc(conf=None, **kw):
    return QueryContext(RapidsConf(conf or {}), **kw)


def test_admission_weighted_grant_order():
    """2 slots, tenants a(weight 3) / b(1) each holding one; on a's
    release the freed slot must go to the waiting a (score 0/3) over
    the earlier-queued b (score 1/1)."""
    ctl = FairAdmissionController(2, RapidsConf({
        "spark.rapids.query.admission.weights": "a:3,b:1"}))
    sa = ctl.slot(_qc(tenant="a"))
    sb = ctl.slot(_qc(tenant="b"))
    got = []

    def waiter(tenant, tag):
        with ctl.slot(_qc(tenant=tenant)):
            got.append(tag)
            time.sleep(0.2)

    tb = threading.Thread(target=waiter, args=("b", "b2"))
    tb.start()
    time.sleep(0.05)  # b2 queues first
    ta = threading.Thread(target=waiter, args=("a", "a2"))
    ta.start()
    time.sleep(0.05)
    assert ctl.snapshot()["queued"] == {"b": 1, "a": 1}
    sa.release()  # freed slot: a2 (0/3) beats b2 (1/1) despite FIFO age
    time.sleep(0.1)
    assert got == ["a2"]
    sb.release()
    ta.join()
    tb.join()
    assert got == ["a2", "b2"]
    assert ctl.snapshot()["in_use"] == 0 and not ctl.snapshot()["tenants"]


def test_admission_queue_full_rejects_classified():
    ctl = FairAdmissionController(1, RapidsConf({
        "spark.rapids.query.admission.maxQueuedPerTenant": "1"}))
    held = ctl.slot(_qc(tenant="t"))
    parked = threading.Thread(
        target=lambda: ctl.slot(_qc(tenant="t")).release())
    parked.start()
    time.sleep(0.05)
    with pytest.raises(QueryCancelled) as ei:
        ctl.slot(_qc(tenant="t"))
    assert ei.value.reason == "admission"
    assert "queue full" in ei.value.detail
    held.release()
    parked.join()


def test_admission_timeout_rejects_classified():
    ctl = FairAdmissionController(1, RapidsConf({
        "spark.rapids.query.admission.timeout": "0.1"}))
    held = ctl.slot(None)
    qx = _qc(tenant="t")
    t0 = time.monotonic()
    with pytest.raises(QueryCancelled) as ei:
        ctl.slot(qx)
    assert ei.value.reason == "admission"
    assert time.monotonic() - t0 < 5.0
    assert qx.token.reason == "admission"  # the token was classified
    held.release()
    assert ctl.snapshot()["in_use"] == 0
    assert not ctl.snapshot()["queued"]  # the loser left no ticket


def test_admission_cancel_while_queued():
    ctl = FairAdmissionController(1, RapidsConf())
    held = ctl.slot(None)
    qx = _qc(tenant="t")
    threading.Timer(0.05, qx.cancel).start()
    with pytest.raises(QueryCancelled) as ei:
        ctl.slot(qx)
    assert ei.value.reason == "user"
    held.release()
    assert ctl.snapshot()["in_use"] == 0


def test_admission_slow_admission_chaos_trips_timeout():
    """slow_admission chaos keys on the QUERY id and delays admission
    deterministically past the queue-time deadline."""
    ctl = FairAdmissionController(2, RapidsConf({
        "spark.rapids.query.admission.timeout": "0.1",
        "spark.rapids.tpu.test.injectFaults": "slow_admission:qslow:*:0.3",
    }))
    with pytest.raises(QueryCancelled) as ei:
        ctl.slot(_qc(query_id="qslow"))
    assert ei.value.reason == "admission"
    # non-matching query ids admit instantly
    ctl.slot(_qc(query_id="qfast")).release()
    assert ctl.snapshot()["in_use"] == 0


def test_exclusive_cleared_at_query_end_even_without_slot():
    """width-1 exclusivity set by a slotless (CPU-island) subtree must
    not outlive its query — clear_exclusive resumes grants."""
    ctl = FairAdmissionController(2, RapidsConf())
    qx = _qc(query_id="qdeg")
    ctl.await_exclusive(qx, timeout=0.01)  # in_use==0: returns at once
    assert ctl.snapshot()["exclusive"] == "qdeg"
    ctl.clear_exclusive("other-query")  # someone else's end: no-op
    assert ctl.snapshot()["exclusive"] == "qdeg"
    ctl.clear_exclusive("qdeg")
    assert ctl.snapshot()["exclusive"] is None
    ctl.slot(None).release()  # grants flow again


# --- cancel-aware pipeline --------------------------------------------------

def test_pipelined_map_cancels_at_consumer_and_unparks_feeder():
    tok = CancellationToken("qp")
    fed = []

    def items():
        for i in range(100):
            fed.append(i)
            yield i

    gen = pipelined_map(lambda x: x, items(), threads=1, window=2,
                        token=tok)
    assert next(gen) == 0
    tok.cancel("user", "enough")
    with pytest.raises(QueryCancelled):
        list(gen)
    time.sleep(0.2)  # feeder must die promptly, not fill the window
    assert len(fed) < 100


def test_pipelined_map_serial_path_checks_token():
    tok = CancellationToken("qs")
    tok.cancel("user")
    with pytest.raises(QueryCancelled):
        list(pipelined_map(lambda x: x, range(5), threads=0, token=tok))


# --- local query paths ------------------------------------------------------

def _frame(session, nbatches=40, rows=200):
    tbl = pa.Table.from_batches([
        pa.RecordBatch.from_arrays(
            [pa.array(np.arange(rows, dtype=np.int64))], names=["a"])
        for _ in range(nbatches)])
    return session.create_dataframe(tbl)


def test_local_user_cancel_releases_everything():
    s = TpuSession()
    qx = s.query_context()
    mm = DeviceMemoryManager.shared(s.conf)
    base_bytes = mm.device_bytes
    threading.Timer(0.05, qx.cancel).start()
    with pytest.raises(QueryCancelled) as ei:
        for _ in range(300):  # keep running queries until the cancel
            _frame(s).select("a").collect(qx)
    assert ei.value.reason == "user"
    assert mm.device_bytes == base_bytes  # zero ledger leakage
    snap = mm.admission.snapshot()
    assert snap["in_use"] == 0 and not snap["queued"]  # zero slot leakage


def test_local_deadline_cancel_classified_with_event_log(tmp_path):
    log_dir = str(tmp_path / "events")
    s = TpuSession({"spark.rapids.query.deadline": "0.0001",
                    "spark.rapids.eventLog.dir": log_dir})
    time.sleep(0.01)
    with pytest.raises(QueryCancelled) as ei:
        _frame(s).select("a").collect()
    assert ei.value.reason == "deadline"
    evs = [json.loads(line)
           for n in os.listdir(log_dir)
           for line in open(os.path.join(log_dir, n))]
    cancels = [e for e in evs if e.get("type") == "query_cancelled"]
    assert len(cancels) == 1 and cancels[0]["reason"] == "deadline"


def test_budget_action_cancel_classifies():
    s = TpuSession({"spark.rapids.query.memoryBudgetBytes": "1",
                    "spark.rapids.query.memoryBudget.action": "cancel"})
    with pytest.raises(QueryCancelled) as ei:
        _frame(s, nbatches=2).select("a").collect()
    assert ei.value.reason == "budget"
    assert "budget exceeded" in ei.value.detail


def test_budget_degrade_exhausts_to_budget_cancel():
    """action=degrade: the unsatisfiable budget walks the ladder and
    terminates as QueryCancelled(budget), not CPU fallback (the user
    asked for the bound, not a slower path around it)."""
    s = TpuSession({"spark.rapids.query.memoryBudgetBytes": "1",
                    "spark.rapids.sql.oomRetry.maxSplits": "1"})
    qx = s.query_context()
    with pytest.raises(QueryCancelled) as ei:
        _frame(s, nbatches=2).select("a").collect(qx)
    assert ei.value.reason == "budget"
    # the walk is visible: halving, then spill and width1 rungs
    assert qx.ladder.counts.get("spill", 0) >= 1
    assert qx.ladder.counts.get("width1", 0) >= 1


def test_oom_storm_walks_all_four_rungs_to_correct_result():
    """ISSUE acceptance: an injected OOM storm exhausts halving, the
    ladder walks spill -> width1 -> cpu, and the query still returns
    the correct answer (via the classified CPU fallback)."""
    s = TpuSession({"spark.rapids.sql.test.injectRetryOOM.storm": "200",
                    "spark.rapids.sql.oomRetry.maxSplits": "2"})
    qx = s.query_context()
    df = _frame(s, nbatches=1, rows=64)
    got = df.select("a").collect(qx)
    assert got.column(0).to_pylist() == list(range(64))
    for rung in ("halve", "spill", "width1", "cpu"):
        assert qx.ladder.counts.get(rung, 0) >= 1, qx.ladder.counts
    pp = df.select("a")._plan()
    pp.collect(qctx=s.query_context())  # plan path reusable afterwards


def test_ladder_metrics_and_query_cancelled_counter():
    from spark_rapids_tpu.lifecycle import QUERY_CANCELLED, QUERY_DEGRADED
    before = QUERY_CANCELLED.labels("user").value
    CancellationToken("qm").cancel("user")
    assert QUERY_CANCELLED.labels("user").value == before + 1
    b2 = QUERY_DEGRADED.labels("spill").value
    qx = _qc()
    qx.ladder.escalate()
    assert QUERY_DEGRADED.labels("spill").value == b2 + 1


# --- chaos grammar (query-scoped modes) -------------------------------------

def test_chaos_conf_overrides_oom_storm():
    from spark_rapids_tpu.scheduler.chaos import conf_overrides
    ov = conf_overrides("oom_storm:q1s1m0:0:6", 0, "q1s1m0", 0)
    assert ov == {"spark.rapids.sql.test.injectRetryOOM.storm": "6"}
    assert conf_overrides("oom_storm:q1s1m0:0:6", 0, "q1s1m0", 1) == {}
    assert conf_overrides("crash:q1s1m0:*", 0, "q1s1m0", 0) == {}


def test_chaos_spill_fault_modes_do_not_silently_collide():
    """spill_corrupt + spill_torn share the one injectSpillFault
    channel a manager has: both matching the same (task, attempt) is a
    contradictory spec and a named hard error (never a silent no-op),
    while disjoint task globs and repeated rules of ONE mode still
    compose / first-match-win."""
    from spark_rapids_tpu.scheduler.chaos import conf_overrides
    with pytest.raises(ValueError,
                       match="spill_corrupt.*spill_torn"):
        conf_overrides("spill_corrupt:q1r0:*;spill_torn:q1r0:*",
                       0, "q1r0", 0)
    spec = "spill_corrupt:q1r0:*;spill_torn:q2r0:*"
    assert conf_overrides(spec, 0, "q1r0", 0) == {
        "spark.rapids.memory.test.injectSpillFault": "corrupt"}
    assert conf_overrides(spec, 0, "q2r0", 0) == {
        "spark.rapids.memory.test.injectSpillFault": "torn"}
    assert conf_overrides("spill_torn:q1r0:*;spill_torn:q1r0:*",
                          0, "q1r0", 0) == {
        "spark.rapids.memory.test.injectSpillFault": "torn"}


def test_chaos_hang_query_returns_after_bound_without_cancel(tmp_path):
    from spark_rapids_tpu.scheduler.chaos import maybe_inject
    t0 = time.monotonic()
    maybe_inject("hang_query:t1:*:0.1", 0, "t1", 0,
                 cancel_path=str(tmp_path / "none.cancel"))
    assert 0.1 <= time.monotonic() - t0 < 2.0


def test_chaos_hang_query_raises_classified_on_marker(tmp_path):
    from spark_rapids_tpu.scheduler.chaos import maybe_inject
    marker = str(tmp_path / "q.cancel")
    with open(marker, "w") as f:
        f.write("deadline driver said so")
    with pytest.raises(QueryCancelled) as ei:
        maybe_inject("hang_query:t1:*:30", 0, "t1", 0,
                     cancel_path=marker)
    assert ei.value.reason == "deadline"


# --- process-cluster cancel paths -------------------------------------------

def _cluster_plan(nparts=3):
    from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec
    from spark_rapids_tpu.exec.base import HostBatchSourceExec
    from spark_rapids_tpu.exec.basic import TpuProjectExec
    from spark_rapids_tpu.exec.exchange import TpuShuffleExchangeExec
    from spark_rapids_tpu.expr import Alias, UnresolvedColumn as col
    from spark_rapids_tpu.expr.aggregates import Sum
    from spark_rapids_tpu.expr.base import bind_expr
    from spark_rapids_tpu.shuffle.partitioner import HashPartitioning
    rng = np.random.default_rng(7)
    rb = pa.record_batch({
        "k": pa.array((np.arange(4000) % 7).astype(np.int32)),
        "v": pa.array(rng.integers(0, 100, 4000).astype(np.int64))})
    src = HostBatchSourceExec([rb.slice(0, 2000), rb.slice(2000)])
    # a projection in the map stage so worker-side batches run through
    # the retry scope (budget checks live there)
    proj = TpuProjectExec(
        [bind_expr(col("k"), src.output_schema),
         bind_expr(col("v"), src.output_schema)], src)
    ex = TpuShuffleExchangeExec(HashPartitioning([col("k")], nparts),
                                proj)
    return TpuHashAggregateExec([col("k")],
                                [Alias(Sum(col("v")), "t")], ex)


def _sched_cancel_events(sched):
    return [e for e in sched.events if e["event"] == "query_cancelled"]


def test_cluster_user_cancel_midstage_no_leaks(tmp_path):
    """ISSUE satellite: cancel mid-stage on a 2-worker process
    cluster — zero ledger leakage (worker gauges via the metrics
    rendezvous), zero admission-slot leakage, classified user cancel,
    and a post-cancel query that runs green on the same cluster."""
    from spark_rapids_tpu.cluster import TpuProcessCluster
    from spark_rapids_tpu.obs.metrics import read_worker_metrics
    conf = RapidsConf({
        # hold every final-stage task until the cancel lands
        "spark.rapids.tpu.test.injectFaults": "hang_query:q1r*:*:60",
        "spark.rapids.metrics.enabled": "true",
        "spark.rapids.query.cancel.joinTimeout": "10",
    })
    plan = _cluster_plan()
    with TpuProcessCluster(n_workers=2, conf=conf) as c:
        canceller = threading.Timer(
            1.0, lambda: c.cancel_running("operator hit ctrl-c"))
        canceller.start()
        with pytest.raises(QueryCancelled) as ei:
            c.run_query(plan)
        canceller.cancel()
        assert ei.value.reason == "user"
        sched = c.last_scheduler
        assert _sched_cancel_events(sched)
        # zero admission-slot leakage on the driver
        snap = DeviceMemoryManager.shared(conf).admission.snapshot()
        assert snap["in_use"] == 0 and not snap["queued"]
        # zero ledger leakage in the workers: the error-path metric
        # flush records each worker's ledger AFTER the reap
        time.sleep(1.0)
        for tag, ms in read_worker_metrics(c.root):
            fam = ms.get("rapids_memory_device_bytes_in_use")
            if fam:
                for _, v in fam["samples"].items():
                    assert v == 0, (tag, v)
        # the same cluster is not poisoned: a clean query runs green
        got = c.run_query(plan, conf=RapidsConf({}))
        assert got.num_rows == 7


def test_cluster_deadline_cancel_with_incident(tmp_path):
    """Deadline-exceeded under hang_query: classified deadline cancel,
    exactly one query_cancelled event-log line, and exactly one
    incident bundle, which the schema checker accepts."""
    from asserts import obs_checker
    from spark_rapids_tpu.cluster import TpuProcessCluster
    log_dir = str(tmp_path / "events")
    flight_dir = str(tmp_path / "flight")
    conf = RapidsConf({
        "spark.rapids.query.deadline": "2.0",
        "spark.rapids.tpu.test.injectFaults": "hang_query:q1r*:*:60",
        "spark.rapids.eventLog.dir": log_dir,
        "spark.rapids.flight.dir": flight_dir,
    })
    plan = _cluster_plan()
    with TpuProcessCluster(n_workers=2, conf=conf) as c:
        with pytest.raises(QueryCancelled) as ei:
            c.run_query(plan)
        assert ei.value.reason == "deadline"
        assert c.last_incident_path \
            and os.path.exists(c.last_incident_path)
        with open(c.last_incident_path) as f:
            bundle = json.load(f)
        assert any(a["kind"] == "query_cancelled"
                   for a in bundle["anomalies"])
        assert os.listdir(flight_dir) == \
            [os.path.basename(c.last_incident_path)]
        # under load the deadline can beat the map stage's first
        # allocation: then the bundle has no memory event, and no more
        assert set(obs_checker().check_flight(c.last_incident_path)) \
            <= {"memory timeline empty"}
    evs = [json.loads(line)
           for n in os.listdir(log_dir)
           for line in open(os.path.join(log_dir, n))]
    cancels = [e for e in evs if e.get("type") == "query_cancelled"]
    assert len(cancels) == 1 and cancels[0]["reason"] == "deadline"


def test_cluster_admission_and_budget_reasons(tmp_path):
    """The remaining two classified reasons on the process cluster:
    slow_admission chaos trips the queue-time deadline (admission),
    and a 1-byte budget with action=cancel classifies from the worker
    through the .qcancel marker (budget)."""
    from spark_rapids_tpu.cluster import TpuProcessCluster
    plan = _cluster_plan()
    adm_conf = RapidsConf({
        "spark.rapids.query.admission.timeout": "0.2",
        "spark.rapids.tpu.test.injectFaults": "slow_admission:q1:*:1.0",
    })
    with TpuProcessCluster(n_workers=2, conf=adm_conf) as c:
        with pytest.raises(QueryCancelled) as ei:
            c.run_query(plan)
        assert ei.value.reason == "admission"
        assert _sched_cancel_events(c.last_scheduler)
        snap = DeviceMemoryManager.shared(adm_conf).admission.snapshot()
        assert snap["in_use"] == 0 and not snap["queued"]
    bud_conf = RapidsConf({
        "spark.rapids.query.memoryBudgetBytes": "1",
        "spark.rapids.query.memoryBudget.action": "cancel",
    })
    with TpuProcessCluster(n_workers=2, conf=bud_conf) as c:
        with pytest.raises(QueryCancelled) as ei:
            c.run_query(plan)
        assert ei.value.reason == "budget"
        ev = _sched_cancel_events(c.last_scheduler)
        assert ev and "[budget]" in ev[0]["reason"]
        snap = DeviceMemoryManager.shared(bud_conf).admission.snapshot()
        assert snap["in_use"] == 0
        # cancelling after the query already finished is a no-op, not
        # phantom cancel evidence
        assert c.cancel_running() is False
    # the DEFAULT budget action (degrade) must also classify on the
    # cluster: workers have no ladder, so budget exhaustion after the
    # halving budget classifies via the .qcancel marker — never an
    # unclassified retry storm that blacklists healthy workers
    deg_conf = RapidsConf({
        "spark.rapids.query.memoryBudgetBytes": "1",
        "spark.rapids.sql.oomRetry.maxSplits": "1",
    })
    with TpuProcessCluster(n_workers=2, conf=deg_conf) as c:
        with pytest.raises(QueryCancelled) as ei:
            c.run_query(plan)
        assert ei.value.reason == "budget"
        sched = c.last_scheduler
        assert _sched_cancel_events(sched)
        assert not sched.blacklist  # cooperative stop blames no worker


# --- registered timeout confs (satellite) -----------------------------------

def test_shuffle_close_join_timeout_is_a_conf():
    from spark_rapids_tpu.config import (SHUFFLE_CLOSE_JOIN_TIMEOUT,
                                         WORKER_EXIT_TIMEOUT)
    assert SHUFFLE_CLOSE_JOIN_TIMEOUT.key == \
        "spark.rapids.shuffle.close.joinTimeout"
    assert RapidsConf({SHUFFLE_CLOSE_JOIN_TIMEOUT.key: "0.25"}).get(
        SHUFFLE_CLOSE_JOIN_TIMEOUT) == 0.25
    assert RapidsConf().get(WORKER_EXIT_TIMEOUT) == 10.0
    # the transport reads the conf (not a literal) at close time
    from spark_rapids_tpu.shuffle.host import HostShuffleTransport
    t = HostShuffleTransport(RapidsConf(
        {SHUFFLE_CLOSE_JOIN_TIMEOUT.key: "0.25"}), threads=2)
    t.close()  # no outstanding writes: returns immediately
