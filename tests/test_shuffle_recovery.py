"""Shuffle durability chaos matrix: real worker OS processes, committed
map output damaged AFTER its atomic commit (corrupt / drop / eio /
crash-then-loss via spark.rapids.tpu.test.injectFaults), and the query
must still return bit-exact oracle results through classified fetch
failures and map-stage re-execution — bounded by
spark.rapids.shuffle.maxStageRetries. Integrity/classification unit
tests (no processes) live in test_shuffle_integrity.py."""
import json
import os

import pyarrow as pa
import pytest

from asserts import obs_checker
from data_gen import IntegerGen, LongGen, gen_table

from spark_rapids_tpu.cluster import TpuProcessCluster
from spark_rapids_tpu.config import RapidsConf
from spark_rapids_tpu.exec.base import ExecCtx, HostBatchSourceExec
from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec
from spark_rapids_tpu.exec.exchange import TpuShuffleExchangeExec
from spark_rapids_tpu.expr import Alias, UnresolvedColumn as col
from spark_rapids_tpu.expr.aggregates import Count, Sum
from spark_rapids_tpu.shuffle.partitioner import HashPartitioning


def _oracle(plan):
    rbs = list(plan.execute_cpu(ExecCtx()))
    from spark_rapids_tpu.columnar.arrow_bridge import arrow_schema
    return pa.Table.from_batches(rbs,
                                 schema=arrow_schema(plan.output_schema))


def _rows(table):
    return sorted(table.to_pylist(), key=lambda d: tuple(
        (v is None, str(v)) for v in d.values()))


def _agg_plan(nparts=4):
    rbs = [gen_table([IntegerGen(min_val=0, max_val=20, null_frac=0.1),
                      LongGen(nullable=False)], n, seed=s,
                     names=["k", "v"])
           for n, s in [(300, 1), (250, 2)]]
    src = HostBatchSourceExec(rbs)
    exch = TpuShuffleExchangeExec(HashPartitioning([col("k")], nparts),
                                  src)
    return TpuHashAggregateExec(
        [col("k")], [Alias(Sum(col("v")), "s"),
                     Alias(Count(col("v")), "c")], exch)


def _events(sched, kind, task=None):
    return [e for e in sched.events if e["event"] == kind
            and (task is None or e["task"] == task)]


def _run(conf_extra, plan=None, n_workers=2):
    plan = plan or _agg_plan()
    conf = RapidsConf(conf_extra)
    with TpuProcessCluster(n_workers=n_workers, conf=conf) as c:
        got = c.run_query(plan)
        return (got, c.last_scheduler, c.last_incident_path,
                c.prometheus_text())


def test_corrupt_committed_output_recovers_via_stage_rerun(tmp_path):
    """ISSUE acceptance (kind=corrupt): bit-rotted committed map output
    is caught by CRC, classified, quarantined, and regenerated by
    exactly one lineage rerun; the reduce task is never blamed."""
    log_dir = str(tmp_path / "events")
    plan = _agg_plan()
    got, sched, incident, prom = _run({
        "spark.rapids.tpu.test.injectFaults": "corrupt:q1s1m0:0",
        "spark.rapids.eventLog.dir": log_dir,
        "spark.rapids.metrics.enabled": "true",
        "spark.rapids.flight.dir": str(tmp_path / "incidents"),
    }, plan)
    assert _rows(got) == _rows(_oracle(plan))
    ffs = _events(sched, "fetch_failed")
    assert len(ffs) == 1 and "[corrupt]" in ffs[0]["reason"]
    assert "q1s1m0" in ffs[0]["reason"]
    reruns = _events(sched, "stage_rerun", "q1s1m0")
    assert len(reruns) == 1
    # the rerun ran under a fresh attempt number (a chaos rule pinned
    # to attempt 0 must not re-corrupt the regenerated output)
    oks = _events(sched, "task_ok", "q1s1m0")
    assert [e["attempt"] for e in oks] == [0, 1]
    # no ordinary task failure, no blacklist, no respawn: fetch
    # failures blame the DATA, not the reader or its worker
    assert not _events(sched, "task_failed")
    assert sched.summary()["fetch_failures"] == 1
    assert sched.summary()["stage_reruns"] == 1
    # classified by kind in the (worker-flushed) metrics
    assert 'rapids_shuffle_fetch_failures_total{kind="corrupt"' in prom
    assert 'rapids_shuffle_stage_reruns_total{proc="driver"} 1' in prom
    # forensics: the incident bundle names the fetch failure + rerun
    assert incident and os.path.exists(incident)
    with open(incident) as f:
        bundle = json.load(f)
    kinds = {a["kind"] for a in bundle["anomalies"]}
    assert {"fetch_failed", "stage_rerun"} <= kinds
    assert obs_checker().check_flight(incident) == []
    # ... and the persisted event log carries the recovery timeline
    evs = [json.loads(line) for p in os.listdir(log_dir)
           for line in open(os.path.join(log_dir, p))]
    sched_evs = [e for e in evs if e.get("type") == "scheduler"]
    assert sched_evs and sched_evs[0]["summary"]["stage_reruns"] == 1
    assert any(a["event"] == "fetch_failed"
               for e in sched_evs for a in e["attempts"])


def test_dropped_output_plus_worker_crash_both_recover():
    """Committed-then-lost (kind=missing, the executor-loss analog)
    composed with a worker crash on the sibling map task: worker-loss
    retry and lineage rerun coexist in one query."""
    plan = _agg_plan()
    got, sched, _, _ = _run({
        "spark.rapids.tpu.test.injectFaults":
            "drop:q1s1m0:0;crash:q1s1m1:0",
    }, plan)
    assert _rows(got) == _rows(_oracle(plan))
    # the crash path: m1's attempt 0 died with its worker and retried
    failed = _events(sched, "task_failed", "q1s1m1")
    assert failed and "worker died" in failed[0]["reason"]
    assert _events(sched, "worker_respawn")
    # the durability path: m0's committed dir vanished -> missing
    ffs = _events(sched, "fetch_failed")
    assert len(ffs) == 1 and "[missing]" in ffs[0]["reason"]
    assert len(_events(sched, "stage_rerun", "q1s1m0")) == 1


def test_transient_eio_recovers_in_place_without_rerun():
    """kind=io below the retry budget: the reader's bounded in-place
    retries absorb the fault — no fetch failure reaches the driver,
    no stage rerun, no wasted map work."""
    plan = _agg_plan()
    got, sched, _, _ = _run({
        "spark.rapids.tpu.test.injectFaults": "eio:q1s1m*:0:2",
        "spark.rapids.shuffle.fetch.maxRetries": 4,
        "spark.rapids.shuffle.fetch.retryWaitMs": 5,
    }, plan)
    assert _rows(got) == _rows(_oracle(plan))
    assert not _events(sched, "fetch_failed")
    assert not _events(sched, "stage_rerun")
    assert not _events(sched, "task_failed")


def test_persistent_eio_escalates_to_stage_rerun():
    """kind=io above the retry budget: in-place retries exhaust, the
    failure escalates, and the rerun's fresh output (no sidecars on a
    new attempt) completes the query."""
    plan = _agg_plan()
    got, sched, _, _ = _run({
        "spark.rapids.tpu.test.injectFaults": "eio:q1s1m0:0:50",
        "spark.rapids.shuffle.fetch.maxRetries": 1,
        "spark.rapids.shuffle.fetch.retryWaitMs": 5,
    }, plan)
    assert _rows(got) == _rows(_oracle(plan))
    ffs = _events(sched, "fetch_failed")
    assert ffs and "[io]" in ffs[0]["reason"]
    assert len(_events(sched, "stage_rerun", "q1s1m0")) == 1


def test_rerun_budget_exhaustion_fails_query():
    """maxStageRetries bounds recovery: with a zero budget the first
    classified loss is fatal, with the budget named in the error."""
    plan = _agg_plan()
    conf = RapidsConf({
        "spark.rapids.tpu.test.injectFaults": "drop:q1s1m0:0",
        "spark.rapids.shuffle.maxStageRetries": 0,
    })
    with TpuProcessCluster(n_workers=2, conf=conf) as c:
        with pytest.raises(RuntimeError, match="maxStageRetries"):
            c.run_query(plan)
        sched = c.last_scheduler
    assert _events(sched, "fetch_failed")
    assert not _events(sched, "stage_rerun")


def test_deterministic_task_error_fails_fast_without_rerun():
    """A task that fails deterministically (not a fetch failure) keeps
    the PR-1 semantics: bounded task retries then a hard error — the
    lineage machinery must not spin map reruns for it."""
    from spark_rapids_tpu import datatypes as dt
    from spark_rapids_tpu.io.scan import TpuFileScanExec
    schema = dt.Schema([dt.StructField("x", dt.INT64, True)])
    missing = TpuFileScanExec(["/nonexistent/x.parquet"], schema=schema)
    exch = TpuShuffleExchangeExec(HashPartitioning([col("x")], 2),
                                  missing)
    plan = TpuHashAggregateExec([], [Alias(Count(col("x")), "c")], exch)
    conf = RapidsConf({"spark.rapids.tpu.task.maxAttempts": 2})
    with TpuProcessCluster(n_workers=2, conf=conf) as c:
        with pytest.raises(RuntimeError,
                           match="failed after 2 attempts"):
            c.run_query(plan)
        sched = c.last_scheduler
    assert not _events(sched, "fetch_failed")
    assert not _events(sched, "stage_rerun")
