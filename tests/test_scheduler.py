"""Fault-tolerance integration tests: real worker OS processes under
deterministic chaos (spark.rapids.tpu.test.injectFaults). Each test
drives a recovery path end to end — crash mid-map, hang past the
heartbeat, straggler speculation with a zombie commit race — and checks
results against the CPU oracle / a no-fault run, plus the attempt
timeline the scheduler records for the event log. State-machine unit
tests (no processes) live in test_scheduler_unit.py."""
import json
import os

import numpy as np
import pyarrow as pa
import pytest

from data_gen import IntegerGen, LongGen, gen_table

from spark_rapids_tpu.cluster import TpuProcessCluster
from spark_rapids_tpu.config import RapidsConf
from spark_rapids_tpu.exec.base import ExecCtx, HostBatchSourceExec
from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec
from spark_rapids_tpu.exec.exchange import TpuShuffleExchangeExec
from spark_rapids_tpu.exec.joins import TpuShuffledHashJoinExec
from spark_rapids_tpu.expr import Alias, UnresolvedColumn as col
from spark_rapids_tpu.expr.aggregates import Count, Sum
from spark_rapids_tpu.shuffle.partitioner import HashPartitioning


def _oracle(plan):
    rbs = list(plan.execute_cpu(ExecCtx()))
    from spark_rapids_tpu.columnar.arrow_bridge import arrow_schema
    return pa.Table.from_batches(rbs, schema=arrow_schema(
        plan.output_schema))


def _rows(table):
    return sorted(table.to_pylist(), key=lambda d: tuple(
        (v is None, str(v)) for v in d.values()))


def _join_agg_plan(nparts=3, seed=5):
    """The acceptance query: 2-stage (map shuffles + reduce join/agg)
    fact x dim join, two batches per side so both map stages split
    across workers."""
    rng = np.random.default_rng(seed)
    n_f, n_d = 2000, 64
    fact = pa.record_batch({
        "fk": pa.array(rng.integers(0, n_d, n_f).astype(np.int32)),
        "amt": pa.array(rng.integers(1, 100, n_f).astype(np.int64)),
    })
    dim = pa.record_batch({
        "dk": pa.array(np.arange(n_d, dtype=np.int32)),
        "grp": pa.array((np.arange(n_d) % 7).astype(np.int32)),
    })
    fact_src = HostBatchSourceExec([fact.slice(0, 1100), fact.slice(1100)])
    dim_src = HostBatchSourceExec([dim.slice(0, 40), dim.slice(40)])
    lex = TpuShuffleExchangeExec(HashPartitioning([col("fk")], nparts),
                                 fact_src)
    rex = TpuShuffleExchangeExec(HashPartitioning([col("dk")], nparts),
                                 dim_src)
    join = TpuShuffledHashJoinExec([col("fk")], [col("dk")], "inner",
                                   lex, rex)
    # the agg groups by a NON-join key: distributed execution needs the
    # re-partition exchange Spark would plan here
    gex = TpuShuffleExchangeExec(HashPartitioning([col("grp")], nparts),
                                 join)
    return TpuHashAggregateExec(
        [col("grp")], [Alias(Sum(col("amt")), "total"),
                       Alias(Count(col("amt")), "n")], gex)


def _events(sched, kind, task=None):
    return [e for e in sched.events if e["event"] == kind
            and (task is None or e["task"] == task)]


def test_chaos_crash_midmap_join_completes(tmp_path):
    """ISSUE acceptance: a worker killed during the map stage of a
    2-stage join query; the query completes with correct results, the
    retry is in the event log, and speculation stayed off (default)."""
    log_dir = str(tmp_path / "events")
    conf = RapidsConf({
        "spark.rapids.tpu.test.injectFaults": "crash:q1s1m0:0",
        "spark.rapids.eventLog.dir": log_dir,
    })
    plan = _join_agg_plan()
    with TpuProcessCluster(n_workers=2, conf=conf) as c:
        got = c.run_query(plan)
        sched = c.last_scheduler
    want = _oracle(plan)  # == the no-fault run (see test below)
    assert _rows(got) == _rows(want)
    # the crash was detected as a worker death and retried
    failed = _events(sched, "task_failed", "q1s1m0")
    assert failed and "worker died" in failed[0]["reason"]
    ok = _events(sched, "task_ok", "q1s1m0")
    assert ok and ok[0]["attempt"] >= 1
    assert _events(sched, "worker_respawn")
    # speculation is opt-in; the default run must not duplicate tasks
    assert not _events(sched, "speculative_attempt")
    # ... and the retry made it into the persisted event log
    files = [os.path.join(log_dir, n) for n in os.listdir(log_dir)]
    evs = [json.loads(line) for p in files for line in open(p)]
    sched_evs = [e for e in evs if e.get("type") == "scheduler"]
    assert sched_evs and sched_evs[0]["summary"]["failures"] >= 1
    assert any(a["event"] == "task_ok" and a["task"] == "q1s1m0"
               and a["attempt"] >= 1
               for e in sched_evs for a in e["attempts"])


def test_no_fault_run_matches_oracle_and_is_deterministic():
    """Regression guard: with the scheduler on and no faults, a clean
    run matches the CPU oracle and two runs are byte-identical."""
    plan = _join_agg_plan()
    with TpuProcessCluster(n_workers=2) as c:
        got1 = c.run_query(plan)
        sched = c.last_scheduler
        got2 = c.run_query(plan)
    assert _rows(got1) == _rows(_oracle(plan))
    # byte-identical across runs: same stage split, same commit layout
    sink1, sink2 = pa.BufferOutputStream(), pa.BufferOutputStream()
    for t, sink in ((got1, sink1), (got2, sink2)):
        with pa.ipc.new_stream(sink, t.schema) as w:
            w.write_table(t)
    assert sink1.getvalue().equals(sink2.getvalue())
    # a clean run has no retries, respawns, or speculation
    assert not _events(sched, "task_failed")
    assert not _events(sched, "worker_respawn")
    assert not _events(sched, "speculative_attempt")


def test_chaos_hang_past_heartbeat_recovers():
    """A worker that wedges (heartbeat suspended, task never finishes)
    is detected by heartbeat staleness, killed, respawned, and its task
    retried."""
    conf = RapidsConf({
        "spark.rapids.tpu.test.injectFaults": "hang:q1s1m0:0",
        "spark.rapids.tpu.heartbeat.interval": 0.2,
        "spark.rapids.tpu.heartbeat.timeout": 5.0,
    })
    rbs = [gen_table([IntegerGen(min_val=0, max_val=20, null_frac=0.1),
                      LongGen(nullable=False)], n, seed=s,
                     names=["k", "v"])
           for n, s in [(300, 1), (250, 2)]]
    src = HostBatchSourceExec(rbs)
    exch = TpuShuffleExchangeExec(HashPartitioning([col("k")], 4), src)
    plan = TpuHashAggregateExec(
        [col("k")], [Alias(Sum(col("v")), "s")], exch)
    with TpuProcessCluster(n_workers=2, conf=conf) as c:
        got = c.run_query(plan)
        sched = c.last_scheduler
    assert _rows(got) == _rows(_oracle(plan))
    failed = _events(sched, "task_failed", "q1s1m0")
    assert failed and "heartbeat stale" in failed[0]["reason"]
    assert _events(sched, "worker_respawn")
    assert _events(sched, "task_ok", "q1s1m0")[0]["attempt"] >= 1


def test_chaos_delay_speculation_zombie_commit():
    """Straggler mitigation end to end: a delayed map attempt triggers a
    speculative duplicate; both eventually produce full output, the
    commit protocol keeps exactly one, and the result has no duplicated
    rows."""
    conf = RapidsConf({
        "spark.rapids.tpu.test.injectFaults": "delay:q1s1m0:0:8.0",
        "spark.rapids.tpu.speculation": "true",
        "spark.rapids.tpu.speculation.multiplier": 1.5,
        "spark.rapids.tpu.speculation.minRuntime": 2.0,
    })
    rbs = [gen_table([IntegerGen(min_val=0, max_val=20, null_frac=0.1),
                      LongGen(nullable=False)], n, seed=s,
                     names=["k", "v"])
           for n, s in [(300, 1), (250, 2), (411, 3)]]
    src = HostBatchSourceExec(rbs)
    exch = TpuShuffleExchangeExec(HashPartitioning([col("k")], 4), src)
    plan = TpuHashAggregateExec(
        [col("k")], [Alias(Sum(col("v")), "s"),
                     Alias(Count(col("v")), "c")], exch)
    with TpuProcessCluster(n_workers=2, conf=conf) as c:
        got = c.run_query(plan)
        sched = c.last_scheduler
        shuffle_dir = os.path.join(c.root, "shuffle", "s1")
        committed = [n for n in os.listdir(shuffle_dir)
                     if n.startswith("q1s1m0") and n.endswith(".mapout")]
        staging = [n for n in os.listdir(shuffle_dir)
                   if n.startswith("q1s1m0") and ".staging" in n]
        # duplicate attempts may still be in flight; the visible state
        # must be exactly one committed dir for the task
        assert len(committed) == 1
        assert _rows(got) == _rows(_oracle(plan))
    assert _events(sched, "speculative_attempt", "q1s1m0")
    assert len(_events(sched, "task_ok", "q1s1m0")) == 1
    del staging  # may or may not still exist mid-race; not asserted


def test_persistent_task_failure_exhausts_attempts():
    """A task that fails deterministically on every worker raises after
    maxAttempts with the worker traceback, and the failing workers got
    blacklisted along the way."""
    from spark_rapids_tpu import datatypes as dt
    from spark_rapids_tpu.io.scan import TpuFileScanExec
    conf = RapidsConf({
        "spark.rapids.tpu.task.maxAttempts": 2,
        "spark.rapids.tpu.scheduler.maxTaskFailuresPerWorker": 1,
    })
    schema = dt.Schema([dt.StructField("x", dt.INT64, True)])
    missing = TpuFileScanExec(["/nonexistent/x.parquet"], schema=schema)
    exch = TpuShuffleExchangeExec(HashPartitioning([col("x")], 2),
                                  missing)
    plan = TpuHashAggregateExec([], [Alias(Count(col("x")), "c")], exch)
    with TpuProcessCluster(n_workers=2, conf=conf) as c:
        with pytest.raises(RuntimeError,
                           match="worker task .* failed after 2 attempts"):
            c.run_query(plan)
        sched = c.last_scheduler
    assert len(_events(sched, "task_failed")) == 2
    assert _events(sched, "worker_blacklisted")


def test_aqe_wrapped_plan_runs_on_cluster():
    """ADVICE r5 satellite: planner-built plans (AQE on by default) wrap
    exchanges in TpuAQEShuffleReadExec; run_query must strip them
    instead of dying on ProcessShuffleReadExec.materialize."""
    from spark_rapids_tpu.exec.aqe import TpuAQEShuffleReadExec
    from spark_rapids_tpu.planner import overrides
    rbs = [gen_table([IntegerGen(min_val=0, max_val=9, null_frac=0.0),
                      LongGen(nullable=False)], 200, seed=s,
                     names=["k", "v"]) for s in (1, 2)]
    src = HostBatchSourceExec(rbs)
    exch = TpuShuffleExchangeExec(HashPartitioning([col("k")], 3), src)
    plan = TpuHashAggregateExec(
        [col("k")], [Alias(Sum(col("v")), "s")], exch)
    pp = overrides(plan, RapidsConf())  # AQE defaults on
    assert any(isinstance(n, TpuAQEShuffleReadExec)
               for n in _walk(pp.root)), "planner no longer wraps; " \
        "update this test's premise"
    with TpuProcessCluster(n_workers=2) as c:
        got = c.run_query(pp.root)
    assert _rows(got) == _rows(_oracle(plan))


def test_aqe_topn_over_shuffle_on_cluster():
    """TopN wires an internal pipeline to its child at construction:
    stripping the AQE reader / swapping in ProcessShuffleReadExec must
    go through with_new_children or TopN executes the stale child.
    One reduce partition — a global TopN is only partition-local-safe
    when the final stage is a single task."""
    from spark_rapids_tpu.cluster import _strip_aqe_reads
    from spark_rapids_tpu.exec.aqe import TpuAQEShuffleReadExec
    from spark_rapids_tpu.exec.sort import SortOrder, TpuTopNExec

    def build(nparts):
        rbs = [gen_table([IntegerGen(min_val=0, max_val=999,
                                     null_frac=0.0),
                          LongGen(nullable=False)], 300, seed=s,
                         names=["k", "v"]) for s in (3, 4)]
        src = HostBatchSourceExec(rbs)
        exch = TpuShuffleExchangeExec(
            HashPartitioning([col("k")], nparts), src)
        return exch, TpuTopNExec(
            10, [SortOrder(col("v"), ascending=False)],
            TpuAQEShuffleReadExec(exch))

    # wiring: after the strip, TopN's INTERNAL pipeline (not just
    # .children) must chain down to the exchange, not the AQE reader
    exch, plan = build(3)
    stripped = _strip_aqe_reads(plan)
    internal = list(_walk(stripped._out))
    assert not any(isinstance(n, TpuAQEShuffleReadExec)
                   for n in internal)
    assert any(n is exch for n in internal)

    # end to end: distributed run matches the in-process oracle
    exch1, plan1 = build(1)
    oracle_plan = TpuTopNExec(10, [SortOrder(col("v"), ascending=False)],
                              exch1)
    with TpuProcessCluster(n_workers=2) as c:
        got = c.run_query(plan1)
    assert _rows(got) == _rows(_oracle(oracle_plan))


def _walk(node):
    yield node
    for ch in getattr(node, "children", ()):
        yield from _walk(ch)


# --- spill-tier durability under chaos (PR 12) -----------------------------

def _spilling_sort(tmp_path, faults):
    """A reduce-side global sort under budgets tiny enough that it goes
    out-of-core: its runs walk device -> host -> sealed disk files and
    are read back (verified) during the k-way merge. Returns the plan
    and a conf with every evidence directory under ``tmp_path``."""
    from spark_rapids_tpu.exec.sort import SortOrder, TpuSortExec
    conf = RapidsConf({
        "spark.rapids.tpu.test.injectFaults": faults,
        "spark.rapids.memory.device.budgetBytes": 1 << 14,
        "spark.rapids.memory.host.spillStorageSize": 1 << 12,
        "spark.rapids.memory.spillDir": str(tmp_path / "spill"),
        "spark.rapids.eventLog.dir": str(tmp_path / "events"),
        "spark.rapids.flight.dir": str(tmp_path / "incidents"),
        "spark.rapids.warehouse.dir": str(tmp_path / "warehouse"),
        "spark.rapids.metrics.enabled": "true",  # workers flush deltas
    })
    rng = np.random.default_rng(7)
    rbs = [pa.record_batch({
        "k": pa.array(rng.integers(0, 1 << 30, 1200).astype(np.int64)),
        "v": pa.array(rng.integers(0, 1000, 1200).astype(np.int64)),
    }) for _ in range(4)]
    plan = TpuSortExec(
        [SortOrder(col("k"))],
        TpuShuffleExchangeExec(HashPartitioning([col("v")], 1),
                               HostBatchSourceExec(rbs)))
    return plan, conf


def _leaked_spill_files(tmp_path):
    spill_dir = str(tmp_path / "spill")
    leftovers = []
    if os.path.isdir(spill_dir):
        for ns in os.listdir(spill_dir):
            leftovers += [f for f in os.listdir(os.path.join(
                spill_dir, ns)) if f.endswith(".arrow")]
    return leftovers


@pytest.mark.parametrize("mode,kind", [("spill_corrupt", "corrupt"),
                                       ("spill_torn", "torn")])
def test_chaos_spill_damage_classified_retry_no_blacklist(
        tmp_path, mode, kind):
    """PR 12 acceptance: a worker whose committed spill files rot
    (chaos ``spill_corrupt``) fails its attempt CLASSIFIED — the
    SpillReadError rides a structured ``.spillfail`` marker — and the
    scheduler retries the task WITHOUT blacklisting the reading worker
    (bit rot is not a process fault; re-execution regenerates the
    data). The retry (no injection at attempt 1) goes green, the query
    matches the oracle, the incident bundle carries the
    spill_read_failed anomaly, the query's ONE warehouse row says
    completed with its spill bytes, and no live incarnation spill dir
    leaks files."""
    from spark_rapids_tpu.obs.warehouse import read_rows
    plan, conf = _spilling_sort(tmp_path, f"{mode}:q1r*:0")
    with TpuProcessCluster(n_workers=2, conf=conf) as c:
        got = c.run_query(plan)
        sched = c.last_scheduler
        bundle = c.last_incident_path
    assert _rows(got) == _rows(_oracle(plan))
    # the loss was classified, not a raw OSError/ArrowInvalid task error
    spill_fails = _events(sched, "spill_read_failed")
    assert spill_fails, "spill_corrupt never bit a reduce task"
    assert f"[spill {kind}]" in spill_fails[0]["reason"]
    # the reading worker is never blamed
    assert not sched.blacklist
    assert not _events(sched, "worker_blacklisted")
    # the task re-ran and went green elsewhere/next attempt
    task = spill_fails[0]["task"]
    ok = _events(sched, "task_ok", task)
    assert ok and ok[0]["attempt"] >= 1
    # forensics: the bundle names the classified anomaly
    assert bundle and os.path.exists(bundle)
    kinds = {a["kind"] for a in json.load(open(bundle))["anomalies"]}
    assert "spill_read_failed" in kinds, kinds
    (row,) = read_rows(str(tmp_path / "warehouse"))
    assert row["outcome"] == "completed"
    assert sum(int(v or 0) for v in row["spill"].values()) > 0, row["spill"]
    assert _leaked_spill_files(tmp_path) == []


def test_chaos_disk_full_completes_green_with_classified_pressure(
        tmp_path):
    """Every disk-spill write of the reduce task hits injected ENOSPC
    (chaos ``disk_full``): refused writes leave batches host-resident,
    so the query completes and NO task fails; the event log carries
    ``disk_pressure`` (kind enospc), exactly one incident bundle names
    the anomaly, the cluster's boot-time sweep reclaims a planted dead
    incarnation's spill namespace, and no live namespace leaks."""
    import subprocess

    from asserts import obs_checker
    from spark_rapids_tpu.memory import _hostname
    from spark_rapids_tpu.tools.event_log import read_event_logs
    plan, conf = _spilling_sort(tmp_path, "disk_full:q1r*:*:99")
    p = subprocess.Popen(["true"])
    p.wait()  # reaped: the pid is provably dead
    orphan = tmp_path / "spill" / f"{_hostname()}-{p.pid}-{'0' * 8}"
    orphan.mkdir(parents=True)
    (orphan / "spill-stale.arrow").touch()
    with TpuProcessCluster(n_workers=2, conf=conf) as c:
        assert not orphan.exists()
        got = c.run_query(plan)
        sched = c.last_scheduler
        bundle = c.last_incident_path
    assert got.column("k").to_pylist() == sorted(
        _oracle(plan).column("k").to_pylist())
    assert not _events(sched, "task_failed")
    pressure = [e for e in read_event_logs(str(tmp_path / "events"))
                if e.get("type") == "disk_pressure"]
    assert pressure and pressure[0]["kind"] == "enospc", pressure
    assert bundle and os.listdir(tmp_path / "incidents") == \
        [os.path.basename(bundle)]
    assert obs_checker().check_flight(bundle) == []
    kinds = {a["kind"] for a in json.load(open(bundle))["anomalies"]}
    assert "disk_pressure" in kinds, kinds
    assert _leaked_spill_files(tmp_path) == []
