"""Multi-host mesh runtime (ISSUE 16): one logical (dcn, ici) device
mesh spanning two real OS worker processes, gang-scheduled SPMD queries
whose shuffle exchanges cross the process boundary as XLA collectives,
and the failure ladder around them — cooperative cancel with zero
orphaned processes, gang-member death -> remesh -> retry, and the
single-process fallback. The whole point is the process boundary:
`jax.distributed` spans real processes, nothing is shared but the
rendezvous filesystem and the coordinator socket."""
import os
import threading
import time

import numpy as np
import pyarrow as pa
import pytest

from asserts import obs_checker

from spark_rapids_tpu.cluster import (TpuProcessCluster,
                                      _mesh_ineligible,
                                      _slice_for_member)
from spark_rapids_tpu.config import RapidsConf
from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec
from spark_rapids_tpu.exec.base import ExecCtx, HostBatchSourceExec
from spark_rapids_tpu.exec.exchange import TpuShuffleExchangeExec
from spark_rapids_tpu.exec.joins import TpuShuffledHashJoinExec
from spark_rapids_tpu.expr import Alias, UnresolvedColumn as col
from spark_rapids_tpu.expr.aggregates import Count, Sum
from spark_rapids_tpu.lifecycle import QueryCancelled
from spark_rapids_tpu.session import TpuSession
from spark_rapids_tpu.shuffle.partitioner import (HashPartitioning,
                                                  SinglePartitioning)

MESH_CONF = {"spark.rapids.tpu.mesh.enabled": "true"}


@pytest.fixture(scope="module")
def mesh_cluster():
    with TpuProcessCluster(n_workers=2,
                           conf=RapidsConf(MESH_CONF)) as c:
        yield c


def _oracle(plan):
    rbs = list(plan.execute_cpu(ExecCtx()))
    from spark_rapids_tpu.columnar.arrow_bridge import arrow_schema
    return pa.Table.from_batches(rbs, schema=arrow_schema(
        plan.output_schema))


def _rows(table):
    return sorted(table.to_pylist(), key=lambda d: tuple(
        (v is None, str(v)) for v in d.values()))


def _events(cluster, name):
    return [e for e in cluster.last_scheduler.events
            if e["event"] == name]


def _fact_dim(n_f=1200, n_d=48, seed=11):
    rng = np.random.default_rng(seed)
    fact = pa.record_batch({
        "fk": pa.array(rng.integers(0, n_d, n_f).astype(np.int32)),
        "amt": pa.array(rng.integers(1, 100, n_f).astype(np.int64)),
    })
    dim = pa.record_batch({
        "dk": pa.array(np.arange(n_d, dtype=np.int32)),
        "grp": pa.array((np.arange(n_d) % 5).astype(np.int32)),
    })
    return fact, dim


def _join_agg_plan(nparts=3, n_fact_batches=4):
    """shuffle(fact) >< shuffle(dim) -> regroup exchange -> agg: three
    exchanges, every leaf below one, the smoke-proven gang shape."""
    fact, dim = _fact_dim()
    step = fact.num_rows // n_fact_batches
    fact_src = HostBatchSourceExec(
        [fact.slice(i * step, step if i < n_fact_batches - 1 else None)
         for i in range(n_fact_batches)])
    dim_src = HostBatchSourceExec([dim.slice(0, 30), dim.slice(30)])
    lex = TpuShuffleExchangeExec(HashPartitioning([col("fk")], nparts),
                                 fact_src)
    rex = TpuShuffleExchangeExec(HashPartitioning([col("dk")], nparts),
                                 dim_src)
    join = TpuShuffledHashJoinExec([col("fk")], [col("dk")], "inner",
                                   lex, rex)
    gex = TpuShuffleExchangeExec(HashPartitioning([col("grp")], nparts),
                                 join)
    return TpuHashAggregateExec(
        [col("grp")], [Alias(Sum(col("amt")), "total"),
                       Alias(Count(col("amt")), "n")], gex)


def _assert_gang_ran(cluster, gen=0):
    """The query rode the mesh gang path: no fallback, one task_ok per
    member with the gang task-id shape."""
    assert not _events(cluster, "mesh_fallback"), \
        _events(cluster, "mesh_fallback")
    oks = [e["task"] for e in _events(cluster, "task_ok")]
    gang = [t for t in oks if f"g{gen}w" in t]
    assert len(gang) == cluster.n_workers, (oks, gang)


# --- the gang path ---------------------------------------------------------

def test_mesh_gang_join_agg_matches_oracle(mesh_cluster, tmp_path):
    """Join + regroup + agg as ONE SPMD program over a mesh spanning
    two worker processes, result identical to the in-process CPU
    oracle — and the STRUCTURAL evidence that every exchange was a
    cross-process collective: both processes joined one distributed
    mesh, each ran collective epochs, bytes crossed the boundary, and
    the stitched trace carries spans of both."""
    import json

    from spark_rapids_tpu.distributed.runtime import read_mesh_markers
    from spark_rapids_tpu.obs.metrics import read_worker_metrics
    plan = _join_agg_plan()
    got = mesh_cluster.run_query(plan, conf=RapidsConf({
        **MESH_CONF, "spark.rapids.metrics.enabled": "true",
        "spark.rapids.trace.dir": str(tmp_path)}))
    _assert_gang_ran(mesh_cluster)
    assert _rows(got) == _rows(_oracle(plan))
    markers = read_mesh_markers(mesh_cluster.root, 2, 0)
    assert len(markers) == 2 and all(
        m["ok"] and m["distributed"] and int(m["num_processes"]) == 2
        and m["device_kind"] for m in markers), markers
    epochs, nbytes = {}, {}
    for tag, families in read_worker_metrics(mesh_cluster.root):
        for name, acc in (("rapids_mesh_collective_epochs_total", epochs),
                          ("rapids_mesh_collective_bytes_total", nbytes)):
            for v in (families.get(name) or {"samples": {}})["samples"] \
                    .values():
                w = tag.split(".")[0]
                acc[w] = max(acc.get(w, 0), int(v))
    assert len(epochs) == 2 and min(epochs.values()) >= 1, epochs
    assert sum(nbytes.values()) > 0, nbytes
    with open(mesh_cluster.last_trace_path) as f:
        pids = {ev.get("pid") for ev in json.load(f)["traceEvents"]
                if ev.get("ph") == "X"}
    assert {1, 2} <= pids, pids
    assert obs_checker().check_trace(mesh_cluster.last_trace_path) == []


def test_mesh_sql_join_explain_analyze(mesh_cluster):
    """The acceptance bar: a join query from SQL TEXT runs over ICI
    spanning two processes, and EXPLAIN ANALYZE folds operator metrics
    across both (tasks=2 on the operators every member executed)."""
    fact, dim = _fact_dim(seed=23)
    s = TpuSession(conf={"spark.sql.autoBroadcastJoinThreshold": "-1",
                         "spark.sql.shuffle.partitions": "4"})
    # four fact batches so the gang has real per-member slices
    fact_t = pa.Table.from_batches([fact])
    s.register_table("fact", pa.Table.from_batches(
        [b for i in range(4)
         for b in fact_t.slice(i * 300, 300).to_batches()]))
    s.register_table("dim", pa.Table.from_batches([dim]))
    s.set_cluster(mesh_cluster)
    sql = ("SELECT d.grp, SUM(f.amt) AS total, COUNT(*) AS n "
           "FROM fact f JOIN dim d ON f.fk = d.dk GROUP BY d.grp")
    analyzed = s.sql("EXPLAIN ANALYZE " + sql)
    _assert_gang_ran(mesh_cluster)
    assert "tasks=2" in analyzed, analyzed
    # correctness against a numpy oracle (dk == arange, so grp and the
    # per-group sums are direct indexing)
    fk = fact.column("fk").to_numpy()
    amt = fact.column("amt").to_numpy()
    grp_of = dim.column("grp").to_numpy()[fk]
    want = sorted((int(g), int(amt[grp_of == g].sum()),
                   int((grp_of == g).sum()))
                  for g in np.unique(grp_of))
    got_t = mesh_cluster.run_query(s.sql(sql)._plan().root)
    got = sorted((r["grp"], r["total"], r["n"])
                 for r in got_t.to_pylist())
    assert got == want


@pytest.mark.slow  # boots its own 1-worker cluster; the local-mesh
# bootstrap path it exercises also runs in every dryrun/ci-smoke
def test_mesh_single_process_fallback():
    """n_workers=1 with mesh on: the runtime bootstraps the local
    (1, L) mesh — no coordinator — and gang queries still run and
    match the oracle."""
    from spark_rapids_tpu.distributed.runtime import read_mesh_markers
    plan = _join_agg_plan(nparts=2, n_fact_batches=2)
    with TpuProcessCluster(n_workers=1,
                           conf=RapidsConf(MESH_CONF)) as c:
        got = c.run_query(plan)
        _assert_gang_ran(c)
        docs = read_mesh_markers(c.root, 1, 0)
        assert docs and docs[0]["ok"] \
            and docs[0]["distributed"] is False
    assert _rows(got) == _rows(_oracle(plan))


# --- the failure ladder ----------------------------------------------------

def test_mesh_cancel_no_orphans(mesh_cluster):
    """Cancel mid-gang while every member stalls inside the stage:
    exactly one classified QueryCancelled, the whole incarnation is
    torn down (no orphaned worker processes, no wedged collectives),
    and the next mesh query on the same cluster runs green."""
    old_pids = [p.pid for p in mesh_cluster.pool._procs]
    plan = _join_agg_plan()
    conf = RapidsConf(dict(
        MESH_CONF, **{
            "spark.rapids.tpu.test.injectFaults":
                "hang_query:q*g*w*:*:60",
            "spark.rapids.query.cancel.joinTimeout": "10"}))
    canceller = threading.Timer(
        2.0, lambda: mesh_cluster.cancel_running("operator ctrl-c"))
    canceller.start()
    with pytest.raises(QueryCancelled) as ei:
        mesh_cluster.run_query(plan, conf)
    canceller.cancel()
    assert ei.value.reason == "user"
    assert len(_events(mesh_cluster, "query_cancelled")) == 1
    # cancel remeshed the fleet: every member of the cancelled gang's
    # incarnation is dead (waitpid-verified via the pool), none leaked
    deadline = time.monotonic() + 15
    while time.monotonic() < deadline:
        gone = []
        for pid in old_pids:
            try:
                os.kill(pid, 0)
                gone.append(False)
            except ProcessLookupError:
                gone.append(True)
        if all(gone):
            break
        time.sleep(0.1)
    assert all(gone), (old_pids, gone)
    assert all(mesh_cluster.pool.alive(w)
               for w in range(mesh_cluster.n_workers))
    # the cluster is not poisoned: the fresh incarnation runs a gang
    got = mesh_cluster.run_query(plan)
    _assert_gang_ran(mesh_cluster)
    assert _rows(got) == _rows(_oracle(plan))


def test_mesh_gang_member_crash_remesh_retry(mesh_cluster):
    """One member dies mid-gang: the WHOLE gang fails (never half a
    collective), the fleet remeshes under a new incarnation, and the
    retry generation completes on the gang path with a correct
    result."""
    plan = _join_agg_plan()
    conf = RapidsConf(dict(
        MESH_CONF, **{"spark.rapids.tpu.test.injectFaults":
                      "crash:q*g0w1:*"}))
    got = mesh_cluster.run_query(plan, conf)
    assert _events(mesh_cluster, "gang_failed")
    assert any("remesh" in e.get("reason", "")
               for e in _events(mesh_cluster, "worker_respawn"))
    _assert_gang_ran(mesh_cluster, gen=1)
    assert _rows(got) == _rows(_oracle(plan))


# --- plan gating and slicing (no cluster) ----------------------------------

def _mini_src(nbatch=2, name="k"):
    rb = pa.record_batch({name: pa.array([1, 2, 3], pa.int32()),
                          "v": pa.array([10, 20, 30], pa.int64())})
    return HostBatchSourceExec([rb] * nbatch)


def test_mesh_ineligible_reasons():
    src = _mini_src()
    assert "no shuffle exchange" in _mesh_ineligible(
        TpuHashAggregateExec([col("k")],
                             [Alias(Sum(col("v")), "s")], src))
    # a leaf above every exchange replays once per member
    ex = TpuShuffleExchangeExec(HashPartitioning([col("k")], 2), src)
    join = TpuShuffledHashJoinExec([col("k")], [col("k")], "inner",
                                   ex, _mini_src())
    assert "above every exchange" in _mesh_ineligible(join)
    # a stage mixing a deeper exchange with a raw leaf beside it
    outer = TpuShuffleExchangeExec(HashPartitioning([col("k")], 2),
                                   join)
    assert "mixes exchange input" in _mesh_ineligible(outer)
    # non-hash exchange
    single = TpuShuffleExchangeExec(SinglePartitioning(), src)
    assert "exchange" in _mesh_ineligible(single)


def test_slice_for_member_one_distribution_source_per_stage():
    """Join directly over two raw leaves below ONE exchange: exactly
    one side is sliced per member (the other replicates whole), so the
    member contributions stay a disjoint cover of the true join."""
    fact_src = _mini_src(nbatch=4, name="fk")
    dim_src = _mini_src(nbatch=2, name="dk")
    join = TpuShuffledHashJoinExec([col("fk")], [col("dk")], "inner",
                                   fact_src, dim_src)
    ex = TpuShuffleExchangeExec(HashPartitioning([col("fk")], 2), join)
    plan = TpuHashAggregateExec([col("fk")],
                                [Alias(Sum(col("v")), "s")], ex)
    assert _mesh_ineligible(plan) is None
    seen = []
    for k in range(2):
        m = _slice_for_member(plan, k, 2)
        f, d = m.child.child.children
        assert len(f.batches) == 2, "fact side carries the k::n slice"
        assert len(d.batches) == 2, "dim side replicates whole"
        seen.append(len(f.batches))
    assert sum(seen) == 4


def test_slice_for_member_aliased_leaf_runs_on_member0():
    """A self-join sharing ONE source object cannot slice either side
    (the slice would apply to both); the stage runs whole on member 0
    and empty elsewhere — still a disjoint cover."""
    src = _mini_src(nbatch=4)
    join = TpuShuffledHashJoinExec([col("k")], [col("k")], "inner",
                                   src, src)
    ex = TpuShuffleExchangeExec(HashPartitioning([col("k")], 2), join)
    plan = TpuHashAggregateExec([col("k")],
                                [Alias(Count(col("v")), "c")], ex)
    m0 = _slice_for_member(plan, 0, 2)
    m1 = _slice_for_member(plan, 1, 2)
    assert all(len(c.batches) == 4
               for c in m0.child.child.children)
    assert all(len(c.batches) == 0
               for c in m1.child.child.children)
