"""Per-operator metrics layer tests (obs/opmetrics.py): stable
operator-instance ids, always-on row/batch accounting, cross-worker
folding, EXPLAIN ANALYZE, query-profile history and regression
comparison.

The acceptance shape from the issue: per-operator totals match oracle
row counts on a process-cluster join query; a worker crash leaves
partial snapshots harvested (not a crashed fold); EXPLAIN ANALYZE text
carries every operator id exactly once; `profiling compare` flags a
seeded 2x regression.
"""
import copy
import json
import os
import pickle
import re

import pyarrow as pa
import pytest

from asserts import obs_checker

from spark_rapids_tpu.cluster import TpuProcessCluster
from spark_rapids_tpu.config import RapidsConf
from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec
from spark_rapids_tpu.exec.base import HostBatchSourceExec
from spark_rapids_tpu.exec.exchange import TpuShuffleExchangeExec
from spark_rapids_tpu.expr import Alias, UnresolvedColumn as col
from spark_rapids_tpu.expr.aggregates import Sum
from spark_rapids_tpu.obs.opmetrics import (assign_op_ids, fold_ctx,
                                            fold_snapshots, plan_source,
                                            render_analyzed)
from spark_rapids_tpu.session import TpuSession
from spark_rapids_tpu.shuffle.partitioner import HashPartitioning

OPID_RE = re.compile(r"\(op(\d+)\)")


def _session(extra=None):
    conf = {"spark.sql.shuffle.partitions": "2"}
    conf.update(extra or {})
    return TpuSession(conf)


def _join_agg_df(s, n_left=400, n_dim=10):
    left = s.create_dataframe({
        "k": [i % n_dim for i in range(n_left)],
        "v": list(range(n_left))})
    dim = s.create_dataframe({
        "k": list(range(n_dim)),
        "name": [f"d{i}" for i in range(n_dim)]})
    return left.join(dim, on="k").group_by("name").agg(
        Alias(Sum(col("v")), "sv"))


def _ops_by_name(folded, name):
    return [st for st in folded.values()
            if st["label"].split("#", 1)[0] == name]


def _rows_total(folded, name):
    return sum(int(st["metrics"].get("rows", 0))
               for st in _ops_by_name(folded, name))


# --- stable ids --------------------------------------------------------------

def test_op_ids_unique_and_survive_pickle_and_deepcopy():
    s = _session()
    pp = _join_agg_df(s)._plan()
    labels = []

    def walk(n, seen):
        if id(n) in seen:
            return
        seen.add(id(n))
        labels.append(n.node_label())
        for c in n.children:
            walk(c, seen)

    walk(pp.root, set())
    assert all("#op" in lb for lb in labels), labels
    assert len(labels) == len(set(labels)), labels
    # ids ride the task pickle and deep copies unchanged — that is what
    # lets worker snapshots fold back under the driver's labels
    for clone in (pickle.loads(pickle.dumps(pp.root)),
                  copy.deepcopy(pp.root)):
        c_labels = []

        def walk2(n, seen):
            if id(n) in seen:
                return
            seen.add(id(n))
            c_labels.append(n.node_label())
            for c in n.children:
                walk2(c, seen)

        walk2(clone, set())
        assert c_labels == labels


def test_assign_op_ids_shares_aliased_subtrees():
    src = HostBatchSourceExec([pa.record_batch({"k": [1, 2]})])
    agg = TpuHashAggregateExec([col("k")], [Alias(Sum(col("k")), "s")],
                               src)
    # the same exchange object under two parents (self-join shape)
    exch = TpuShuffleExchangeExec(HashPartitioning([col("k")], 2), agg)
    from spark_rapids_tpu.exec.misc import TpuUnionExec
    root = TpuUnionExec([exch, exch])
    assign_op_ids(root, force=True)
    assert root.children[0] is root.children[1]
    assert root.children[0]._op_id == root.children[1]._op_id


# --- local EXPLAIN ANALYZE ---------------------------------------------------

def test_explain_analyze_local_ids_unique_and_rows():
    s = _session()
    s.register_table("t", {"k": [i % 3 for i in range(90)],
                           "v": list(range(90))})
    text = s.sql("EXPLAIN ANALYZE SELECT k, SUM(v) AS sv FROM t "
                 "GROUP BY k ORDER BY k")
    ids = OPID_RE.findall(text)
    assert ids, text
    assert len(ids) == len(set(ids)), f"duplicate op ids: {text}"
    # the source and the aggregate both report their true row counts
    src_line = next(ln for ln in text.splitlines()
                    if "HostBatchSourceExec" in ln)
    assert "rows=90" in src_line, src_line
    agg_line = next(ln for ln in text.splitlines()
                    if "HashAggregateExec" in ln)
    assert "rows=3" in agg_line, agg_line
    # FORMATTED renders the full metric set
    full = s.sql("EXPLAIN ANALYZE FORMATTED SELECT k, SUM(v) AS sv "
                 "FROM t GROUP BY k ORDER BY k")
    assert "outputBytes=" in full, full


def test_explain_analyze_marks_fused_and_sql_source():
    s = _session()
    s.register_table("t", {"k": [1, 2, 3, 4], "v": [1.0, 2.0, 3.0, 4.0]})
    df = s.sql("SELECT k + 1 AS k1 FROM t WHERE v > 1.5")
    assert plan_source(df._node) == "sql"
    pp = df._plan()
    pp.collect()
    text = pp.explain_analyze()
    # project/filter chains fuse into one XLA program below their
    # consumer: the fused node is marked with the program it joined
    # ("fused into opN's program"; nodes with no metrics at all still
    # get the generic parent-stage marker) — never silently zeroed
    assert "fused into" in text, text


# --- process cluster: fold across workers ------------------------------------

def test_cluster_join_totals_match_oracle_rows():
    s = _session()
    df = _join_agg_df(s, n_left=400, n_dim=10)
    with TpuProcessCluster(n_workers=2) as c:
        out = c.run_query(df._plan().root)
        folded = c.last_opmetrics
        analyzed = c.last_analyzed()
    assert out.num_rows == 10
    assert sorted(r["sv"] for r in out.to_pylist()) == sorted(
        sum(v for v in range(400) if v % 10 == k) for k in range(10))
    # per-operator totals match the oracle row counts exactly
    assert _rows_total(folded, "HostBatchSourceExec") == 400 + 10
    assert _rows_total(folded, "ShuffledHashJoinExec") == 400
    assert _rows_total(folded, "HashAggregateExec") == 10
    # the exchange folds with its reduce-side read: output rows = what
    # the reducers consumed = the join's 400 output rows
    exch_line = next(ln for ln in analyzed.splitlines()
                     if "ShuffleExchangeExec" in ln)
    assert "rows=400" in exch_line, analyzed
    # cross-worker aggregation is visible: the reduce ops ran as 2 tasks
    agg_st = _ops_by_name(folded, "HashAggregateExec")[0]
    assert agg_st["tasks"] == 2, agg_st
    assert agg_st["skew"] >= 1.0


def test_cluster_runs_persist_valid_profiles_that_compare(tmp_path):
    """Each cluster run of a plan leaves its own query profile under
    ``spark.rapids.history.dir``; the schema checker accepts both and
    ``profiling compare`` renders the pair."""
    from spark_rapids_tpu.tools.profiling import compare_report
    root = _join_agg_df(_session())._plan().root
    conf = RapidsConf({"spark.rapids.history.dir": str(tmp_path)})
    paths = []
    with TpuProcessCluster(n_workers=2, conf=conf) as c:
        for _ in range(2):
            c.run_query(root)
            paths.append(c.last_profile_path)
    first, second = paths
    assert first != second
    for path in paths:
        assert obs_checker().check_profile(path) == []
    assert json.load(open(first))["cluster"] == "process"
    assert "per-operator opTime" in compare_report(first, second)


def test_cluster_worker_crash_partial_snapshots_harvested():
    rbs = [pa.record_batch({"k": [i % 5 for i in range(300)],
                            "v": list(range(300))}),
           pa.record_batch({"k": [i % 5 for i in range(300, 600)],
                            "v": list(range(300, 600))})]
    src = HostBatchSourceExec(rbs)
    plan = TpuHashAggregateExec(
        [col("k")], [Alias(Sum(col("v")), "s")],
        TpuShuffleExchangeExec(HashPartitioning([col("k")], 4), src))
    conf = RapidsConf({
        "spark.rapids.tpu.test.injectFaults": "crash:q1s1m0:0"})
    with TpuProcessCluster(n_workers=2, conf=conf) as c:
        out = c.run_query(plan)
        folded = c.last_opmetrics
        sched = c.last_scheduler
    assert out.num_rows == 5
    # the crash really happened and was retried
    assert any(e["event"] == "task_failed" for e in sched.events)
    # fold survives the crashed attempt's missing/partial snapshot and
    # counts ONLY winning attempts: source rows are exact, not doubled
    assert _rows_total(folded, "HostBatchSourceExec") == 600
    assert _rows_total(folded, "HashAggregateExec") == 5


def test_fold_tolerates_torn_snapshot(tmp_path):
    # a torn .opm.json (crash mid-write) is skipped, never fatal
    from spark_rapids_tpu.obs.opmetrics import read_task_opmetrics
    good = tmp_path / "t1.a0.w0.task.opm.json"
    good.write_text(json.dumps(
        {"task": "t1", "attempt": 0,
         "ops": {"FooExec#op1": {"rows": 7, "opTime": 0.1}}}))
    torn = tmp_path / "t2.a1.w1.task.opm.json"
    torn.write_text('{"task": "t2", "ops": {"FooExec#')
    snaps = read_task_opmetrics(str(tmp_path),
                                [("t1", 0, 0), ("t2", 1, 1),
                                 ("t3", 0, 0)])
    assert len(snaps) == 1 and snaps[0]["task"] == "t1"
    folded = fold_snapshots(snaps)
    assert folded["op1"]["metrics"]["rows"] == 7


# --- profiles + history + compare --------------------------------------------

def test_profile_written_and_history_renders(tmp_path):
    hist = str(tmp_path / "hist")
    s = _session({"spark.rapids.history.dir": hist})
    df = _join_agg_df(s)
    pp = df._plan()
    pp.collect()
    assert pp.last_profile_path and os.path.exists(pp.last_profile_path)
    doc = json.load(open(pp.last_profile_path))
    assert doc["cluster"] == "local" and doc["source"] == "plan"
    assert doc["ops"] and doc["nodes"]
    from spark_rapids_tpu.tools.profiling import history_report
    listing = history_report(hist)
    assert doc["profile_id"] in listing
    inspect = history_report(hist, doc["profile_id"])
    assert "HashAggregateExec" in inspect and "rows=" in inspect


def test_profiling_compare_flags_seeded_2x_regression(tmp_path):
    hist = str(tmp_path / "hist")
    s = _session({"spark.rapids.history.dir": hist})
    df = _join_agg_df(s)
    pp = df._plan()
    pp.collect()
    a_path = pp.last_profile_path
    pp2 = df._plan()
    pp2.collect()
    b_path = pp2.last_profile_path
    assert a_path != b_path
    # seed a 2x opTime regression into run B's hottest operator
    a = json.load(open(a_path))
    b = json.load(open(b_path))
    key = max(a["ops"], key=lambda k: a["ops"][k]["metrics"]
              .get("opTime", 0.0))
    seeded = a["ops"][key]["metrics"]["opTime"] * 2.0 + 0.01
    b["ops"][key]["metrics"]["opTime"] = seeded
    b["ops"][key]["max"]["opTime"] = seeded
    with open(b_path, "w") as f:
        json.dump(b, f)
    from spark_rapids_tpu.tools.profiling import compare_report
    rep = compare_report(a_path, b_path, threshold=1.5)
    flagged = [ln for ln in rep.splitlines() if "REGRESSED" in ln]
    assert len(flagged) == 1, rep
    assert a["ops"][key]["label"] in flagged[0], rep
    # and an identical pair flags nothing
    rep_same = compare_report(a_path, a_path, threshold=1.5)
    assert "REGRESSED" not in rep_same
    assert "0 regression(s)" in rep_same


def test_compare_accepts_bench_json(tmp_path):
    a = tmp_path / "a.json"
    b = tmp_path / "b.json"
    a.write_text(json.dumps({"parsed": {"value": 30.0, "frac": 0.2}}))
    b.write_text(json.dumps({"parsed": {"value": 10.0, "frac": 0.21}}))
    from spark_rapids_tpu.tools.profiling import compare_report
    rep = compare_report(str(a), str(b), threshold=1.5)
    assert "bench compare" in rep
    assert "CHANGED" in rep and "value" in rep


def test_compare_refuses_cross_device_kind(tmp_path):
    """Comparability guard: profiles/benches measured on different
    hardware REFUSE to diff (a CPU-backend run read against a TPU run
    is a ~1000x fake regression, not a result) unless the cross-device
    diff is explicitly forced — then the report leads with a warning."""
    from spark_rapids_tpu.tools.profiling import compare_report
    a = tmp_path / "a.json"
    b = tmp_path / "b.json"
    a.write_text(json.dumps(
        {"parsed": {"value": 30.0, "device_kind": "TPU v5 lite"}}))
    b.write_text(json.dumps(
        {"parsed": {"value": 0.02, "device_kind": "cpu"}}))
    rep = compare_report(str(a), str(b), threshold=1.5)
    assert rep.startswith("=== compare REFUSED"), rep
    assert "device_kind" in rep and "cpu" in rep
    forced = compare_report(str(a), str(b), threshold=1.5,
                            allow_cross_device=True)
    assert "WARNING" in forced.splitlines()[0]
    assert "bench compare" in forced
    # same-kind docs still compare cleanly
    c = tmp_path / "c.json"
    c.write_text(json.dumps(
        {"parsed": {"value": 29.0, "device_kind": "TPU v5 lite"}}))
    rep_ok = compare_report(str(a), str(c), threshold=1.5)
    assert "REFUSED" not in rep_ok and "bench compare" in rep_ok
    # profile docs carry device_kind too (build_profile records it)
    pa_ = tmp_path / "pa.json"
    pb_ = tmp_path / "pb.json"
    ops = {"op1": {"label": "ProjectExec#op1",
                   "metrics": {"opTime": 0.1, "rows": 10},
                   "max": {"opTime": 0.1}, "tasks": 1, "skew": 1.0}}
    pa_.write_text(json.dumps({"profile_id": "profile-a", "ops": ops,
                               "wall_s": 0.2,
                               "device_kind": "TPU v5 lite"}))
    pb_.write_text(json.dumps({"profile_id": "profile-b", "ops": ops,
                               "wall_s": 0.2, "device_kind": "cpu"}))
    assert compare_report(str(pa_), str(pb_)).startswith(
        "=== compare REFUSED")


# --- event log + duration histogram satellites -------------------------------

def test_event_log_embeds_top_op_sinks(tmp_path):
    log_dir = str(tmp_path / "events")
    s = _session({"spark.rapids.eventLog.dir": log_dir})
    _join_agg_df(s).collect()
    from spark_rapids_tpu.tools.event_log import read_event_logs
    evs = [e for e in read_event_logs(log_dir) if "op_sinks" in e]
    assert evs, "no query event with op_sinks"
    sinks = evs[-1]["op_sinks"]
    assert 1 <= len(sinks) <= 3
    times = [sk["time_s"] for sk in sinks]
    assert times == sorted(times, reverse=True)
    assert all("#" in sk["op"] and sk["rows"] >= 0 for sk in sinks)


def test_query_duration_histogram_observed():
    from spark_rapids_tpu.obs.metrics import REGISTRY
    s = _session()
    _join_agg_df(s).collect()
    snap = REGISTRY.snapshot()["rapids_query_duration_seconds"]
    assert snap["kind"] == "histogram"
    assert snap["labelnames"] == ["source", "cluster"]
    key = "plan\tlocal"
    assert key in snap["samples"], snap["samples"].keys()
    assert snap["samples"][key]["count"] >= 1


def test_no_double_count_on_super_delegating_execute():
    # TpuBroadcastNestedLoopJoinExec.execute delegates to the wrapped
    # _BaseJoinExec.execute via super() for conditionless cross joins:
    # both shims fire, but the re-entrancy guard must count each batch
    # exactly once
    s = _session()
    left = s.create_dataframe({"a": [1, 2, 3]})
    right = s.create_dataframe({"b": [10, 20]})
    df = left.join(right, on=None)  # cross join, no condition
    pp = df._plan()
    out = pp.collect()
    assert out.num_rows == 6
    folded = fold_ctx(pp.last_ctx)
    join = _ops_by_name(folded, "BroadcastNestedLoopJoinExec")[0]
    assert join["metrics"]["rows"] == 6, join
    assert join["metrics"]["batches"] == 1, join


def test_render_analyzed_direct():
    # render over a raw (unplanned) tree falls back to per-instance
    # labels and never throws on empty folds
    src = HostBatchSourceExec([pa.record_batch({"k": [1, 2, 3]})])
    assign_op_ids(src, force=True)
    text = render_analyzed(src, {}, cluster="local")
    assert "HostBatchSourceExec" in text
    text2 = render_analyzed(
        src, fold_snapshots([{"ops": {src.node_label():
                                      {"rows": 3, "batches": 1}}}]))
    assert "rows=3" in text2
