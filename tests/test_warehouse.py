"""Query telemetry warehouse tests (spark_rapids_tpu/obs/warehouse.py +
obs/attribution.py): sealed-segment durability (torn tails salvage,
crash-safe appends), one-row-per-query emission across every outcome
class (completed / cancelled / degraded / failed), per-operator and
per-transport cost attribution — including the exchange write-side row
fix (the BENCH_r07 ``ShuffleExchangeExec rows: 0`` bug) — the drift
sentinel's structural-regression rc semantics, and the /status JSON
endpoint."""
import glob
import json
import os
import urllib.request

import numpy as np
import pyarrow as pa
import pytest

from data_gen import IntegerGen, LongGen, gen_table

from spark_rapids_tpu import datatypes as dt
from spark_rapids_tpu.config import RapidsConf
from spark_rapids_tpu.exec import HostBatchSourceExec
from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec
from spark_rapids_tpu.exec.exchange import TpuShuffleExchangeExec
from spark_rapids_tpu.exec.joins import TpuShuffledHashJoinExec
from spark_rapids_tpu.expr import Alias, UnresolvedColumn as col
from spark_rapids_tpu.expr.aggregates import Count, Sum
from spark_rapids_tpu.lifecycle import QueryCancelled
from spark_rapids_tpu.obs.warehouse import (append_row, drift_report,
                                            read_rows, render_warehouse,
                                            tail_rows, warehouse_dir)
from spark_rapids_tpu.planner import overrides
from spark_rapids_tpu.session import TpuSession
from spark_rapids_tpu.shuffle.partitioner import HashPartitioning


def _conf(d, **extra):
    base = {"spark.rapids.warehouse.dir": str(d)}
    base.update({k: str(v) for k, v in extra.items()})
    return RapidsConf(base)


def _row(**kw):
    r = {"query_id": "q1", "tenant": "default", "outcome": "completed",
         "device_kind": "cpu", "fingerprint": "fp0", "wall_s": 1.0,
         "fusion": {"fused_dispatches": 4, "jit_variants": 2,
                    "scan_programs": 4},
         "scan": {"device_chunks": 6, "fallback_chunks": 0},
         "bytes": {"host_written": 1000}, "spill": {}}
    r.update(kw)
    return r


# --- writer / reader durability ---------------------------------------------

def test_warehouse_dir_gating(tmp_path):
    assert warehouse_dir(RapidsConf()) is None  # no dir configured
    assert warehouse_dir(_conf(tmp_path)) == str(tmp_path)
    off = _conf(tmp_path, **{"spark.rapids.warehouse.enabled": "false"})
    assert warehouse_dir(off) is None  # kill switch wins over dir


def test_append_read_roundtrip_sealed(tmp_path):
    conf = _conf(tmp_path)
    for i in range(3):
        p = append_row(conf, _row(query_id=f"q{i}", ts=float(i)))
        assert p is not None
    rows = read_rows(str(tmp_path))
    assert [r["query_id"] for r in rows] == ["q0", "q1", "q2"]
    assert all(r["version"] == 1 for r in rows)
    # segments really carry the CRC32C seal: the verified read succeeds
    from spark_rapids_tpu.shuffle.integrity import read_sealed_file
    segs = glob.glob(os.path.join(str(tmp_path), "wh-*.jsonl"))
    assert segs
    for s in segs:
        read_sealed_file(s, lambda k, d: AssertionError(f"{k}: {d}"))


def test_segment_roll_and_retention(tmp_path):
    conf = _conf(tmp_path,
                 **{"spark.rapids.warehouse.segment.maxRows": "1",
                    "spark.rapids.warehouse.maxFiles": "2"})
    for i in range(5):
        append_row(conf, _row(query_id=f"q{i}", ts=float(i)))
    segs = glob.glob(os.path.join(str(tmp_path), "wh-*.jsonl"))
    assert len(segs) == 2  # oldest pruned at write time
    assert [r["query_id"] for r in read_rows(str(tmp_path))] == \
        ["q3", "q4"]


def test_torn_tail_salvaged(tmp_path):
    conf = _conf(tmp_path)
    for i in range(3):
        append_row(conf, _row(query_id=f"q{i}", ts=float(i)))
    (seg,) = glob.glob(os.path.join(str(tmp_path), "wh-*.jsonl"))
    raw = open(seg, "rb").read()
    # crash mid-write of a FUTURE append: sealed payload + torn tail
    with open(seg, "wb") as f:
        f.write(raw + b'{"query_id": "q3", "torn')
    rows = read_rows(str(tmp_path))
    # the seal no longer verifies -> line salvage recovers the intact
    # prefix rows and skips the torn line + binary footer
    assert [r["query_id"] for r in rows] == ["q0", "q1", "q2"]
    # a fully garbage segment contributes nothing but doesn't raise
    with open(os.path.join(str(tmp_path), "wh-0-0.jsonl"), "wb") as f:
        f.write(b"\x00\xff\x01garbage")
    assert len(read_rows(str(tmp_path))) == 3


def test_append_row_disabled_is_noop(tmp_path):
    off = _conf(tmp_path, **{"spark.rapids.warehouse.enabled": "false"})
    assert append_row(off, _row()) is None
    assert not glob.glob(os.path.join(str(tmp_path), "wh-*"))


# --- one row per query, every outcome class ---------------------------------

def _frame(session, nbatches=2, rows=200):
    tbl = pa.Table.from_batches([
        pa.RecordBatch.from_arrays(
            [pa.array(np.arange(rows, dtype=np.int64))], names=["a"])
        for _ in range(nbatches)])
    return session.create_dataframe(tbl)


def test_completed_row_attribution_consistent(tmp_path):
    conf = _conf(tmp_path)
    rb = gen_table([IntegerGen(min_val=0, max_val=4, null_frac=0.0),
                    LongGen(nullable=False)], 300, seed=1,
                   names=["k", "v"])
    src = HostBatchSourceExec([rb])
    exch = TpuShuffleExchangeExec(HashPartitioning([col("k")], 4), src)
    plan = TpuHashAggregateExec([col("k")],
                                [Alias(Sum(col("v")), "s")], exch)
    overrides(plan, conf).collect()
    (row,) = read_rows(str(tmp_path))
    assert row["outcome"] == "completed" and row["cancel"] is None
    assert row["source"] == "plan" and row["query_id"]
    assert row["fingerprint"] and row["device_kind"]
    # internal consistency: op time fits inside the wall, ops carry the
    # oracle row counts
    assert 0 < row["wall_s"]
    assert row["split"]["op_time_s"] <= row["wall_s"] * 1.5
    by_label = {op["label"].split("#")[0]: op
                for op in row["ops"].values()}
    assert by_label["HostBatchSourceExec"]["rows"] == 300
    assert by_label["HashAggregateExec"]["rows"] == 5
    assert set(row["bytes"]) == {"host_written", "host_fetched",
                                 "ici_written", "ici_fetched",
                                 "process_fetched", "gang_dcn",
                                 "gang_epochs"}
    assert set(row["spill"]) == {"write_bytes", "disk_write_bytes",
                                 "read_bytes"}


def test_row_splits_out_the_rejit(tmp_path):
    """``split.jit_s`` and ``fusion.jit_misses`` are the growth of the
    registry's ``rapids_jit_*`` families over the query: a plan built
    anew traces its programs, the same plan collected again traces
    none. ``jit_variants``, which the drift sentinel reads, stays."""
    from spark_rapids_tpu.obs.metrics import REGISTRY
    conf = _conf(tmp_path)
    rb = gen_table([IntegerGen(min_val=0, max_val=6, null_frac=0.0),
                    LongGen(nullable=False)], 333, seed=5,
                   names=["k", "v"])
    pp = overrides(TpuHashAggregateExec(
        [col("k")], [Alias(Sum(col("v")), "s")], HostBatchSourceExec([rb])),
        conf)

    def traces():
        fam = REGISTRY.snapshot()["rapids_jit_traces_total"]["samples"]
        return sum(fam.values())

    before = traces()
    pp.collect()
    first = traces()
    pp.collect()
    first_row, again = read_rows(str(tmp_path))
    assert first_row["fusion"]["jit_misses"] == first - before >= 1
    assert first_row["split"]["jit_s"] > 0
    assert again["fusion"]["jit_misses"] == traces() - first == 0
    assert again["split"]["jit_s"] == 0
    assert set(again["split"]) == {"dispatch_s", "op_time_s", "jit_s"}
    assert "jit_variants" in again["fusion"]


def test_exchange_write_side_rows_attributed(tmp_path):
    """BENCH_r07 regression: the AQE reader drives the exchange through
    materialize() (never execute()), so without write-side counting the
    exchange showed rows=0 while its consumers saw the full stream."""
    conf = _conf(tmp_path)
    rb = gen_table([IntegerGen(min_val=0, max_val=9, null_frac=0.0),
                    LongGen(nullable=False)], 400, seed=2,
                   names=["k", "v"])
    src = HostBatchSourceExec([rb])
    exch = TpuShuffleExchangeExec(HashPartitioning([col("k")], 4), src)
    plan = TpuHashAggregateExec([col("k")],
                                [Alias(Count(col("v")), "c")], exch)
    overrides(plan, conf).collect()  # AQE on by default
    (row,) = read_rows(str(tmp_path))
    by_label = {op["label"].split("#")[0]: op["rows"]
                for op in row["ops"].values()}
    # the exchange counts every row it partitions — exactly its input,
    # not zero and not double-counted with the reader's read side
    assert by_label["ShuffleExchangeExec"] == 400
    assert by_label.get("AQEShuffleReadExec", 400) == 400


def test_cancelled_row_classified(tmp_path):
    s = TpuSession({"spark.rapids.warehouse.dir": str(tmp_path),
                    "spark.rapids.query.memoryBudgetBytes": "1",
                    "spark.rapids.query.memoryBudget.action": "cancel"})
    with pytest.raises(QueryCancelled):
        _frame(s).select("a").collect()
    (row,) = read_rows(str(tmp_path))
    assert row["outcome"] == "cancelled"
    assert row["cancel"]["reason"] == "budget"
    assert "budget exceeded" in row["cancel"]["detail"]
    assert "error" not in row  # cancelled, not failed


def test_degraded_row_carries_ladder_and_reasons(tmp_path):
    s = TpuSession({"spark.rapids.warehouse.dir": str(tmp_path),
                    "spark.rapids.sql.test.injectRetryOOM.storm": "200",
                    "spark.rapids.sql.oomRetry.maxSplits": "2"})
    qx = s.query_context()
    got = _frame(s, nbatches=1, rows=64).select("a").collect(qx)
    assert got.column(0).to_pylist() == list(range(64))
    (row,) = read_rows(str(tmp_path))
    assert row["outcome"] == "degraded"
    for rung in ("halve", "spill", "width1", "cpu"):
        assert row["ladder"].get(rung, 0) >= 1, row["ladder"]
    assert any(r.startswith("ladder_cpu_fallback:")
               for r in row["fallback_reasons"])


def test_failed_row_carries_error(tmp_path):
    conf = _conf(tmp_path)
    schema = dt.Schema([dt.StructField("x", dt.INT64, True)])
    from spark_rapids_tpu.io.scan import TpuFileScanExec
    plan = TpuFileScanExec(["/nonexistent/wh.parquet"], schema=schema)
    with pytest.raises(Exception):
        overrides(plan, conf).collect()
    (row,) = read_rows(str(tmp_path))
    assert row["outcome"] == "failed"
    assert row["error"]  # classified exception text rides the row


# --- drift sentinel ---------------------------------------------------------

def test_drift_silent_on_identical_runs(tmp_path):
    conf = _conf(tmp_path)
    append_row(conf, _row(ts=1.0))
    append_row(conf, _row(ts=2.0))
    rep, rc = drift_report(str(tmp_path))
    assert rc == 0
    assert "drift: clean" in rep


def test_drift_flags_seeded_dispatch_regression_once(tmp_path):
    conf = _conf(tmp_path)
    append_row(conf, _row(ts=1.0))
    seeded = _row(ts=2.0)
    seeded["fusion"] = dict(seeded["fusion"], fused_dispatches=5)
    append_row(conf, seeded)
    rep, rc = drift_report(str(tmp_path))
    assert rc == 1
    # flagged exactly once, naming the offending counter and the delta
    assert rep.count("REGRESSION") == 1
    assert "fusedDispatches: 4 -> 5 (+1)" in rep


def test_drift_flags_fallback_variants_and_bytes(tmp_path):
    conf = _conf(tmp_path)
    append_row(conf, _row(ts=1.0))
    bad = _row(ts=2.0)
    bad["scan"] = {"device_chunks": 5, "fallback_chunks": 1}
    bad["fusion"] = dict(bad["fusion"], jit_variants=99)
    bad["bytes"] = {"host_written": 10000}  # 10x > 25% tolerance
    append_row(conf, bad)
    rep, rc = drift_report(str(tmp_path))
    assert rc == 1
    assert "fallbackChunks: 0 -> 1" in rep
    assert "jitVariants: 99 exceeds bound 8" in rep
    assert "bytesMoved: 1000 -> 10000" in rep
    # knobs loosen the sentinel
    rep2, rc2 = drift_report(str(tmp_path), bytes_tolerance=100.0,
                             variant_bound=1000)
    assert "jitVariants" not in rep2 and "bytesMoved" not in rep2


def test_drift_refuses_cross_device_kind_rc3(tmp_path):
    conf = _conf(tmp_path)
    append_row(conf, _row(ts=1.0, device_kind="cpu"))
    append_row(conf, _row(ts=2.0, device_kind="TPU v4"))
    rep, rc = drift_report(str(tmp_path))
    assert rc == 3
    assert rep.startswith("=== drift REFUSED: device_kind mismatch ===")
    assert "'TPU v4'" in rep and "'cpu'" in rep
    # explicit opt-out downgrades to a warning and compares anyway
    rep2, rc2 = drift_report(str(tmp_path), allow_cross_device=True)
    assert rc2 == 0
    assert "WARNING" in rep2


def test_drift_same_device_baseline_preferred_over_cross(tmp_path):
    """A same-device_kind prior exists further back: compare against
    IT, not the interleaved foreign-device run."""
    conf = _conf(tmp_path)
    append_row(conf, _row(ts=1.0, device_kind="cpu"))
    append_row(conf, _row(ts=2.0, device_kind="TPU v4"))
    append_row(conf, _row(ts=3.0, device_kind="cpu"))
    rep, rc = drift_report(str(tmp_path))
    assert rc == 0, rep


def test_profiling_cli_warehouse_and_drift(tmp_path, capsys):
    from spark_rapids_tpu.tools.profiling import _main as main
    conf = _conf(tmp_path)
    append_row(conf, _row(ts=1.0, tenant="etl"))
    seeded = _row(ts=2.0, tenant="etl")
    seeded["fusion"] = dict(seeded["fusion"], fused_dispatches=7)
    append_row(conf, seeded)
    assert main(["warehouse", str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "telemetry warehouse" in out and "etl" in out
    assert main(["drift", str(tmp_path)]) == 1  # seeded regression
    assert "fusedDispatches" in capsys.readouterr().out
    assert main(["drift", str(tmp_path),
                 "--variant-bound", "1"]) == 1
    # cross-device history refuses with rc 3
    append_row(conf, _row(ts=3.0, device_kind="TPU v4",
                          fingerprint="fpX"))
    append_row(conf, _row(ts=2.5, device_kind="cpu",
                          fingerprint="fpX"))
    assert main(["drift", str(tmp_path)]) == 3
    assert main(["drift", str(tmp_path), "--allow-cross-device"]) == 1


def test_render_warehouse_rollups(tmp_path):
    conf = _conf(tmp_path)
    append_row(conf, _row(ts=1.0, tenant="etl"))
    append_row(conf, _row(ts=2.0, tenant="adhoc",
                          outcome="cancelled"))
    out = render_warehouse(str(tmp_path))
    assert "rows: 2" in out
    assert "etl" in out and "adhoc" in out
    assert "cancelled=1" in out
    assert "fp0" in out  # per-fingerprint structural summary


# --- /status endpoint -------------------------------------------------------

def test_render_status_document_shape(tmp_path):
    from spark_rapids_tpu.obs.metrics import (clear_status_provider,
                                              render_status,
                                              set_status_provider)
    doc = render_status()
    assert doc["pid"] == os.getpid()
    assert "device_bytes_in_use" in doc["memory"]
    assert "in_use" in doc["admission"]
    sentinel = {"in_flight": [{"query_id": "q9", "phase": "running"}]}
    set_status_provider(lambda: sentinel)
    try:
        doc = render_status()
        assert doc["in_flight"][0]["query_id"] == "q9"
        # the whole document is JSON-serializable
        json.loads(json.dumps(doc))
    finally:
        clear_status_provider()
    assert "in_flight" not in render_status()


def test_status_provider_stale_clear_does_not_clobber():
    from spark_rapids_tpu.obs import metrics as M
    old = lambda: {"gen": 1}  # noqa: E731
    new = lambda: {"gen": 2}  # noqa: E731
    M.set_status_provider(old)
    M.set_status_provider(new)
    M.clear_status_provider(old)  # stale shutdown: must be a no-op
    try:
        assert M.render_status()["gen"] == 2
    finally:
        M.clear_status_provider()


def test_http_status_endpoint(tmp_path):
    import socket
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    from spark_rapids_tpu.obs import metrics as M
    conf = RapidsConf({"spark.rapids.metrics.port": port})
    bound = M.maybe_start_http_server(conf)
    if bound is None:
        pytest.skip("metrics port raced away")
    M.set_status_provider(lambda: {"probe": "alive"})
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{bound}/status", timeout=5) as resp:
            assert resp.headers["Content-Type"] == "application/json"
            doc = json.load(resp)
        assert doc["probe"] == "alive"
        assert "memory" in doc and "admission" in doc
        # /metrics still serves prometheus text beside it
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{bound}/metrics", timeout=5).read()
        assert b"# TYPE" in body
    finally:
        M.clear_status_provider()


def test_tail_rows_compacts_for_status(tmp_path):
    conf = _conf(tmp_path)
    for i in range(7):
        append_row(conf, _row(ts=float(i), query_id=f"q{i}"))
    tail = tail_rows(str(tmp_path), 3)
    assert [t["query_id"] for t in tail] == ["q4", "q5", "q6"]
    assert set(tail[0]) == {"query_id", "tenant", "outcome", "wall_s",
                            "device_kind", "fingerprint"}


# --- process cluster: folded attribution + failed-query rows ----------------

@pytest.fixture(scope="module")
def wh_cluster(tmp_path_factory):
    from spark_rapids_tpu.cluster import TpuProcessCluster
    d = str(tmp_path_factory.mktemp("wh"))
    conf = RapidsConf({"spark.rapids.warehouse.dir": d,
                       "spark.rapids.metrics.enabled": "true"})
    with TpuProcessCluster(n_workers=2, conf=conf) as c:
        yield c, d


def _join_plan(n_fact=400, n_dim=10):
    rng = np.random.default_rng(7)
    fact = pa.record_batch({
        "fk": pa.array(rng.integers(0, n_dim, n_fact)
                       .astype(np.int32)),
        "amt": pa.array(rng.integers(1, 100, n_fact).astype(np.int64)),
    })
    dim = pa.record_batch({
        "dk": pa.array(np.arange(n_dim, dtype=np.int32)),
        "grp": pa.array((np.arange(n_dim) % 3).astype(np.int32)),
    })
    lex = TpuShuffleExchangeExec(
        HashPartitioning([col("fk")], 3),
        HostBatchSourceExec([fact.slice(0, 250), fact.slice(250)]))
    rex = TpuShuffleExchangeExec(
        HashPartitioning([col("dk")], 3), HostBatchSourceExec([dim]))
    join = TpuShuffledHashJoinExec([col("fk")], [col("dk")], "inner",
                                   lex, rex)
    gex = TpuShuffleExchangeExec(HashPartitioning([col("grp")], 3),
                                 join)
    return TpuHashAggregateExec(
        [col("grp")], [Alias(Sum(col("amt")), "total")], gex), n_fact


def test_cluster_exchange_rows_match_consumer_input(wh_cluster):
    """Satellite regression: on a 2-worker join, every exchange's row
    count equals what its consumer read — never 0, never doubled."""
    c, d = wh_cluster
    plan, n_fact = _join_plan()
    before = len(read_rows(d))
    out = c.run_query(plan)
    assert out.num_rows == 3
    rows = read_rows(d)
    assert len(rows) == before + 1  # exactly ONE row for the query
    row = rows[-1]
    assert row["outcome"] == "completed"
    assert row["cluster"] == {"kind": "process", "n_workers": 2,
                              "mesh_incarnation": 0}
    # the cluster replaces each exchange with a ProcessShuffleReadExec
    # carrying the exchange's stable op id, so its read rows fold under
    # the exchange node
    exch_rows = sorted(
        op["rows"] for op in row["ops"].values()
        if op["label"].startswith(("ShuffleExchangeExec",
                                   "ProcessShuffleReadExec")))
    join_rows = sum(op["rows"] for op in row["ops"].values()
                    if op["label"].startswith("ShuffledHashJoinExec"))
    # lex carries the fact side (400), rex the dim side (10), gex the
    # join output — each exactly its consumer's input
    assert exch_rows == sorted([10, n_fact, join_rows])
    assert join_rows == n_fact  # every fact row hits one dim row
    # transport attribution: the workers really moved shuffle bytes
    # through host files, and the row saw the worker-side deltas
    assert row["bytes"]["host_written"] > 0
    assert row["bytes"]["gang_dcn"] == 0  # no mesh in this cluster


def test_cluster_failed_query_row_partial_attribution(wh_cluster):
    """A query that dies mid-flight still leaves ONE row —
    outcome=failed, with whatever attribution the .opm harvest
    recovered from completed stages."""
    c, d = wh_cluster
    from spark_rapids_tpu.io.scan import TpuFileScanExec
    rb = gen_table([IntegerGen(min_val=0, max_val=4, null_frac=0.0),
                    LongGen(nullable=False)], 300, seed=3,
                   names=["k", "v"])
    good = TpuShuffleExchangeExec(
        HashPartitioning([col("k")], 2), HostBatchSourceExec([rb]))
    schema = dt.Schema([dt.StructField("k", dt.INT32, True),
                        dt.StructField("v", dt.INT64, True)])
    bad = TpuShuffleExchangeExec(
        HashPartitioning([col("k")], 2),
        TpuFileScanExec(["/nonexistent/wh-fail.parquet"],
                        schema=schema))
    join = TpuShuffledHashJoinExec([col("k")], [col("k")], "inner",
                                   good, bad)
    plan = TpuHashAggregateExec([col("k")],
                                [Alias(Sum(col("v")), "s")], join)
    before = len(read_rows(d))
    with pytest.raises(Exception):
        c.run_query(plan)
    rows = read_rows(d)
    assert len(rows) == before + 1
    row = rows[-1]
    assert row["outcome"] == "failed" and row["error"]
    # the good map stage ran before the bad one killed the query: its
    # flushed .opm snapshots give the row partial attribution
    src_rows = sum(op["rows"] for op in row["ops"].values()
                   if op["label"].startswith("HostBatchSourceExec"))
    assert src_rows == 300


def test_cluster_status_doc_shape(wh_cluster):
    """The cluster's /status provider: worker census, mesh health, and
    the warehouse tail (a query in flight:
    ``test_cluster_cancelled_query_row_and_live_status``)."""
    c, d = wh_cluster
    doc = c._status_doc()
    json.loads(json.dumps(doc))  # serializable as served
    assert doc["cluster"]["n_workers"] == 2
    assert doc["in_flight"] == []  # nothing running right now
    assert doc["mesh"]["enabled"] is False
    tail = doc["warehouse_tail"]
    assert tail and set(tail[0]) == {"query_id", "tenant", "outcome",
                                     "wall_s", "device_kind",
                                     "fingerprint"}


def test_cluster_cancelled_query_row_and_live_status(wh_cluster):
    """A query stalled by chaos ``hang_query`` shows in the status
    document WHILE it runs (query id, phase, the warehouse tail of the
    queries before it); the user's cancel then leaves exactly ONE row,
    classified cancelled / user."""
    import threading
    import time
    c, d = wh_cluster
    plan, _ = _join_plan()
    c.run_query(plan)  # a finished query: the tail is not empty
    before = len(read_rows(d))
    seen = {}

    def watch_then_cancel():
        deadline = time.time() + 45
        while time.time() < deadline and not seen:
            doc = c._status_doc()
            if doc["in_flight"]:
                seen.update(json.loads(json.dumps(doc)))
            time.sleep(0.05)
        while not c.cancel_running() and time.time() < deadline:
            time.sleep(0.05)

    watcher = threading.Thread(target=watch_then_cancel, daemon=True)
    watcher.start()
    with pytest.raises(QueryCancelled) as ei:
        c.run_query(plan, conf=RapidsConf({
            **dict(c.conf.items()),
            "spark.rapids.tpu.test.injectFaults": "hang_query:q*r*:*:60"}))
    watcher.join(timeout=60)
    assert ei.value.reason == "user"
    (live,) = seen["in_flight"]
    assert live["query_id"].startswith("q") and "phase" in live
    assert seen["warehouse_tail"]
    rows = read_rows(d)
    assert len(rows) == before + 1
    assert rows[-1]["query_id"] == live["query_id"]
    assert rows[-1]["outcome"] == "cancelled"
    assert rows[-1]["cancel"]["reason"] == "user"


def test_cluster_repeat_run_lands_under_one_fingerprint_drift_clean(
        wh_cluster):
    """The same plan run twice on the cluster: two completed rows under
    ONE fingerprint and device kind, and the drift sentinel, which
    compares runs of a fingerprint, stays silent (rc 0)."""
    c, d = wh_cluster
    plan, _ = _join_plan()
    c.run_query(plan)
    c.run_query(plan)
    first, second = read_rows(d)[-2:]
    assert first["outcome"] == second["outcome"] == "completed"
    assert first["fingerprint"] == second["fingerprint"]
    assert first["device_kind"] == second["device_kind"]
    rep, rc = drift_report(d)
    assert rc == 0, rep
