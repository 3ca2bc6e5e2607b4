"""Observability tier tests: span tracer, metrics registry, Prometheus
exposition, Chrome trace export, critical-path mining, event-log reader
guarantees — plus the ISSUE acceptance test: a process-cluster query
with an injected worker crash produces ONE stitched Chrome trace with
driver query/stage spans, both task attempts (failed + retried) under
the right parents, and worker-side operator spans."""
import json
import os
import threading
import urllib.request

import pyarrow as pa
import pytest

from asserts import obs_checker as _load_checker
from data_gen import IntegerGen, LongGen, gen_table

from spark_rapids_tpu.config import RapidsConf
from spark_rapids_tpu.obs.metrics import (MetricsRegistry, dump_prometheus,
                                          render_merged_snapshots)
from spark_rapids_tpu.obs.tracer import (NULL_TRACER, Tracer,
                                         load_chrome_trace,
                                         tracer_from_conf)
from spark_rapids_tpu.tools.profiling import (critical_path,
                                              format_critical_path,
                                              profile_trace)


# --- tracer -----------------------------------------------------------------

def test_disabled_tracer_is_shared_noop():
    t = tracer_from_conf(RapidsConf())
    assert t is NULL_TRACER and not t.enabled
    # span() must return ONE shared object: no allocation when disabled
    assert t.span("a") is t.span("b")
    with t.span("x") as sp:
        assert sp.span_id is None
    assert t.drain() == [] and t.write_chrome("/nonexistent") == ""


def test_tracer_from_conf_enabled(tmp_path):
    conf = RapidsConf({"spark.rapids.trace.dir": str(tmp_path),
                       "spark.rapids.trace.maxSpans": 7})
    t = tracer_from_conf(conf, pid=3)
    assert t.enabled and t.pid == 3 and t.max_spans == 7


def test_span_nesting_thread_local_stack():
    t = Tracer()
    with t.span("outer", cat="query") as o:
        with t.span("inner", cat="op"):
            pass
    spans = {s["name"]: s for s in t.drain()}
    assert spans["outer"]["parent_id"] is None
    assert spans["inner"]["parent_id"] == spans["outer"]["span_id"]
    assert spans["inner"]["dur"] <= spans["outer"]["dur"]


def test_span_stack_is_per_thread():
    t = Tracer()
    seen = {}

    def work(name):
        with t.span(name):
            seen[name] = t._stack()[:]

    with t.span("root"):
        th = threading.Thread(target=work, args=("other-thread",))
        th.start()
        th.join()
    # the other thread must not have nested under this thread's root
    other = [s for s in t.drain() if s["name"] == "other-thread"][0]
    assert other["parent_id"] is None


def test_live_holds_the_innermost_live_spans_tracer_per_thread():
    """``tracer.LIVE``: what a named program's call reads to decide
    whether it opens a span. Set by a live span for its extent, put back
    as it closes, per thread; the null tracer never sets it."""
    from spark_rapids_tpu.obs.tracer import LIVE
    a, b = Tracer(), Tracer()
    seen = []
    assert LIVE.tracer is None
    with a.span("outer"):
        assert LIVE.tracer is a
        with b.span("inner"):
            assert LIVE.tracer is b
            th = threading.Thread(target=lambda: seen.append(LIVE.tracer))
            th.start()
            th.join()
        assert LIVE.tracer is a
        with NULL_TRACER.span("off"), NULL_TRACER.span("t", timed=True):
            assert LIVE.tracer is a
    assert LIVE.tracer is None and seen == [None]


def test_emit_deterministic_ids_and_absorb():
    t = Tracer(trace_id="abc", pid=0)
    sid = t.emit("attempt t1 a0", "attempt", ts=100.0, dur=2.0,
                 span_id="t1.a0", parent_id=None)
    assert sid == "t1.a0"
    # a worker serialized spans parented on the attempt id
    t.absorb([{"name": "task t1 a0", "cat": "task", "span_id": "t1.a0.1.1",
               "parent_id": "t1.a0", "ts": 100.5, "dur": 1.0, "pid": 1},
              {"garbage": True},  # torn entry: skipped, not fatal
              {"name": "no-id"}])
    spans = t.drain()
    assert len(spans) == 2
    task = [s for s in spans if s["cat"] == "task"][0]
    assert task["parent_id"] == "t1.a0" and task["pid"] == 1


def test_span_buffer_bound_counts_drops():
    t = Tracer(max_spans=3)
    for i in range(5):
        with t.span(f"s{i}"):
            pass
    assert len(t.drain()) == 3 and t.dropped == 2


def test_worker_id_prefix_prevents_collisions():
    a = Tracer(trace_id="x", pid=1, id_prefix="t1.a0.")
    b = Tracer(trace_id="x", pid=1, id_prefix="t1.a1.")
    with a.span("s"):
        pass
    with b.span("s"):
        pass
    ids = {a.drain()[0]["span_id"], b.drain()[0]["span_id"]}
    assert len(ids) == 2


def test_chrome_roundtrip(tmp_path):
    t = Tracer(trace_id="deadbeef", pid=0)
    with t.span("query q1", cat="query", args={"fingerprint": "f"}):
        with t.span("stage map s1", cat="stage"):
            pass
    t.absorb([{"name": "task", "cat": "task", "span_id": "w.1",
               "parent_id": None, "ts": 1.0, "dur": 0.5, "pid": 2}])
    path = t.write_chrome(str(tmp_path))
    assert os.path.basename(path) == "trace-deadbeef.json"
    # the checker is the schema oracle
    assert _load_checker().check_trace(path) == []
    back = load_chrome_trace(path)
    by_name = {s["name"]: s for s in back}
    assert by_name["stage map s1"]["parent_id"] == \
        by_name["query q1"]["span_id"]
    assert by_name["task"]["pid"] == 2
    assert abs(by_name["task"]["dur"] - 0.5) < 1e-6
    # process metadata rows for driver + worker 1
    doc = json.load(open(path))
    names = {e["args"]["name"] for e in doc["traceEvents"]
             if e["ph"] == "M"}
    assert {"driver", "worker 1"} <= names


def test_summary_rolls_up_by_category():
    t = Tracer()
    t.emit("a", "shuffle", 0.0, 2.0)
    t.emit("b", "shuffle", 0.0, 3.0)
    t.emit("c", "op", 0.0, 1.0)
    s = t.summary()
    assert s["spans"] == 3
    assert s["by_cat"]["shuffle"] == {"spans": 2, "total_s": 5.0}


# --- metrics registry -------------------------------------------------------

def test_counter_gauge_histogram_basics():
    r = MetricsRegistry()
    c = r.counter("c_total", "help", ("k",))
    c.labels("x").inc()
    c.labels("x").inc(2)
    g = r.gauge("g")
    g.set(5)
    g.dec(2)
    h = r.histogram("h_seconds", buckets=(0.1, 1.0, float("inf")))
    for v in (0.05, 0.5, 10.0):
        h.observe(v)
    snap = r.snapshot()
    assert snap["c_total"]["samples"]["x"] == 3
    assert snap["g"]["samples"][""] == 3
    hs = snap["h_seconds"]["samples"][""]
    # bucket counts are CUMULATIVE (Prometheus histogram semantics)
    assert hs["count"] == 3 and hs["counts"] == [1, 2, 3]
    assert abs(hs["sum"] - 10.55) < 1e-9


def test_family_redeclaration_idempotent_kind_checked():
    r = MetricsRegistry()
    assert r.counter("x") is r.counter("x")
    with pytest.raises(ValueError):
        r.gauge("x")


@pytest.mark.parametrize("bound", [None, 192])
def test_bounded_label_sets_overflow_to_other(bound):
    from spark_rapids_tpu.obs.metrics import (JIT_HOST_SECONDS, JIT_PHASES,
                                              MAX_CHILDREN, _OTHER)
    r = MetricsRegistry()
    c = r.counter("c", "", ("id",), **({"max_children": bound}
                                       if bound else {}))
    bound = bound or MAX_CHILDREN
    for i in range(bound + 10):
        c.labels(f"id{i}").inc()
    snap = r.snapshot()["c"]["samples"]
    assert len(snap) == bound + 1
    assert snap[_OTHER] == 10  # the overflow collapsed into one series
    # the re-jit's seconds keep MAX_CHILDREN programs in every phase
    assert JIT_HOST_SECONDS.max_children == MAX_CHILDREN * len(JIT_PHASES)


def test_prometheus_text_valid_per_checker():
    r = MetricsRegistry()
    r.counter("rapids_test_total", 'escapes "quoted" help',
              ("a",)).labels('v"1"').inc()
    r.histogram("rapids_wait_seconds").observe(0.2)
    text = dump_prometheus(r)
    assert _load_checker().check_prometheus(text) == []
    assert "# TYPE rapids_test_total counter" in text
    assert 'le="+Inf"' in text


def test_merged_snapshots_proc_labels():
    d, w = MetricsRegistry(), MetricsRegistry()
    d.counter("c_total").inc(1)
    w.counter("c_total").inc(41)
    text = render_merged_snapshots([("driver", d.snapshot()),
                                    ("w0", w.snapshot())])
    assert 'c_total{proc="driver"} 1' in text
    assert 'c_total{proc="w0"} 41' in text
    # one TYPE line per family, not per process
    assert text.count("# TYPE c_total") == 1
    assert _load_checker().check_prometheus(text) == []


def test_http_metrics_endpoint():
    import socket
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    from spark_rapids_tpu.obs import metrics as M
    conf = RapidsConf({"spark.rapids.metrics.port": port})
    bound = M.maybe_start_http_server(conf)
    if bound is None and M._http_server == "failed":
        pytest.skip("port raced away")
    assert bound == port
    # idempotent: second call reuses the server
    assert M.maybe_start_http_server(conf) == port
    M.REGISTRY.counter("rapids_http_test_total").inc()
    body = urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metrics", timeout=5).read().decode()
    assert _load_checker().check_prometheus(body) == []
    assert urllib.request.urlopen(
        f"http://127.0.0.1:{port}/", timeout=5).status == 200
    with pytest.raises(urllib.error.HTTPError):
        urllib.request.urlopen(f"http://127.0.0.1:{port}/nope",
                               timeout=5)


def test_worker_snapshot_flush_and_read(tmp_path):
    from spark_rapids_tpu.obs.metrics import (flush_worker_metrics,
                                              read_worker_metrics)
    r = MetricsRegistry()
    r.counter("n_total").inc(7)
    flush_worker_metrics(str(tmp_path), 0, r)
    # a torn snapshot must not break the merge
    with open(os.path.join(str(tmp_path), "metrics", "w1.json"),
              "w") as f:
        f.write('{"torn":')
    tagged = read_worker_metrics(str(tmp_path))
    assert [t for t, _ in tagged] == ["w0"]
    assert tagged[0][1]["n_total"]["samples"][""] == 7


# --- critical path ----------------------------------------------------------

def _span(name, cat, sid, parent, ts, dur, pid=0, args=None):
    return {"name": name, "cat": cat, "span_id": sid, "parent_id": parent,
            "ts": ts, "dur": dur, "pid": pid, "args": args or {}}


def test_critical_path_follows_dominant_child():
    spans = [
        _span("query", "query", "q", None, 0.0, 10.0),
        _span("stage 1", "stage", "s1", "q", 0.0, 2.0),
        _span("stage 2", "stage", "s2", "q", 2.0, 7.0),
        _span("shuffle_fetch", "shuffle", "f", "s2", 2.0, 6.2, pid=1),
    ]
    path = critical_path(spans)
    assert [p["name"] for p in path] == ["query", "stage 2",
                                         "shuffle_fetch"]
    leaf = path[-1]
    assert leaf["self_s"] == pytest.approx(6.2)
    assert leaf["frac"] == pytest.approx(0.62)
    text = "\n".join(format_critical_path(spans))
    assert "62% of wall time is shuffle_fetch (shuffle)" in text


def test_critical_path_names_retry_overhead():
    spans = [
        _span("query", "query", "q", None, 0.0, 10.0),
        _span("attempt t1 a0", "attempt", "t1.a0", "q", 0.0, 4.0,
              pid=1, args={"state": "err"}),
        _span("attempt t1 a1", "attempt", "t1.a1", "q", 4.0, 6.0,
              pid=2, args={"state": "ok"}),
    ]
    text = "\n".join(format_critical_path(spans))
    assert "retry overhead" in text and "attempt t1 a0" in text
    assert "40% of wall" in text


def test_critical_path_empty_and_orphans():
    assert critical_path([]) == []
    # orphan parents (dropped spans) must not crash the miner
    spans = [_span("a", "op", "1", "gone", 0.0, 1.0)]
    assert [p["name"] for p in critical_path(spans)] == ["a"]


# --- hotspot keying on stable instance ids (satellite) ----------------------
# The old name-based dedup across AQE-duplicated instance labels is
# GONE: planner-assigned #op<N> ids make AQE deep copies of a reused
# sub-plan accumulate into one metric row at the store itself, while
# two genuinely distinct instances of the same operator class rank as
# separate hotspots (per-instance attribution).

def test_profile_report_keys_hotspots_on_stable_instance_ids():
    from spark_rapids_tpu.exec.base import TpuMetric
    from spark_rapids_tpu.exec import HostBatchSourceExec, TpuProjectExec
    from spark_rapids_tpu.expr import Alias, UnresolvedColumn as col
    from spark_rapids_tpu.planner import overrides
    from spark_rapids_tpu.tools import profile_report
    src = HostBatchSourceExec([gen_table([IntegerGen()], 50, seed=1)])
    pp = overrides(TpuProjectExec([Alias(col("c0"), "x")], src),
                   RapidsConf())
    pp.collect()
    ctx = pp.last_ctx
    # an AQE-reused exchange keeps ONE stable label, so both uses hit
    # the same store entry; a second exchange instance keeps its own
    for label, v in (("ShuffleExchangeExec#op90", 0.75),
                     ("ShuffleExchangeExec#op91", 0.25)):
        m = TpuMetric("opTime")
        m.value = v
        ctx.metrics[label] = {"opTime": m}
    rep = profile_report(pp)
    assert "ShuffleExchangeExec#op90" in rep
    assert "ShuffleExchangeExec#op91" in rep
    assert "(x2)" not in rep  # the merge hack is gone
    assert "750.00ms" in rep and "250.00ms" in rep


# --- event-log reader guarantees (satellite) --------------------------------

def test_read_event_logs_tolerates_torn_last_line(tmp_path):
    from spark_rapids_tpu.tools.event_log import read_event_logs
    p = tmp_path / "app-1-1.jsonl"
    p.write_text(json.dumps({"a": 1}) + "\n"
                 + json.dumps({"b": 2}) + "\n"
                 + '{"torn": tru')  # crashed writer mid-line
    evs = list(read_event_logs(str(tmp_path)))
    assert evs == [{"a": 1}, {"b": 2}]


def test_plan_fingerprint_stable_and_sensitive():
    from spark_rapids_tpu.exec import HostBatchSourceExec, TpuProjectExec
    from spark_rapids_tpu.expr import Alias, UnresolvedColumn as col
    from spark_rapids_tpu.tools.event_log import plan_fingerprint

    def build(extra_project):
        src = HostBatchSourceExec([gen_table([IntegerGen()], 10, seed=1)])
        plan = TpuProjectExec([Alias(col("c0"), "x")], src)
        if extra_project:
            plan = TpuProjectExec([Alias(col("x"), "y")], plan)
        return plan

    # stable across runs: instance ids (#N) differ between the two
    # builds but must not leak into the fingerprint
    assert plan_fingerprint(build(False)) == plan_fingerprint(build(False))
    # sensitive to the operator tree
    assert plan_fingerprint(build(False)) != plan_fingerprint(build(True))


# --- ML path query events (satellite) ---------------------------------------

def test_ml_path_emits_query_events(tmp_path):
    from spark_rapids_tpu import TpuSession
    from spark_rapids_tpu.ml import columnar_rdd, to_feature_matrix
    from spark_rapids_tpu.tools.event_log import read_event_logs
    trace_dir = str(tmp_path / "traces")
    s = TpuSession({"spark.rapids.eventLog.dir": str(tmp_path),
                    "spark.rapids.trace.dir": trace_dir})
    df = s.create_dataframe({"a": [1.0, 2.0, 3.0], "b": [4, 5, 6]})
    list(columnar_rdd(df))
    to_feature_matrix(df, ["a"], "b")
    evs = [e for e in read_event_logs(str(tmp_path))
           if e.get("type") != "scheduler"]
    assert len(evs) == 2
    assert all("fingerprint" in e and e["wall_s"] > 0 for e in evs)
    # the embedded trace summary must reference a trace that EXISTS
    written = {n for n in os.listdir(trace_dir)}
    for e in evs:
        assert f"trace-{e['trace']['trace_id']}.json" in written


# --- the acceptance test: stitched trace across a worker crash --------------

def _crash_plan():
    """2-stage query (map shuffle + reduce agg), two source batches so
    the map stage splits across both workers."""
    from spark_rapids_tpu.exec.base import HostBatchSourceExec
    from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec
    from spark_rapids_tpu.exec.exchange import TpuShuffleExchangeExec
    from spark_rapids_tpu.expr import Alias, UnresolvedColumn as col
    from spark_rapids_tpu.expr.aggregates import Sum
    from spark_rapids_tpu.shuffle.partitioner import HashPartitioning
    rbs = [gen_table([IntegerGen(min_val=0, max_val=9, nullable=False),
                      LongGen(nullable=False)], n, seed=s,
                     names=["k", "v"])
           for n, s in [(400, 1), (350, 2)]]
    src = HostBatchSourceExec(rbs)
    exch = TpuShuffleExchangeExec(HashPartitioning([col("k")], 4), src)
    return TpuHashAggregateExec(
        [col("k")], [Alias(Sum(col("v")), "s")], exch)


def test_cluster_crash_produces_single_stitched_trace(tmp_path):
    """ISSUE acceptance: injected worker crash; ONE Chrome trace JSON
    holding driver query/stage spans, BOTH attempts of the crashed task
    (failed + retried) with correct parent linkage, and worker-side
    operator spans; metrics aggregate across processes; the trace
    profiler names the retry overhead."""
    from spark_rapids_tpu.cluster import TpuProcessCluster
    from spark_rapids_tpu.exec.base import ExecCtx
    trace_dir = str(tmp_path / "traces")
    conf = RapidsConf({
        "spark.rapids.tpu.test.injectFaults": "crash:q1s1m0:0",
        "spark.rapids.trace.dir": trace_dir,
        "spark.rapids.metrics.enabled": True,
    })
    plan = _crash_plan()
    with TpuProcessCluster(n_workers=2, conf=conf) as c:
        got = c.run_query(plan)
        trace_path = c.last_trace_path
        prom = c.prometheus_text()

    # correct results despite the crash
    from spark_rapids_tpu.columnar.arrow_bridge import arrow_schema
    want = pa.Table.from_batches(
        list(plan.execute_cpu(ExecCtx())),
        schema=arrow_schema(plan.output_schema))
    key = lambda t: sorted(t.to_pylist(), key=lambda d: d["k"])
    assert key(got) == key(want)

    # ONE stitched trace file, schema-valid
    assert trace_path and os.path.dirname(trace_path) == trace_dir
    assert [n for n in os.listdir(trace_dir)
            if n.endswith(".json")] == [os.path.basename(trace_path)]
    assert _load_checker().check_trace(trace_path) == []

    spans = load_chrome_trace(trace_path)
    by_id = {s["span_id"]: s for s in spans}
    # driver query + stage spans
    query = [s for s in spans if s["cat"] == "query"]
    assert len(query) == 1 and query[0]["pid"] == 0
    stages = {s["name"]: s for s in spans if s["cat"] == "stage"}
    assert "stage map s1" in stages and "stage final" in stages
    assert all(s["parent_id"] == query[0]["span_id"]
               for s in stages.values())
    # both attempts of the crashed task, linked under the map stage
    atts = {s["name"]: s for s in spans if s["cat"] == "attempt"
            and "q1s1m0" in s["name"]}
    assert set(atts) == {"attempt q1s1m0 a0", "attempt q1s1m0 a1"}
    assert atts["attempt q1s1m0 a0"]["args"]["state"] == "err"
    assert atts["attempt q1s1m0 a1"]["args"]["state"] == "ok"
    for s in atts.values():
        assert by_id[s["parent_id"]]["name"] == "stage map s1"
    # the retried attempt ran on a worker: its task span parents onto
    # the deterministic attempt span id, and operator spans nest below
    task = [s for s in spans if s["cat"] == "task"
            and s["name"].startswith("task q1s1m0 a1")]
    assert len(task) == 1 and task[0]["pid"] > 0
    assert task[0]["parent_id"] == atts["attempt q1s1m0 a1"]["span_id"]
    ops = [s for s in spans if s["cat"] == "op" and s["pid"] > 0]
    assert ops, "no worker-side operator spans"
    shuf = [s for s in spans if s["cat"] == "shuffle" and s["pid"] > 0]
    assert any(s["name"].startswith("shuffle_write") for s in shuf)

    # cross-process metrics: driver scheduler counters + worker flushes
    assert _load_checker().check_prometheus(prom) == []
    assert ('rapids_scheduler_events_total{event="task_failed",'
            'proc="driver"}') in prom
    assert 'proc="w' in prom
    assert "rapids_shuffle_partitions_written_total" in prom

    # the critical-path miner names the retry overhead
    rep = profile_trace(trace_path)
    assert "retry overhead" in rep and "attempt q1s1m0 a0" in rep


def test_cluster_trace_disabled_has_zero_surface(tmp_path):
    """With tracing off nothing is written and task payloads carry no
    trace context (the near-zero-overhead-when-disabled guarantee)."""
    from spark_rapids_tpu.cluster import TpuProcessCluster
    plan = _crash_plan()
    with TpuProcessCluster(n_workers=2) as c:
        c.run_query(plan)
        assert c.last_trace_path is None
        assert c.last_scheduler.tracer is NULL_TRACER \
            or not c.last_scheduler.tracer.enabled


# --- one span, two sinks: the scan's spans on the profiler's clock ----------

Q6_TEXT = """select sum(l_extendedprice * l_discount) as revenue
from lineitem
where l_shipdate >= date '1994-01-01' and l_shipdate < date '1995-01-01'
  and l_discount between 0.05 and 0.07 and l_quantity < 24"""

# one dispatch per row group, all from ONE feeder thread, so that the
# second batch meets the arena its first decode still owns
_SCAN_CONF = {"spark.sql.shuffle.partitions": "1",
              "spark.rapids.sql.scan.coalesceTargetBytes": "0",
              "spark.rapids.sql.scan.uploadThreads": "1"}

_SPAN_TABLE = ("spark:query", "spark:admit", "spark:op",
               "spark:scan.fetch", "spark:scan.read",
               "spark:scan.wait", "spark:scan.assemble",
               "spark:scan.arena_wait", "spark:scan.upload",
               "spark:scan.dispatch", "spark:download", "spark:finish",
               "spark:program")


def _lineitem_files(base, files=3, rows=1500, row_group=500, seed=7):
    """A rehearsal-size lineitem: the four columns Q6 reads and a
    string beside them, several row groups a file."""
    import datetime

    import numpy as np
    import pyarrow.parquet as pq
    rng = np.random.default_rng(seed)
    day0 = datetime.date(1993, 6, 1)
    paths = []
    for i in range(files):
        t = pa.table({
            "l_quantity": rng.integers(1, 51, rows).astype("float64"),
            "l_extendedprice": rng.uniform(900.0, 105000.0, rows),
            "l_discount": rng.integers(0, 11, rows) / 100.0,
            "l_shipdate": pa.array(
                [day0 + datetime.timedelta(days=int(d))
                 for d in rng.integers(0, 900, rows)], pa.date32()),
            "l_comment": pa.array([f"c{j % 97}" for j in range(rows)]),
        })
        p = os.path.join(str(base), f"lineitem-{i:02d}.parquet")
        pq.write_table(t, p, row_group_size=row_group)
        paths.append(p)
    return paths


def _q6_plan(paths, conf=None):
    from spark_rapids_tpu import TpuSession
    from spark_rapids_tpu.planner import TpuOverrides
    s = TpuSession(dict(_SCAN_CONF, **(conf or {})))
    s.register_table("lineitem", s.read_parquet(paths))
    return TpuOverrides(s.conf).apply(s.sql(Q6_TEXT)._node)


def _scan_metrics(pp):
    by_name = {}
    for node_metrics in pp.last_ctx.metrics.values():
        for k, m in node_metrics.items():
            if k in ("scanTime", "fetchTime", "fetchAheadMax",
                     "assembleTime", "uploadTime", "uploadWaitTime",
                     "arenaWaitTime"):
                by_name[k] = by_name.get(k, 0.0) + m.value
    return by_name


def _host_spans(trace_dir):
    """``(name, start_ns, end_ns, stats, line)`` of every ``spark:``
    event of the profile written under ``trace_dir``."""
    import glob

    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    out = []
    for pl in ProfileData.from_file(path).planes:
        if pl.name != "/host:CPU":
            continue
        for li, ln in enumerate(pl.lines):
            for e in ln.events:
                if e.name.startswith("spark:"):
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns,
                                dict(e.stats), (li, ln.name)))
    return out


def test_profiler_session_carries_every_span_of_a_parquet_query(
        tmp_path, monkeypatch):
    """A query under ``jax.profiler.trace`` and nothing else: the host
    plane holds every span of the local query path, on the threads that
    did the work, each with the query's id and a parent that leads to
    ``spark:query``; no file is written; ``bytes`` of the uploads is
    what ``jax.device_put`` was handed on the feeder threads."""
    import jax
    import numpy as np
    put, real_put = [], jax.device_put

    def spy(x, *a, **kw):
        if isinstance(x, np.ndarray) and \
                threading.current_thread().name.startswith("scan-upload"):
            put.append(x.nbytes)
        return real_put(x, *a, **kw)

    monkeypatch.setattr(jax, "device_put", spy)
    pp = _q6_plan(_lineitem_files(tmp_path))
    prof = str(tmp_path / "prof")
    with jax.profiler.trace(prof):
        table = pp.collect()
    assert table.num_rows == 1
    tracer = pp.last_ctx.tracer
    assert tracer.enabled and tracer is not NULL_TRACER
    assert not [n for n in os.listdir(tmp_path) if n.startswith("trace-")]
    spans = _host_spans(prof)
    names = {s[0] for s in spans}
    assert names == set(_SPAN_TABLE)
    by_id = {s[3]["span"]: s for s in spans}
    assert len(by_id) == len(spans) == len(tracer.spans)
    root, = [s for s in spans if s[0] == "spark:query"]
    assert "parent" not in root[3] and root[3]["fingerprint"]
    for s in spans:
        assert s[3]["query"] == "q" + tracer.trace_id
        at = s
        while at is not root:  # KeyError: a parent that is not there
            at = by_id[at[3]["parent"]]
    # whose line each span is on: fetches on the pool, the walks all on
    # the feeders' source thread (where it waits for the fetches), the
    # feeder's stages on a scan-upload thread, the rest on the caller's
    line_of = lambda n: {s[4] for s in spans if s[0] == n}  # noqa: E731
    caller, = line_of("spark:query")
    for n in ("spark:admit", "spark:op", "spark:download", "spark:finish"):
        assert line_of(n) == {caller}
    assert any(name.startswith("scan-fetch")
               for _, name in line_of("spark:scan.fetch"))
    source, = line_of("spark:scan.read")
    assert source != caller and source not in line_of("spark:scan.fetch")
    feeder = line_of("spark:scan.dispatch")
    assert len(feeder) == 1 and not feeder & {caller, source}
    assert next(iter(feeder))[1].startswith("scan-upload")
    for n in ("spark:scan.assemble", "spark:scan.arena_wait",
              "spark:scan.upload"):
        assert line_of(n) == feeder
    waits = {s[3]["on"]: s[4] for s in spans if s[0] == "spark:scan.wait"}
    assert waits["upload"] == caller and waits["fetch"] == source
    # what the spans carry: nine row groups read, nine programs
    # dispatched under the name they compile under, the node label with
    # its '#' replaced, the bytes handed to the device
    reads = [s[3] for s in spans if s[0] == "spark:scan.read"]
    # (column pruning: Q6 names four of the files' five columns)
    assert len(reads) == 9 and all(
        r["chunks"] == r["columns"] == 4 and r["file_columns"] == 5
        and r["bytes"] > 0 for r in reads)
    assert {r["file"] for r in reads} == {f"lineitem-0{i}.parquet"
                                          for i in range(3)}
    # ... each walked from what its fetch brought in (`bytes` there:
    # the chunks as the file holds them), and saying how many fetched
    # row groups waited behind it when it started
    fetches = [s[3] for s in spans if s[0] == "spark:scan.fetch"]
    assert sorted((f["file"], f["rg"]) for f in fetches) == \
        sorted((r["file"], r["rg"]) for r in reads)
    assert all(f["bytes"] > 0 for f in fetches)
    assert all(0 <= r["ahead"] <= 8 for r in reads)
    programs = [s[3] for s in spans if s[0] == "spark:scan.dispatch"]
    assert len(programs) == 9 and all(
        p["program"] == "jit_scan_decode_chain" and p["fused"]
        for p in programs)
    # no chunk of these files holds a null: none ran a definition-level
    # pass, and the span says what the ExecCtx counter says
    assert [p["null_free"] for p in programs] == [4] * 9
    assert sum(int(m["nullFreeChunks"].value)
               for m in pp.last_ctx.metrics.values()
               if "nullFreeChunks" in m) == 36
    ops = {s[3]["op"] for s in spans if s[0] == "spark:op"}
    assert ops and all("#" not in o and ":op" in o for o in ops)
    uploads = [s[3]["bytes"] for s in spans if s[0] == "spark:scan.upload"]
    assert sum(uploads) == sum(put) and len(uploads) == len(put) == 9
    down, = [s[3] for s in spans if s[0] == "spark:download"]
    assert down["rows"] == 1 and down["bytes"] == table.nbytes


Q1_TEXT = """select l_returnflag, l_linestatus, sum(l_quantity) as sum_qty,
  sum(l_extendedprice * (1 - l_discount)) as sum_disc_price,
  avg(l_discount) as avg_disc, count(*) as count_order
from lineitem
where l_shipdate <= date '1998-12-01' - interval '90' day (3)
group by l_returnflag, l_linestatus
order by l_returnflag, l_linestatus"""


def _q1_files(base, files=3, rows=1500, row_group=500, seed=9):
    """``_lineitem_files`` with the two flag columns Q1 groups by."""
    import numpy as np
    import pyarrow.parquet as pq
    rng = np.random.default_rng(seed)
    paths = _lineitem_files(base, files, rows, row_group, seed)
    for p in paths:
        t = pq.read_table(p)
        t = t.append_column("l_returnflag", pa.array(
            rng.choice(["A", "N", "R"], t.num_rows)))
        t = t.append_column("l_linestatus", pa.array(
            rng.choice(["F", "O"], t.num_rows)))
        pq.write_table(t, p, row_group_size=row_group)
    return paths


def test_grouped_aggregate_spans_and_counters(tmp_path):
    """A grouped aggregate's fused ``op`` spans carry the lanes its
    group-by sorts (``agg_lanes``, the batch's capacity) and its key
    count; ``spark:agg.final`` holds the concat and the final's dispatch;
    the ExecCtx counters say the same and EXPLAIN ANALYZE shows them.
    The ungrouped Q6 carries none of it."""
    import jax
    from spark_rapids_tpu import TpuSession
    from spark_rapids_tpu.planner import TpuOverrides
    s = TpuSession(dict(_SCAN_CONF))
    s.register_table("lineitem", s.read_parquet(_q1_files(tmp_path)))
    pp = TpuOverrides(s.conf).apply(s.sql(Q1_TEXT)._node)
    prof = str(tmp_path / "prof")
    with jax.profiler.trace(prof):
        table = pp.collect()
    assert table.num_rows == 6
    spans = _host_spans(prof)
    grouped = [a for n, _, _, a, _ in spans
               if n == "spark:op" and "agg_lanes" in a]
    # one batch a row group: nine partials of 512 lanes
    assert [(a["keys"], a["agg_lanes"]) for a in grouped] == [(2, 512)] * 9
    final, = [a for n, _, _, a, _ in spans if n == "spark:agg.final"]
    assert final["partials"] == 9 and final["lanes"] >= 9 * 6
    counters = {k: m.value for ms in pp.last_ctx.metrics.values()
                for k, m in ms.items()
                if k in ("aggSortedLanes", "aggPartials")}
    assert counters == {"aggSortedLanes": 9 * 512, "aggPartials": 9}
    analyzed = pp.explain_analyze()
    assert "aggSortedLanes=4608" in analyzed and "aggPartials=9" in analyzed
    (tmp_path / "q6").mkdir()
    pp6 = _q6_plan(_lineitem_files(tmp_path / "q6"))
    with jax.profiler.trace(str(tmp_path / "prof6")):
        assert pp6.collect().num_rows == 1
    spans6 = _host_spans(str(tmp_path / "prof6"))
    assert not [a for n, _, _, a, _ in spans6 if "agg_lanes" in a]
    assert "spark:agg.final" not in {n for n, *_ in spans6}
    assert not [k for ms in pp6.last_ctx.metrics.values() for k in ms
                if k.startswith("agg")]


def test_profile_path_session_carries_the_spans(tmp_path):
    """``spark.rapids.profile.path`` and nothing else: ``collect`` starts
    the profiler session BEFORE it makes the ``ExecCtx``, so the query's
    tracer is live and the profile holds the program's spans."""
    prof = str(tmp_path / "prof")
    pp = _q6_plan(_lineitem_files(tmp_path, files=1),
                  {"spark.rapids.profile.path": prof})
    assert pp.collect().num_rows == 1
    assert pp.last_ctx.tracer.enabled
    names = {s[0] for s in _host_spans(prof)}
    assert {"spark:query", "spark:scan.read", "spark:scan.dispatch",
            "spark:download", "spark:finish"} <= names


def test_untraced_query_records_no_span(tmp_path, monkeypatch):
    """No profiler session and no trace directory: the ExecCtx holds the
    shared no-op, and not one ``Span`` is made."""
    from spark_rapids_tpu.obs import tracer as tracer_mod
    made = []
    real = tracer_mod.Span.__init__
    monkeypatch.setattr(tracer_mod.Span, "__init__",
                        lambda self, *a, **kw: (made.append(a),
                                                real(self, *a, **kw))[1])
    pp = _q6_plan(_lineitem_files(tmp_path))
    assert pp.collect().num_rows == 1
    assert pp.last_ctx.tracer is NULL_TRACER
    assert made == []
    # the counters still count: each stage is timed at its one site
    m = _scan_metrics(pp)
    assert m["scanTime"] > 0 and m["assembleTime"] > 0 \
        and m["uploadTime"] > 0 and m["uploadWaitTime"] > 0


def test_scan_counters_are_their_spans_and_chrome_nests_them(tmp_path):
    """``spark.rapids.trace.dir`` alone: each scan counter is the summed
    duration of its spans, and the Chrome JSON holds the scan's spans
    under the query span."""
    trace_dir = str(tmp_path / "traces")
    pp = _q6_plan(_lineitem_files(tmp_path),
                  {"spark.rapids.trace.dir": trace_dir})
    assert pp.collect().num_rows == 1
    name, = os.listdir(trace_dir)
    spans = load_chrome_trace(os.path.join(trace_dir, name))
    total = lambda keep: sum(s["dur"] for s in spans if keep(s))  # noqa
    m = _scan_metrics(pp)
    rel = 1e-5  # the JSON rounds a span to a thousandth of a microsecond
    assert m["scanTime"] == pytest.approx(total(
        lambda s: s["name"] == "scan.read" or s["name"] == "scan.wait"
        and s["args"]["on"] == "fetch"), rel=rel)
    assert m["fetchTime"] == pytest.approx(total(
        lambda s: s["name"] == "scan.fetch"), rel=rel)
    reads = [s for s in spans if s["name"] == "scan.read"]
    assert m["fetchAheadMax"] == max(s["args"]["ahead"] for s in reads)
    assert m["uploadWaitTime"] == pytest.approx(total(
        lambda s: s["name"] == "scan.wait"
        and s["args"]["on"] == "upload"), rel=rel)
    assert m["assembleTime"] == pytest.approx(total(
        lambda s: s["name"] == "scan.assemble"), rel=rel)
    assert m["uploadTime"] == pytest.approx(total(
        lambda s: s["name"] in ("scan.upload", "scan.dispatch")), rel=rel)
    assert m["arenaWaitTime"] == pytest.approx(total(
        lambda s: s["name"] == "scan.arena_wait"), rel=rel)
    assert m["arenaWaitTime"] > 0
    query, = [s for s in spans if s["name"] == "query"]
    scan = [s for s in spans if s["cat"] == "scan"]
    assert {s["name"] for s in scan} == {
        "scan.fetch", "scan.read", "scan.wait", "scan.assemble",
        "scan.arena_wait", "scan.upload", "scan.dispatch"}
    assert all(s["parent_id"] == query["span_id"] for s in scan)
    for n in ("admit", "download", "finish"):
        s, = [s for s in spans if s["name"] == n]
        assert s["parent_id"] == query["span_id"]
        assert query["ts"] <= s["ts"] and \
            s["ts"] + s["dur"] <= query["ts"] + query["dur"] + 1e-6


def test_local_query_files_pass_the_schema_checker(tmp_path):
    """What ONE in-process parquet query leaves for an operator: a Chrome
    trace the checker accepts, and the scan's assemble / upload
    histograms in a Prometheus dump the checker accepts."""
    trace_dir = str(tmp_path / "traces")
    pp = _q6_plan(_lineitem_files(tmp_path, files=1),
                  {"spark.rapids.trace.dir": trace_dir})
    assert pp.collect().num_rows == 1
    name, = os.listdir(trace_dir)
    checker = _load_checker()
    assert checker.check_trace(os.path.join(trace_dir, name)) == []
    prom = dump_prometheus()
    assert checker.check_prometheus(prom) == []
    for family in ("rapids_scan_assemble_seconds",
                   "rapids_scan_upload_seconds"):
        assert family + "_count" in prom, family


def test_q6_compiles_only_programs_of_the_registry(tmp_path):
    """Every program a rehearsal-size Q6 sends to the compiler carries a
    name of ``programs.PROGRAM_NAMES``: the next closure, lambda or
    partial handed to ``jax.jit`` on this path (``jit(build)``,
    ``jit(composed)``, ``jit(<lambda>)``, ``jit(_unknown)``) fails here."""
    import jax.monitoring

    from spark_rapids_tpu.programs import PROGRAM_NAMES
    seen = []

    def listen(event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            seen.append(kw.get("fun_name"))

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        # sizes no other test uses: these programs are compiled here
        pp = _q6_plan(_lineitem_files(tmp_path, rows=1311, row_group=437))
        assert pp.collect().num_rows == 1
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    names = {n[len("jit("):-1] for n in seen}
    assert {"scan_decode_chain", "concat_batches", "agg_final"} <= names
    assert names <= PROGRAM_NAMES, names - PROGRAM_NAMES


def _q3_star_files(base, seed=11):
    """A rehearsal-size store-channel star: the ten columns q3 names and
    one beside them in each table."""
    import numpy as np
    import pyarrow.parquet as pq
    rng = np.random.default_rng(seed)
    nd, ni, ns = 211, 157, 1733  # sizes no other test uses
    brand = rng.integers(1, 30, ni).astype("int32")
    tables = {
        "date_dim": pa.table({
            "d_date_sk": np.arange(1, nd + 1, dtype="int32"),
            "d_year": rng.integers(1998, 2003, nd).astype("int32"),
            "d_moy": rng.choice([11, 11, 4], nd).astype("int32"),
            "d_day_name": pa.array([f"day{j % 7}" for j in range(nd)])}),
        "item": pa.table({
            "i_item_sk": np.arange(1, ni + 1, dtype="int32"),
            "i_brand_id": brand,
            "i_brand": pa.array([f"brand #{b}" for b in brand]),
            "i_manufact_id": rng.choice([128, 128, 5], ni).astype("int32"),
            "i_item_desc": pa.array([f"desc {j}" for j in range(ni)])}),
        "store_sales": pa.table({
            "ss_sold_date_sk": rng.integers(1, nd + 1, ns).astype("int32"),
            "ss_item_sk": rng.integers(1, ni + 1, ns).astype("int32"),
            "ss_ext_sales_price": np.round(rng.uniform(0, 2e4, ns), 2),
            "ss_ticket_number": rng.integers(1, 1 << 40, ns)})}
    paths = {}
    for name, t in tables.items():
        paths[name] = [os.path.join(str(base), name + ".parquet")]
        pq.write_table(t, paths[name][0])
    return paths


def _q3_planner(base):
    """A session over ``_q3_star_files`` and a function that plans the
    benchmark's q3 text anew each call."""
    from spark_rapids_tpu import TpuSession
    from spark_rapids_tpu.planner import TpuOverrides
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    with open(os.path.join(bench, "queries", "tpcds", "q3.sql")) as f:
        text = "\n".join(ln for ln in f.read().splitlines()
                         if not ln.lstrip().startswith("--"))
    s = TpuSession(dict(_SCAN_CONF,
                        **{"spark.sql.shuffle.partitions": "1"}))
    for name, paths in _q3_star_files(base).items():
        s.register_table(name, s.read_parquet(paths))
    return lambda: TpuOverrides(s.conf).apply(s.sql(text)._node)


def test_q3_compiles_only_named_programs(tmp_path):
    """Every program a rehearsal-size TPC-DS q3 (two joins, a
    string-keyed group-by, order-by, limit) sends to the compiler is one
    of the engine's ``named_jit`` sites, by a name of
    ``programs.PROGRAM_NAMES``, or a single primitive JAX dispatches for
    an eager ``jnp`` call (``programs.EAGER_OPS``): a join that reached
    the trace as ``jit__unknown`` / ``jit__lambda_`` (ROADMAP S0) fails
    here."""
    import jax.monitoring

    from spark_rapids_tpu.programs import EAGER_OPS, PROGRAM_NAMES
    plan = _q3_planner(tmp_path)
    seen = []

    def listen(event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            seen.append(kw.get("fun_name"))

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        pp = plan()
        assert pp.collect().num_rows > 10
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    names = {n[len("jit("):-1] for n in seen}
    assert {"scan_decode_chain", "join_build_probe", "join_probe",
            "join_count", "join_gather", "agg_final",
            "sort_batch"} <= names, names
    assert names <= PROGRAM_NAMES | EAGER_OPS, \
        names - PROGRAM_NAMES - EAGER_OPS


# --- spark:program: the re-jit where it happens -----------------------------


def _planner(query, base):
    """Plans a rehearsal-size q6 or q3 anew each call, over files made
    once (q6 at sizes no other test uses: its programs compile here)."""
    if query == "q3":
        return _q3_planner(base)
    paths = _lineitem_files(base, rows=1203, row_group=401)
    return lambda: _q6_plan(paths)


def _program_spans(prof):
    spans = _host_spans(prof)
    by_id = {s[3]["span"]: s for s in spans}
    return [s for s in spans if s[0] == "spark:program"], by_id


@pytest.mark.parametrize("query", ["q6", "q3"])
def test_every_named_program_call_is_a_program_span(tmp_path, monkeypatch,
                                                    query):
    """Under a profiler session every call of a named program on the
    query's path (caller, feeders, fetch pool) is made inside a live span
    and lies in a ``spark:program`` span on its thread, whose ``program``
    is in the registry and whose parent is the span of the program that
    made the call; a call inside another program's trace opens none."""
    import collections

    import jax

    from spark_rapids_tpu import programs
    from spark_rapids_tpu.obs.tracer import LIVE
    calls = []
    real = programs.NamedProgram.__call__

    def spy(self, *a, **kw):
        if programs._HERE.call is None and not programs._HERE.depth:
            calls.append((self.name, LIVE.tracer is not None))
        return real(self, *a, **kw)

    monkeypatch.setattr(programs.NamedProgram, "__call__", spy)
    pp = _planner(query, tmp_path)()
    prof = str(tmp_path / "prof")
    with jax.profiler.trace(prof):
        assert pp.collect().num_rows >= 1
    spans, by_id = _program_spans(prof)
    assert calls and all(live for _, live in calls)
    assert collections.Counter(s[3]["program"] for s in spans) == \
        collections.Counter(name for name, _ in calls)
    parents = collections.Counter()
    for name, _, _, args, line in spans:
        assert args["program"] in programs.PROGRAM_NAMES
        assert args["miss"] in (0, 1) and args["cache_hits"] >= 0
        parent = by_id[args["parent"]]
        assert parent[0] != "spark:program" and parent[4] == line
        parents[parent[0]] += 1
    # the plan node: an operator's span names it; the decode programs
    # are dispatched by the scan's stage span
    assert parents["spark:op"] and parents["spark:scan.dispatch"]
    ops = {by_id[a["parent"]][3].get("op") for _, _, _, a, _ in spans}
    if query == "q3":
        assert any(o and o.startswith("ShuffledHashJoinExec:") for o in ops)


@pytest.mark.parametrize("query", ["q6", "q3"])
def test_a_plan_made_anew_misses_once_a_program_and_again_never(
        tmp_path, query):
    """A plan made anew from the same text traces every program it
    compiles: ``miss`` sums to the compile requests of its collect (2
    for q6, 11 for q3, as on the chip), each miss with trace and lowering
    seconds; the SAME plan collected again misses nothing and asks the
    compiler for nothing."""
    import jax
    import jax.monitoring
    plan = _planner(query, tmp_path)
    assert plan().collect().num_rows >= 1  # every shape seen once
    requests = []

    def listen(event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            requests.append(kw["fun_name"])

    pp = plan()
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        for k in (1, 2):
            with jax.profiler.trace(str(tmp_path / f"prof{k}")):
                assert pp.collect().num_rows >= 1
            if k == 1:
                anew, requests[:] = len(requests), []
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    spans, _ = _program_spans(str(tmp_path / "prof1"))
    misses = [a for _, _, _, a, _ in spans if a["miss"]]
    assert len(misses) == anew == {"q6": 2, "q3": 11}[query]
    assert all(a["trace_s"] > 0 and a["lower_s"] > 0 and a["compile_s"] > 0
               for a in misses)
    assert all(a["trace_s"] == a["lower_s"] == a["compile_s"] == 0
               for _, _, _, a, _ in spans if not a["miss"])
    spans, _ = _program_spans(str(tmp_path / "prof2"))
    assert spans and requests == [] and not [
        a for _, _, _, a, _ in spans if a["miss"]]


def test_untraced_program_calls_make_no_span_and_lower_as_before(
        tmp_path, monkeypatch):
    """No profiler and no trace directory: a named program's call makes
    no span and no annotation (the tracer's thread-local is unset on
    every calling thread), and ``named_jit(...).lower`` lowers the module
    ``jit_<name>`` as the bare ``jax.jit`` did."""
    import jax
    import jax.numpy as jnp

    from spark_rapids_tpu import programs
    from spark_rapids_tpu.obs import tracer as tracer_mod
    made, live = [], set()

    class Spy(tracer_mod.TraceAnnotation):
        def __init__(self, *a, **kw):
            made.append(a)
            super().__init__(*a, **kw)

    real = programs.NamedProgram.__call__

    def spy(self, *a, **kw):
        live.add(tracer_mod.LIVE.tracer)
        return real(self, *a, **kw)

    monkeypatch.setattr(tracer_mod, "TraceAnnotation", Spy)
    monkeypatch.setattr(programs.NamedProgram, "__call__", spy)
    assert _q6_plan(_lineitem_files(tmp_path)).collect().num_rows == 1
    assert made == [] and live == {None}
    fn = programs.named_jit("sort_batch", lambda x: x * 2)
    x = jnp.arange(5)
    assert "module @jit_sort_batch" in fn.lower(x).as_text()
    assert fn.trace(x).jaxpr is not None
    assert fn.eval_shape(x).shape == (5,)
    assert fn(x).tolist() == [0, 2, 4, 6, 8] and fn._cache_size() == 1
    fn.clear_cache()
    assert fn._cache_size() == 0
    assert jax.jit(lambda x: x * 2).lower(x).as_text() \
        .replace("jit__lambda", "jit_sort_batch") \
        == fn.lower(x).as_text()


def test_registry_counts_the_rejit_by_program_with_tracing_off():
    """The ``rapids_jit_*`` families count every trace by program with
    no tracer anywhere: a named program once a shape, its seconds by
    phase; a jit traced inside its trace, or inside its lowering (a
    scan's loop is traced again as it is lowered), is part of that
    phase; an eager primitive under its own name; a warm call adds
    nothing."""
    import jax
    import jax.numpy as jnp

    from spark_rapids_tpu.obs.metrics import (JIT_HOST_SECONDS,
                                              JIT_TRACES, REGISTRY)
    from spark_rapids_tpu.obs.tracer import LIVE
    from spark_rapids_tpu.programs import _HERE, named_jit

    # a process that ran other tests may hold more than MAX_CHILDREN
    # programs already, and a new one would count under __other__
    REGISTRY.reset()

    def traces(name):
        return JIT_TRACES.labels(name).value

    def nested_probe(x):
        return x * 3

    inner = jax.jit(nested_probe)
    fn = named_jit("agg_single", lambda x: jax.lax.scan(
        lambda c, v: (c + v, c), 0.0, inner(x))[1] + 1)
    phases = [JIT_HOST_SECONDS.labels("agg_single", p).value
              for p in ("trace", "lower", "compile")]
    before = {n: traces(n) for n in ("agg_single", "nested_probe", "clip",
                                     "less")}
    assert LIVE.tracer is None
    x = jnp.arange(1777.0)  # a shape no other test uses
    fn(x)
    fn(x)
    assert traces("agg_single") == before["agg_single"] + 1
    assert all(JIT_HOST_SECONDS.labels("agg_single", p).value > v
               for p, v in zip(("trace", "lower", "compile"), phases))
    fn(jnp.arange(1778.0))
    assert traces("agg_single") == before["agg_single"] + 2
    jnp.clip(jnp.arange(1779.0), 2.0, 9.0)
    assert traces("clip") == before["clip"] + 1
    assert [traces(n) for n in ("nested_probe", "less")] == \
        [before["nested_probe"], before["less"]]
    assert _HERE.depth == 0 and _HERE.call is None and _HERE.hits == 0
