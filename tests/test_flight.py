"""Flight-recorder tier tests: ring bounds, anomaly triggers, memory
timeline, harvest torn-file tolerance, /metrics under concurrent
writers, histogram bisect semantics, trace/event-log retention — plus
the ISSUE acceptance test: with ``spark.rapids.trace.dir`` UNSET, an
injected mid-stage worker crash on ``TpuProcessCluster`` yields exactly
one incident bundle containing the dead worker's preceding ring events,
a memory timeline with a nonzero high-water mark, and straggler/attempt
attribution naming the failed attempt, and ``profiling triage`` renders
it without error."""
import json
import os
import threading
import time
import urllib.request

import pyarrow as pa
import pytest

from asserts import obs_checker as _load_checker
from data_gen import IntegerGen, LongGen, gen_table

from spark_rapids_tpu.config import RapidsConf
from spark_rapids_tpu.obs.anomaly import (AnomalyDetector,
                                          anomalies_from_scheduler,
                                          build_incident_bundle,
                                          conf_delta,
                                          straggler_attribution)
from spark_rapids_tpu.obs.recorder import (RECORDER, FlightRecorder,
                                           memory_timeline, prune_oldest,
                                           read_flight_dumps,
                                           read_worker_rings)
from spark_rapids_tpu.tools.profiling import triage_report


# --- ring buffer ------------------------------------------------------------

def test_ring_bounds_events_and_bytes():
    r = FlightRecorder(max_events=5, max_bytes=1 << 20)
    for i in range(9):
        r.record("t", i=i)
    evs = r.snapshot()
    assert len(evs) == 5 and r.dropped == 4
    assert [e["i"] for e in evs] == [4, 5, 6, 7, 8]  # oldest evicted
    # byte bound evicts even under the event bound
    r2 = FlightRecorder(max_events=10_000, max_bytes=2048)
    for i in range(200):
        r2.record("t", payload="x" * 64)
    assert len(r2.snapshot()) < 40 and r2.dropped > 0


def test_ring_disabled_records_nothing():
    r = FlightRecorder()
    r.configure(RapidsConf({"spark.rapids.flight.enabled": "false"}))
    r.record("t", a=1)
    assert r.snapshot() == []
    r.configure(RapidsConf())  # default is ON
    r.record("t", a=2)
    assert len(r.snapshot()) == 1


def test_ring_snapshot_since():
    r = FlightRecorder()
    r.record("old")
    cut = time.time()
    time.sleep(0.01)
    r.record("new")
    evs = r.snapshot(since=cut)
    assert [e["kind"] for e in evs] == ["new"]


def test_span_tap_joins_ring():
    from spark_rapids_tpu.obs.tracer import Tracer
    RECORDER.configure(RapidsConf())
    RECORDER.clear()
    t = Tracer()
    with t.span("op x", cat="op"):
        pass
    spans = [e for e in RECORDER.snapshot() if e["kind"] == "span"]
    assert spans and spans[-1]["name"] == "op x"


# --- memory timeline --------------------------------------------------------

def test_memory_ledger_transitions_recorded_with_high_water():
    from spark_rapids_tpu.columnar.batch import TpuBatch, bucket_rows
    from spark_rapids_tpu.columnar.column import TpuColumnVector
    from spark_rapids_tpu import datatypes as dt
    from spark_rapids_tpu.memory import DeviceMemoryManager
    import numpy as np
    RECORDER.configure(RapidsConf())
    RECORDER.clear()
    t0 = time.time()
    mgr = DeviceMemoryManager(RapidsConf(
        {"spark.rapids.sql.test.injectRetryOOM": 0,
         "spark.rapids.memory.device.budgetBytes": 1 << 30}))
    n = 64
    col = TpuColumnVector.from_numpy(
        dt.INT64, np.arange(n, dtype=np.int64), None, bucket_rows(n))
    schema = dt.Schema([dt.StructField("a", dt.INT64, False)])
    b = TpuBatch([col], schema, n)
    sb = mgr.register(b)
    sb.spill()
    _ = sb.get()
    sb.release()
    tl = memory_timeline(RECORDER.snapshot(since=t0))
    kinds = [e["ev"] for e in tl["events"]]
    for ev in ("budget", "reserve", "spill", "readback", "release"):
        assert ev in kinds, (ev, kinds)
    assert tl["high_water_bytes"] > 0
    assert tl["budget_bytes"] == 1 << 30
    ts = [e["ts"] for e in tl["events"]]
    assert ts == sorted(ts)


def test_oom_retry_recorded_and_triggers_anomaly():
    from spark_rapids_tpu.columnar.batch import TpuBatch, bucket_rows
    from spark_rapids_tpu.columnar.column import TpuColumnVector
    from spark_rapids_tpu import datatypes as dt
    from spark_rapids_tpu.memory import DeviceMemoryManager
    import numpy as np
    RECORDER.configure(RapidsConf())
    RECORDER.clear()
    t0 = time.time()
    mgr = DeviceMemoryManager(RapidsConf(
        {"spark.rapids.sql.test.injectRetryOOM": 1}))
    n = 8
    col = TpuColumnVector.from_numpy(
        dt.INT64, np.arange(n, dtype=np.int64), None, bucket_rows(n))
    schema = dt.Schema([dt.StructField("a", dt.INT64, False)])
    b = TpuBatch([col], schema, n)
    outs = mgr.with_retry(b, lambda bb: bb)
    assert len(outs) == 2  # split once
    evs = RECORDER.snapshot(since=t0)
    assert any(e.get("ev") == "oom_retry" for e in evs)
    trig = AnomalyDetector().check_task(evs, failed=False)
    assert trig is not None and trig[0] == "oom_retry_cascade"


# --- anomaly detector -------------------------------------------------------

def test_detector_task_failure_and_spill_cascade():
    d = AnomalyDetector(spill_cascade_threshold=2)
    assert d.check_task([], failed=True, error="Boom\nValueError: x") \
        == ("task_failure", "ValueError: x")
    spills = [{"kind": "mem", "ev": "spill"} for _ in range(2)]
    kind, reason = d.check_task(spills, failed=False)
    assert kind == "spill_cascade" and "2" in reason
    assert d.check_task(spills[:1], failed=False) is None
    assert d.check_task([], failed=False) is None


def test_anomalies_from_scheduler_filters_benign_events():
    evs = [
        {"event": "task_submitted", "task": "t1"},
        {"event": "task_failed", "task": "t1", "attempt": 0,
         "worker": 1, "ts": 5.0, "reason": "boom"},
        {"event": "attempt_lost", "task": "t1"},  # benign spec loser
        {"event": "worker_respawn", "worker": 1, "ts": 6.0,
         "reason": "died"},
        {"event": "straggler_detected", "task": "t2", "attempt": 0,
         "worker": 0, "ts": 7.0, "reason": "slow"},
    ]
    out = anomalies_from_scheduler(evs)
    assert [a["kind"] for a in out] == [
        "task_failed", "worker_respawn", "straggler_detected"]


def test_straggler_attribution_flags_failed_and_slow():
    evs = [
        {"event": "task_ok", "stage": "map s1", "task": "m0",
         "attempt": 0, "worker": 0, "wall_s": 1.0},
        {"event": "task_ok", "stage": "map s1", "task": "m1",
         "attempt": 0, "worker": 1, "wall_s": 1.2},
        {"event": "task_ok", "stage": "map s1", "task": "m2",
         "attempt": 1, "worker": 0, "wall_s": 9.0},
        {"event": "task_failed", "stage": "map s1", "task": "m2",
         "attempt": 0, "worker": 1, "wall_s": 0.2, "reason": "err"},
    ]
    att = straggler_attribution(evs, factor=4.0)
    st = att["map s1"]
    assert st["median_ok_s"] == pytest.approx(1.2)
    flagged = {(a["task"], a["attempt"]) for a in st["flagged"]}
    assert ("m2", 0) in flagged   # the failed attempt is named
    assert ("m2", 1) in flagged   # 9.0s > 4 x 1.2s median
    assert ("m0", 0) not in flagged


def test_conf_delta_only_non_defaults():
    c = RapidsConf({"spark.rapids.sql.enabled": "true",       # = default
                    "spark.sql.shuffle.partitions": "4",      # changed
                    "some.unregistered.key": "v"})
    d = conf_delta(c)
    assert "spark.rapids.sql.enabled" not in d
    assert d["spark.sql.shuffle.partitions"] == "4"
    assert d["some.unregistered.key"] == "v"


# --- harvest torn-file tolerance (satellite) --------------------------------

def test_harvest_skips_torn_rings_dumps_and_metrics(tmp_path):
    root = str(tmp_path)
    fdir = os.path.join(root, "flight")
    tdir = os.path.join(root, "tasks")
    os.makedirs(fdir)
    os.makedirs(tdir)
    # one good ring, one torn, one alien shape
    with open(os.path.join(fdir, "w0-11.ring.json"), "w") as f:
        json.dump({"proc": "w0", "pid": 11,
                   "events": [{"ts": 1.0, "kind": "task"}]}, f)
    with open(os.path.join(fdir, "w1-12.ring.json"), "w") as f:
        f.write('{"proc": "w1", "events": [{"t')   # torn mid-write
    with open(os.path.join(fdir, "w2-13.ring.json"), "w") as f:
        json.dump({"proc": "w2", "events": "not-a-list"}, f)
    rings = read_worker_rings(root)
    assert [t for t, _ in rings] == ["w0:11"]
    # one good dump, one torn, one for another query
    with open(os.path.join(tdir, "q1s1m0.a0.w1.task.flight.json"),
              "w") as f:
        json.dump({"proc": "w1", "task": "q1s1m0", "attempt": 0,
                   "trigger": "task_failure", "events": []}, f)
    with open(os.path.join(tdir, "q1s1m1.a0.w0.task.flight.json"),
              "w") as f:
        f.write('{"torn":')
    with open(os.path.join(tdir, "q10s1m0.a0.w0.task.flight.json"),
              "w") as f:
        json.dump({"proc": "w0", "task": "q10s1m0", "attempt": 0,
                   "trigger": "task_failure", "events": []}, f)
    dumps = read_flight_dumps(tdir, query_id="q1")
    assert [d["task"] for d in dumps] == ["q1s1m0"]  # q10 NOT matched
    # torn worker metrics snapshots: same guarantee (existing reader)
    from spark_rapids_tpu.obs.metrics import read_worker_metrics
    os.makedirs(os.path.join(root, "metrics"))
    with open(os.path.join(root, "metrics", "w0.json"), "w") as f:
        f.write('{"half":')
    assert read_worker_metrics(root) == []


def test_bundle_assembly_and_schema(tmp_path):
    sched_events = [
        {"event": "task_failed", "stage": "map s1", "task": "m0",
         "attempt": 0, "worker": 1, "ts": 10.0, "wall_s": 0.5,
         "reason": "boom"},
        {"event": "task_ok", "stage": "map s1", "task": "m0",
         "attempt": 1, "worker": 0, "ts": 11.0, "wall_s": 0.4},
    ]
    driver_events = [
        {"ts": 9.0, "kind": "mem", "ev": "budget", "budget": 100,
         "device": 0, "host": 0},
        {"ts": 9.5, "kind": "mem", "ev": "reserve", "bytes": 10,
         "device": 10, "host": 0},
    ]
    bundle = build_incident_bundle(
        query_id="q1", flight_id="abcd", seq=3,
        trigger_anomalies=anomalies_from_scheduler(sched_events),
        driver_events=driver_events,
        worker_rings=[("w0:11", {"events": [
            {"ts": 9.9, "kind": "task", "ev": "claim", "task": "m0",
             "attempt": 0}]})],
        worker_dumps=[], sched_events=sched_events,
        metrics_snapshot={"driver": {}}, conf=RapidsConf(),
        straggler_factor=6.0)
    assert bundle["incident_id"] == "incident-abcd-3"
    assert bundle["memory_timeline"]["high_water_bytes"] == 10
    p = os.path.join(str(tmp_path), "incident-abcd-3.json")
    with open(p, "w") as f:
        json.dump(bundle, f)
    assert _load_checker().check_flight(p) == []
    # the renderer accepts it
    rep = triage_report(bundle)
    assert "task_failed" in rep and "high water" in rep


# --- /metrics endpoint under concurrent writers (satellite) -----------------

def test_http_metrics_endpoint_under_concurrent_updates():
    import socket
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    from spark_rapids_tpu.obs import metrics as M
    srv_before = M._http_server  # restore after: the server is a
    # process singleton and later tests assert on a fresh bind
    conf = RapidsConf({"spark.rapids.metrics.port": port})
    bound = M.maybe_start_http_server(conf)
    if bound is None:
        pytest.skip("metrics http server unavailable (bound elsewhere)")
    checker = _load_checker()
    c = M.REGISTRY.counter("rapids_flight_conc_total", "", ("k",))
    h = M.REGISTRY.histogram("rapids_flight_conc_seconds")
    stop = threading.Event()

    def hammer():
        i = 0
        while not stop.is_set():
            c.labels(f"k{i % 4}").inc()
            h.observe((i % 100) / 1000.0)
            i += 1

    threads = [threading.Thread(target=hammer) for _ in range(3)]
    for t in threads:
        t.start()
    try:
        for _ in range(10):
            body = urllib.request.urlopen(
                f"http://127.0.0.1:{bound}/metrics",
                timeout=5).read().decode()
            # every scrape parses and holds the histogram invariants
            # (cumulative buckets, +Inf == _count) mid-hammer
            assert checker.check_prometheus(body) == []
    finally:
        stop.set()
        for t in threads:
            t.join()
        if srv_before is None and M._http_server not in (None, "failed"):
            M._http_server.shutdown()
            M._http_server.server_close()
            M._http_server = None


# --- histogram bisect semantics (satellite) ---------------------------------

def test_histogram_bisect_bucket_edges():
    from spark_rapids_tpu.obs.metrics import MetricsRegistry
    r = MetricsRegistry()
    h = r.histogram("h_seconds", buckets=(0.1, 1.0, float("inf")))
    for v in (0.1, 0.100001, 1.0, 50.0, float("inf"), 0.0):
        h.observe(v)
    snap = r.snapshot()["h_seconds"]["samples"][""]
    # v <= le semantics: 0.1 and 0.0 in bucket 0; 0.100001 and 1.0 in
    # bucket 1; 50.0 and inf in +Inf — cumulative [2, 4, 6]
    assert snap["counts"] == [2, 4, 6]
    assert snap["count"] == 6


def test_transfer_buckets_observe_matches_linear_walk():
    from spark_rapids_tpu.obs.metrics import (TRANSFER_BUCKETS,
                                              MetricsRegistry)
    import random
    rng = random.Random(7)
    r = MetricsRegistry()
    h = r.histogram("t_seconds", buckets=TRANSFER_BUCKETS)
    vals = [rng.uniform(0, 2) for _ in range(500)] \
        + list(TRANSFER_BUCKETS[:-1])
    for v in vals:
        h.observe(v)
    got = r.snapshot()["t_seconds"]["samples"][""]["counts"]
    want = [sum(1 for v in vals if v <= le) for le in TRANSFER_BUCKETS]
    assert got == want


# --- retention (satellite) --------------------------------------------------

def test_trace_dir_retention_prunes_oldest(tmp_path):
    from spark_rapids_tpu.obs.tracer import Tracer
    d = str(tmp_path)
    for i in range(6):
        t = Tracer(trace_id=f"{i:04x}", max_files=4)
        with t.span("q", cat="query"):
            pass
        t.write_chrome(d)
        os.utime(os.path.join(d, f"trace-{i:04x}.json"),
                 (1000 + i, 1000 + i))
    names = sorted(n for n in os.listdir(d) if n.endswith(".json"))
    assert len(names) == 4
    assert "trace-0000.json" not in names  # oldest-first
    assert "trace-0005.json" in names


def test_event_log_retention(tmp_path):
    base = str(tmp_path)
    for i in range(7):
        with open(os.path.join(base, f"app-{i}-1.jsonl"), "w") as f:
            f.write("{}\n")
        os.utime(os.path.join(base, f"app-{i}-1.jsonl"),
                 (2000 + i, 2000 + i))
    assert prune_oldest(base, 3, prefix="app-", suffix=".jsonl") == 4
    left = sorted(os.listdir(base))
    assert left == ["app-4-1.jsonl", "app-5-1.jsonl", "app-6-1.jsonl"]


# --- the acceptance test: crash -> one bundle, tracing DISABLED -------------

def _crash_plan():
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


def test_crash_yields_one_incident_bundle_without_tracing(tmp_path):
    """ISSUE acceptance: spark.rapids.trace.dir UNSET; a mid-stage
    worker crash must leave exactly one incident bundle holding (a) the
    failed task's preceding ring events from the dead worker, (b) a
    memory timeline with a nonzero high-water mark, and (c) attempt
    attribution naming the failed attempt — and triage renders it."""
    from spark_rapids_tpu.cluster import TpuProcessCluster
    from spark_rapids_tpu.exec.base import ExecCtx
    flight_dir = str(tmp_path / "incidents")
    conf = RapidsConf({
        "spark.rapids.tpu.test.injectFaults": "crash:q1s1m0:0",
        "spark.rapids.flight.dir": flight_dir,
    })
    plan = _crash_plan()
    with TpuProcessCluster(n_workers=2, conf=conf) as c:
        got = c.run_query(plan)
        bundle_path = c.last_incident_path
        assert c.last_trace_path is None  # tracing really was off

    # the query still succeeded (scheduler retried the crashed task)
    from spark_rapids_tpu.columnar.arrow_bridge import arrow_schema
    want = pa.Table.from_batches(
        list(plan.execute_cpu(ExecCtx())),
        schema=arrow_schema(plan.output_schema))
    key = lambda t: sorted(t.to_pylist(), key=lambda d: d["k"])
    assert key(got) == key(want)

    # exactly ONE bundle, schema-valid
    assert bundle_path and os.path.dirname(bundle_path) == flight_dir
    assert [n for n in os.listdir(flight_dir)
            if n.endswith(".json")] == [os.path.basename(bundle_path)]
    assert _load_checker().check_flight(bundle_path) == []
    with open(bundle_path) as f:
        bundle = json.load(f)

    # (a) the dead worker's ring contains the crashed attempt's claim
    dead_rings = [
        tag for tag, evs in bundle["rings"].items()
        if any(e.get("kind") == "task" and e.get("ev") == "claim"
               and e.get("task") == "q1s1m0" and e.get("attempt") == 0
               for e in evs)]
    assert dead_rings, bundle["rings"].keys()
    # ... and it is a WORKER ring that survived the respawn (the
    # incarnation-tagged flush at claim time)
    assert all(t.startswith("w") for t in dead_rings)

    # (b) merged memory timeline with a nonzero high-water mark
    mt = bundle["memory_timeline"]
    assert mt["high_water_bytes"] > 0 and mt["events"]

    # (c) attribution names the failed attempt in its stage
    st = bundle["attempts"]["map s1"]
    flagged = {(a["task"], a["attempt"], a["state"])
               for a in st["flagged"]}
    assert ("q1s1m0", 0, "err") in flagged
    # the anomaly list names the same attempt
    assert any(a["kind"] == "task_failed" and a["task"] == "q1s1m0"
               for a in bundle["anomalies"])
    # the crash (worker death) is visible as a respawn anomaly
    assert any(a["kind"] == "worker_respawn"
               for a in bundle["anomalies"])

    # triage renders without error and names the pieces
    rep = triage_report(bundle_path)
    assert "what fired" in rep and "q1s1m0" in rep
    assert "HBM timeline" in rep and "high water" in rep
    assert "straggler / attempt attribution" in rep


def test_straggler_trigger_fires_and_clean_query_leaves_no_bundle(
        tmp_path):
    """A chaos-delayed attempt past stragglerFactor x the stage median
    is recorded and bundled; a clean follow-up query on the same
    cluster leaves no second bundle."""
    from spark_rapids_tpu.cluster import TpuProcessCluster
    flight_dir = str(tmp_path / "incidents")
    conf = RapidsConf({
        # m0 attempt 0 sleeps 6s; its sibling map task sets the median,
        # so m0 trips factor x median while still running (the delay
        # dominates per-task compile noise by construction: firing
        # needs 6 + T > 2T, i.e. sibling time T < 6s)
        "spark.rapids.tpu.test.injectFaults": "delay:q1s1m0:0:6.0",
        "spark.rapids.flight.dir": flight_dir,
        "spark.rapids.flight.stragglerFactor": 2.0,
    })
    plan = _crash_plan()
    with TpuProcessCluster(n_workers=2, conf=conf) as c:
        c.run_query(plan)
        first = c.last_incident_path
        assert first and os.path.exists(first)
        with open(first) as f:
            bundle = json.load(f)
        assert any(a["kind"] == "straggler_detected"
                   and a["task"] == "q1s1m0"
                   for a in bundle["anomalies"]), bundle["anomalies"]
        # the attribution carries the straggler observation too
        st = bundle["attempts"]["map s1"]
        assert any(a["state"] == "straggler" for a in st["attempts"])
        # clean second query on the same cluster: no new bundle (a
        # huge factor rules out timing-noise false stragglers — the
        # point is that NO anomaly means NO bundle)
        c.run_query(_crash_plan(), conf.with_settings(
            {"spark.rapids.tpu.test.injectFaults": "",
             "spark.rapids.flight.stragglerFactor": 1000.0}))
        bundles = [n for n in os.listdir(flight_dir)
                   if n.endswith(".json")]
        assert bundles == [os.path.basename(first)]
