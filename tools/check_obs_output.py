#!/usr/bin/env python
"""Schema checks for the observability outputs CI smoke exercises.

A CPU-ONLY tool: its smokes run queries in this process and then start
``TpuProcessCluster`` workers, and a chip belongs to one process at a
time — so the platform is pinned to the CPU below, before jax is
imported, wherever the script is started (tools/ci_smoke.sh does the
same for every step; the on-chip proof is ``chip_smoke.py``).

Two validators and one driver:

- ``--trace FILE``   validate a Chrome trace_event JSON written under
  ``spark.rapids.trace.dir`` (event shape, unique span ids, resolvable
  parent linkage, process-name metadata, trace_id consistency);
- ``--prom FILE``    validate Prometheus text exposition (sample-line
  grammar, TYPE declarations, histogram bucket monotonicity and
  _count/+Inf agreement);
- ``--smoke DIR``    run one tiny in-process query with tracing +
  metrics enabled, write the trace JSON and a Prometheus dump under
  DIR, then validate both — the one-command CI gate.
- ``--flight FILE``  validate a flight-recorder incident bundle
  (required keys, monotonic timestamps, non-empty memory timeline);
- ``--flight-smoke DIR``  run a 2-worker process-cluster query with an
  injected worker crash and tracing DISABLED, assert exactly one valid
  incident bundle is produced, schema-check it, and render the triage
  report — the always-on-forensics CI gate.
- ``--shuffle-smoke DIR``  run a 2-worker shuffle query whose committed
  map output is corrupted post-commit (chaos ``corrupt``), assert the
  query still returns oracle-correct rows via exactly one classified
  fetch failure + map-stage rerun, validated through the event log and
  the incident bundle — the shuffle-durability CI gate.
- ``--sql-smoke DIR``  parse + compile + plan-verify the FULL NDS SQL
  corpus (zero parse failures, zero unexpected fallbacks), run one SQL
  query end to end on a 2-worker process cluster against the pandas
  oracle, and assert a broken statement leaves a ``sql_parse_error``
  event-log line — the SQL-frontend CI gate.
- ``--profile FILE``  validate a query-profile JSON
  (``spark.rapids.history.dir`` output: required keys, non-empty plan
  record + per-operator aggregate, coherent totals/maxima).
- ``--analyze-smoke DIR``  run ``EXPLAIN ANALYZE`` on NDS q3 FROM SQL
  over a 2-worker process cluster: every scan/join/agg node must show
  nonzero cross-worker rows, the run must persist a valid profile
  json, and ``profiling compare`` across two runs must render — the
  operator-metrics CI gate.
- ``--warehouse-smoke DIR``  run three queries on a 2-worker process
  cluster (a green agg, a chaos ``hang_query`` stall user-cancelled
  while ``/status`` is read mid-flight, a ``spill_corrupt``-bitten
  sort completing through a classified retry), assert EXACTLY three
  sealed warehouse rows with the right outcome classes and a silent
  drift sentinel across a repeat run — the telemetry-warehouse CI
  gate.
- ``--lint-report FILE``  validate a tpu-lint 2.0 JSON report
  (schema 2: rule names, count consistency, required allowlist
  reasons) and gate on ZERO unallowlisted, unbaselined violations —
  the static-analysis ratchet CI gate.
- ``--lockwatch FILE``  validate lock-order watchdog report(s) (the
  file plus any ``<FILE>.w*`` worker siblings): watchdog installed,
  nonzero checked acquisitions, ZERO inversions of the declared lock
  hierarchy — the dynamic half of the lock-order gate.

Exit status 0 = all checks passed; failures are listed on stderr.
"""
import argparse
import json
import os
import re
import sys

# runnable from anywhere: the package lives next to this script's parent
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
os.environ["JAX_PLATFORMS"] = "cpu"  # never takes a chip (see above)

_SAMPLE_RE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)"          # metric name
    r"(\{[^{}]*\})?"                        # optional labels
    r" (-?\d+(\.\d+)?([eE][+-]?\d+)?|[+-]Inf|NaN)$")  # value
_TYPES = ("counter", "gauge", "histogram")


def check_trace(path):
    errors = []
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        return [f"trace unreadable: {e}"]
    if not isinstance(doc, dict) or "traceEvents" not in doc:
        return ["trace is not a trace_event JSON object"]
    trace_id = doc.get("otherData", {}).get("trace_id")
    if not trace_id:
        errors.append("otherData.trace_id missing")
    dropped = int(doc.get("otherData", {}).get("dropped_spans", 0))
    span_ids, parents, cats = set(), [], set()
    n_x = n_m = 0
    for i, ev in enumerate(doc["traceEvents"]):
        ph = ev.get("ph")
        if ph == "M":
            n_m += 1
            if not (ev.get("args") or {}).get("name"):
                errors.append(f"event {i}: M event without args.name")
            continue
        if ph != "X":
            errors.append(f"event {i}: unexpected ph {ph!r}")
            continue
        n_x += 1
        if not isinstance(ev.get("name"), str) or not ev["name"]:
            errors.append(f"event {i}: missing name")
        for k in ("ts", "dur"):
            if not isinstance(ev.get(k), (int, float)) or ev[k] < 0:
                errors.append(f"event {i}: bad {k} {ev.get(k)!r}")
        if not isinstance(ev.get("pid"), int):
            errors.append(f"event {i}: bad pid {ev.get('pid')!r}")
        args = ev.get("args") or {}
        sid = args.get("span_id")
        if not sid:
            errors.append(f"event {i}: args.span_id missing")
        elif sid in span_ids:
            errors.append(f"event {i}: duplicate span_id {sid}")
        else:
            span_ids.add(sid)
        if trace_id and args.get("trace_id") != trace_id:
            errors.append(f"event {i}: trace_id mismatch")
        if args.get("parent_id"):
            parents.append((i, args["parent_id"]))
        cats.add(ev.get("cat"))
    if n_x == 0:
        errors.append("no X (span) events")
    if n_m == 0:
        errors.append("no M (process_name) metadata events")
    if "query" not in cats:
        errors.append("no query-category span")
    if not dropped:  # a bounded tracer may legitimately orphan children
        for i, p in parents:
            if p not in span_ids:
                errors.append(f"event {i}: parent_id {p} unresolved")
    return errors


def check_prometheus(text):
    errors = []
    typed = {}
    seen_names = set()
    for ln, line in enumerate(text.splitlines(), 1):
        if not line.strip():
            continue
        if line.startswith("# TYPE "):
            parts = line.split()
            if len(parts) != 4 or parts[3] not in _TYPES:
                errors.append(f"line {ln}: malformed TYPE: {line!r}")
            else:
                typed[parts[2]] = parts[3]
            continue
        if line.startswith("#"):
            continue
        m = _SAMPLE_RE.match(line)
        if not m:
            errors.append(f"line {ln}: not a valid sample: {line!r}")
            continue
        name = m.group(1)
        seen_names.add(name)
        base = re.sub(r"_(bucket|sum|count)$", "", name)
        if name not in typed and base not in typed:
            errors.append(f"line {ln}: sample {name} has no TYPE")
    # histogram invariants: cumulative buckets non-decreasing, the +Inf
    # bucket equals _count, per label-set
    hists = {n for n, t in typed.items() if t == "histogram"}
    for name in hists:
        series = {}
        counts = {}
        for line in text.splitlines():
            m = _SAMPLE_RE.match(line)
            if not m:
                continue
            labels = m.group(2) or "{}"
            if m.group(1) == name + "_bucket":
                key = re.sub(r'(,?)le="[^"]*"', "", labels)
                series.setdefault(key, []).append(float(m.group(3)))
            elif m.group(1) == name + "_count":
                counts[labels] = float(m.group(3))
        for key, vals in series.items():
            if vals != sorted(vals):
                errors.append(
                    f"{name}{key}: bucket counts not cumulative: {vals}")
        for key, vals in series.items():
            cnt = counts.get(key)
            if cnt is not None and vals and vals[-1] != cnt:
                errors.append(
                    f"{name}{key}: +Inf bucket {vals[-1]} != _count {cnt}")
    if not seen_names:
        errors.append("no samples at all")
    return errors


_FLIGHT_KEYS = ("version", "incident_id", "ts", "query", "anomalies",
                "rings", "memory_timeline", "metrics", "plan_fallbacks",
                "conf_delta", "attempts")


def check_flight(path):
    """Incident-bundle schema: required keys present, every ring's and
    the memory timeline's timestamps monotonic non-decreasing, the
    memory timeline non-empty with a coherent high-water mark, and at
    least one anomaly naming a task or worker."""
    errors = []
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        return [f"bundle unreadable: {e}"]
    if not isinstance(doc, dict):
        return ["bundle is not a JSON object"]
    for k in _FLIGHT_KEYS:
        if k not in doc:
            errors.append(f"missing key {k}")
    if errors:
        return errors
    if not str(doc["incident_id"]).startswith("incident-"):
        errors.append(f"incident_id malformed: {doc['incident_id']!r}")
    if not isinstance(doc["anomalies"], list) or not doc["anomalies"]:
        errors.append("no anomalies — a bundle only exists because "
                      "something fired")
    else:
        for i, a in enumerate(doc["anomalies"]):
            if not a.get("kind"):
                errors.append(f"anomaly {i}: no kind")
            # query-scoped anomalies (the lifecycle layer / the plan
            # verifier) name the query, not a task or worker
            elif a["kind"] in ("query_cancelled", "plan_rejected"):
                if not a.get("detail"):
                    errors.append(f"anomaly {i}: query-scoped "
                                  f"{a['kind']} carries no detail")
            elif not (a.get("task") or a.get("worker", -1) >= 0):
                errors.append(f"anomaly {i}: names neither task nor "
                              "worker")
    if not isinstance(doc["rings"], dict) or "driver" not in doc["rings"]:
        errors.append("rings must include the driver's")
    else:
        for proc, evs in doc["rings"].items():
            ts = [e.get("ts", 0.0) for e in evs]
            if any(b < a for a, b in zip(ts, ts[1:])):
                errors.append(f"ring {proc}: timestamps not monotonic")
    mt = doc["memory_timeline"]
    if not isinstance(mt, dict) or not mt.get("events"):
        errors.append("memory timeline empty")
    else:
        ts = [e.get("ts", 0.0) for e in mt["events"]]
        if any(b < a for a, b in zip(ts, ts[1:])):
            errors.append("memory timeline timestamps not monotonic")
        high = int(mt.get("high_water_bytes", 0) or 0)
        seen = max((int(e.get("device", 0) or 0) for e in mt["events"]),
                   default=0)
        if high != seen:
            errors.append(f"high_water_bytes {high} != max device "
                          f"occupancy in events {seen}")
    if not isinstance(doc["attempts"], dict):
        errors.append("attempts attribution is not a dict")
    return errors


def run_flight_smoke(out_dir):
    """Injected worker crash with tracing DISABLED: the always-on
    flight recorder must leave exactly one incident bundle, and the
    triage renderer must accept it. Returns the bundle path."""
    import pyarrow as pa

    from spark_rapids_tpu.cluster import TpuProcessCluster
    from spark_rapids_tpu.config import RapidsConf
    from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec
    from spark_rapids_tpu.exec.base import HostBatchSourceExec
    from spark_rapids_tpu.exec.exchange import TpuShuffleExchangeExec
    from spark_rapids_tpu.expr import Alias, UnresolvedColumn as col
    from spark_rapids_tpu.expr.aggregates import Sum
    from spark_rapids_tpu.shuffle.partitioner import HashPartitioning
    from spark_rapids_tpu.tools.profiling import triage_report
    flight_dir = os.path.join(out_dir, "incidents")
    rbs = [pa.record_batch({"k": [i % 5 for i in range(n)],
                            "v": list(range(n))})
           for n in (300, 250)]
    src = HostBatchSourceExec(rbs)
    plan = TpuHashAggregateExec(
        [col("k")], [Alias(Sum(col("v")), "s")],
        TpuShuffleExchangeExec(HashPartitioning([col("k")], 4), src))
    conf = RapidsConf({
        "spark.rapids.tpu.test.injectFaults": "crash:q1s1m0:0",
        "spark.rapids.flight.dir": flight_dir,
        # tracing deliberately NOT set: forensics must not depend on it
    })
    with TpuProcessCluster(n_workers=2, conf=conf) as c:
        out = c.run_query(plan)
        assert out.num_rows == 5, f"query wrong across crash: {out}"
        bundle = c.last_incident_path
    assert bundle, "no incident bundle written"
    bundles = [n for n in os.listdir(flight_dir)
               if n.startswith("incident-") and n.endswith(".json")]
    assert bundles == [os.path.basename(bundle)], \
        f"expected exactly one bundle, got {bundles}"
    report = triage_report(bundle)
    assert "what fired" in report and "HBM timeline" in report, report
    return bundle


def run_lifecycle_smoke(out_dir):
    """ci_smoke step: a deadline-exceeded query under chaos
    ``hang_query`` must yield exactly ONE classified query_cancelled
    event-log line, ONE incident bundle carrying the anomaly — and a
    post-cancel query on the SAME cluster must run green (no poisoned
    state: no leaked admission slots, no stale cancel observed).
    Returns the bundle path (validated by check_flight)."""
    import pyarrow as pa

    from spark_rapids_tpu.cluster import TpuProcessCluster
    from spark_rapids_tpu.config import RapidsConf
    from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec
    from spark_rapids_tpu.exec.base import HostBatchSourceExec
    from spark_rapids_tpu.exec.exchange import TpuShuffleExchangeExec
    from spark_rapids_tpu.expr import Alias, UnresolvedColumn as col
    from spark_rapids_tpu.expr.aggregates import Sum
    from spark_rapids_tpu.lifecycle import QueryCancelled
    from spark_rapids_tpu.memory import DeviceMemoryManager
    from spark_rapids_tpu.shuffle.partitioner import HashPartitioning
    from spark_rapids_tpu.tools.event_log import read_event_logs
    flight_dir = os.path.join(out_dir, "incidents")
    log_dir = os.path.join(out_dir, "events")
    rbs = [pa.record_batch({"k": [i % 5 for i in range(n)],
                            "v": list(range(n))})
           for n in (300, 250)]
    src = HostBatchSourceExec(rbs)
    plan = TpuHashAggregateExec(
        [col("k")], [Alias(Sum(col("v")), "s")],
        TpuShuffleExchangeExec(HashPartitioning([col("k")], 4), src))
    conf = RapidsConf({
        "spark.rapids.query.deadline": "2.0",
        "spark.rapids.tpu.test.injectFaults": "hang_query:q1r*:*:60",
        "spark.rapids.flight.dir": flight_dir,
        "spark.rapids.eventLog.dir": log_dir,
    })
    with TpuProcessCluster(n_workers=2, conf=conf) as c:
        try:
            c.run_query(plan)
            raise AssertionError("hang_query deadline did not cancel")
        except QueryCancelled as e:
            assert e.reason == "deadline", e
        bundle = c.last_incident_path
        assert bundle, "no incident bundle from the cancelled query"
        with open(bundle) as f:
            doc = json.load(f)
        kinds = [a["kind"] for a in doc["anomalies"]]
        assert "query_cancelled" in kinds, kinds
        # no poisoned state: the same cluster runs the query green
        out = c.run_query(plan, conf=RapidsConf({}))
        assert out.num_rows == 5, f"post-cancel query wrong: {out}"
        snap = DeviceMemoryManager.shared(conf).admission.snapshot()
        assert snap["in_use"] == 0 and not snap["queued"], snap
    bundles = [n for n in os.listdir(flight_dir)
               if n.startswith("incident-") and n.endswith(".json")]
    assert bundles == [os.path.basename(bundle)], \
        f"expected exactly one bundle, got {bundles}"
    cancels = [e for e in read_event_logs(log_dir)
               if e.get("type") == "query_cancelled"]
    assert len(cancels) == 1, cancels
    assert cancels[0]["reason"] == "deadline", cancels
    print(f"lifecycle smoke OK: one classified cancel "
          f"({cancels[0]['reason']}), one bundle, post-cancel query "
          f"green")
    return bundle


def run_shuffle_smoke(out_dir):
    """Injected post-commit corruption of a map output: the query must
    return oracle-correct rows through exactly one classified fetch
    failure and one lineage stage rerun, with the recovery visible in
    the persisted event log AND the incident bundle. Returns the bundle
    path (validated by check_flight like any other bundle)."""
    import pyarrow as pa

    from spark_rapids_tpu.cluster import TpuProcessCluster
    from spark_rapids_tpu.config import RapidsConf
    from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec
    from spark_rapids_tpu.exec.base import HostBatchSourceExec
    from spark_rapids_tpu.exec.exchange import TpuShuffleExchangeExec
    from spark_rapids_tpu.expr import Alias, UnresolvedColumn as col
    from spark_rapids_tpu.expr.aggregates import Sum
    from spark_rapids_tpu.shuffle.partitioner import HashPartitioning
    from spark_rapids_tpu.tools.event_log import read_event_logs
    flight_dir = os.path.join(out_dir, "incidents")
    log_dir = os.path.join(out_dir, "events")
    n = 600
    rbs = [pa.record_batch({"k": [i % 7 for i in range(n)],
                            "v": list(range(n))}),
           pa.record_batch({"k": [i % 7 for i in range(n, 2 * n)],
                            "v": list(range(n, 2 * n))})]
    src = HostBatchSourceExec(rbs)
    plan = TpuHashAggregateExec(
        [col("k")], [Alias(Sum(col("v")), "s")],
        TpuShuffleExchangeExec(HashPartitioning([col("k")], 4), src))
    conf = RapidsConf({
        "spark.rapids.tpu.test.injectFaults": "corrupt:q1s1m0:0",
        "spark.rapids.flight.dir": flight_dir,
        "spark.rapids.eventLog.dir": log_dir,
    })
    with TpuProcessCluster(n_workers=2, conf=conf) as c:
        out = c.run_query(plan)
        sched = c.last_scheduler
        bundle = c.last_incident_path
    # oracle: sum(v) per k over both batches
    want = {}
    for rb in rbs:
        for k, v in zip(rb.column(0).to_pylist(),
                        rb.column(1).to_pylist()):
            want[k] = want.get(k, 0) + v
    got = {r["k"]: r["s"] for r in out.to_pylist()}
    assert got == want, f"rows wrong across corruption: {got} != {want}"
    ffs = [e for e in sched.events if e["event"] == "fetch_failed"]
    reruns = [e for e in sched.events if e["event"] == "stage_rerun"]
    assert len(ffs) == 1 and "[corrupt]" in ffs[0]["reason"], ffs
    assert len(reruns) == 1, f"expected exactly one stage rerun: {reruns}"
    # the persisted event log carries the recovery timeline
    sched_evs = [e for e in read_event_logs(log_dir)
                 if e.get("type") == "scheduler"]
    assert sched_evs and sched_evs[-1]["summary"]["stage_reruns"] == 1, \
        "stage rerun missing from the event log"
    assert any(a["event"] == "fetch_failed"
               for e in sched_evs for a in e["attempts"]), \
        "fetch_failed missing from the event log"
    # ... and the incident bundle names both
    assert bundle and os.path.exists(bundle), "no incident bundle"
    with open(bundle) as f:
        kinds = {a["kind"] for a in json.load(f)["anomalies"]}
    assert {"fetch_failed", "stage_rerun"} <= kinds, kinds
    return bundle


def run_spill_smoke(out_dir):
    """ci_smoke step: a reduce-side out-of-core sort whose disk-spill
    writes ALL hit injected ENOSPC (chaos ``disk_full``). The full-disk
    response must be classified end to end: the query completes green
    (refused writes leave batches host-resident — no raw OSError
    escapes into the eviction cascade), the persisted event log carries
    ``disk_pressure`` lines with kind=enospc, exactly ONE incident
    bundle names the ``disk_pressure`` anomaly, a PLANTED
    dead-incarnation spill namespace is reclaimed by the boot-time
    orphan sweep, and no live namespace leaks a spill file. Returns
    the bundle path (validated by check_flight)."""
    import subprocess

    import numpy as np
    import pyarrow as pa

    from spark_rapids_tpu.cluster import TpuProcessCluster
    from spark_rapids_tpu.config import RapidsConf
    from spark_rapids_tpu.exec.base import HostBatchSourceExec
    from spark_rapids_tpu.exec.exchange import TpuShuffleExchangeExec
    from spark_rapids_tpu.exec.sort import SortOrder, TpuSortExec
    from spark_rapids_tpu.expr import UnresolvedColumn as col
    from spark_rapids_tpu.memory import _hostname
    from spark_rapids_tpu.shuffle.partitioner import HashPartitioning
    from spark_rapids_tpu.tools.event_log import read_event_logs
    flight_dir = os.path.join(out_dir, "incidents")
    log_dir = os.path.join(out_dir, "events")
    spill_dir = os.path.join(out_dir, "spill")
    # plant a dead incarnation: a namespace owned by a reaped pid,
    # holding a stale spill file a crashed process would have leaked
    p = subprocess.Popen(["true"])
    p.wait()
    orphan = os.path.join(spill_dir, f"{_hostname()}-{p.pid}-{'0' * 8}")
    os.makedirs(orphan)
    open(os.path.join(orphan, "spill-stale.arrow"), "w").close()
    rng = np.random.default_rng(7)
    rbs = [pa.record_batch({
        "k": pa.array(rng.integers(0, 1 << 30, 1200).astype(np.int64)),
        "v": pa.array(rng.integers(0, 1000, 1200).astype(np.int64)),
    }) for _ in range(4)]
    plan = TpuSortExec(
        [SortOrder(col("k"))],
        TpuShuffleExchangeExec(HashPartitioning([col("v")], 1),
                               HostBatchSourceExec(rbs)))
    conf = RapidsConf({
        # every disk-spill write the reduce task attempts is refused
        "spark.rapids.tpu.test.injectFaults": "disk_full:q1r*:*:99",
        # tiny budgets: the reduce-side sort goes out-of-core and its
        # host tier WANTS to cascade to disk on every run
        "spark.rapids.memory.device.budgetBytes": 1 << 14,
        "spark.rapids.memory.host.spillStorageSize": 1 << 12,
        "spark.rapids.memory.spillDir": spill_dir,
        "spark.rapids.flight.dir": flight_dir,
        "spark.rapids.eventLog.dir": log_dir,
    })
    with TpuProcessCluster(n_workers=2, conf=conf) as c:
        assert not os.path.exists(orphan), \
            "boot-time orphan sweep did not reclaim the dead namespace"
        out = c.run_query(plan)
        sched = c.last_scheduler
        bundle = c.last_incident_path
    assert out.num_rows == 4 * 1200, \
        f"query wrong under full disk: {out.num_rows} rows"
    ks = out.column("k").to_pylist()
    assert ks == sorted(ks), "sort order lost under full disk"
    # no raw OSError reached the scheduler: zero failed attempts
    failed = [e for e in sched.events if e["event"] == "task_failed"]
    assert not failed, f"full disk broke a task: {failed}"
    # classified evidence: event log
    pressure = [e for e in read_event_logs(log_dir)
                if e.get("type") == "disk_pressure"]
    assert pressure and pressure[0]["kind"] == "enospc", pressure
    # ... and exactly one bundle naming the anomaly
    assert bundle, "no incident bundle from the pressured query"
    bundles = [n for n in os.listdir(flight_dir)
               if n.startswith("incident-") and n.endswith(".json")]
    assert bundles == [os.path.basename(bundle)], \
        f"expected exactly one bundle, got {bundles}"
    with open(bundle) as f:
        kinds = {a["kind"] for a in json.load(f)["anomalies"]}
    assert "disk_pressure" in kinds, kinds
    # no live namespace leaks a spill file (refused writes cleaned
    # their partials; committed files were read back or released)
    leftovers = []
    for ns in os.listdir(spill_dir):
        nsp = os.path.join(spill_dir, ns)
        if os.path.isdir(nsp):
            leftovers += [f for f in os.listdir(nsp)
                          if f.endswith(".arrow")]
    assert leftovers == [], f"leaked spill files: {leftovers}"
    print(f"spill smoke OK: query green under injected ENOSPC, "
          f"{len(pressure)} classified disk_pressure event(s), one "
          f"bundle, orphan namespace reclaimed")
    return bundle


def run_warehouse_smoke(out_dir):
    """ci_smoke step: the query-telemetry warehouse under fire. One
    2-worker cluster runs three queries — a green shuffle+agg, a chaos
    ``hang_query`` stall the driver cancels (``cancel_running``) while
    a second thread reads ``/status`` mid-flight, and a
    ``spill_corrupt``-bitten out-of-core sort that completes through a
    classified retry. EXACTLY three sealed warehouse rows must land
    with the right outcome classes (completed / cancelled:user /
    completed), every segment must verify its seal (no salvage), and a
    repeat of the green query must leave the drift sentinel silent
    (rc 0). Returns None — the warehouse rows are the artifact."""
    import socket
    import threading
    import time
    import urllib.request

    import numpy as np
    import pyarrow as pa

    from spark_rapids_tpu.cluster import TpuProcessCluster
    from spark_rapids_tpu.config import RapidsConf
    from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec
    from spark_rapids_tpu.exec.base import HostBatchSourceExec
    from spark_rapids_tpu.exec.exchange import TpuShuffleExchangeExec
    from spark_rapids_tpu.exec.sort import SortOrder, TpuSortExec
    from spark_rapids_tpu.expr import Alias, UnresolvedColumn as col
    from spark_rapids_tpu.expr.aggregates import Sum
    from spark_rapids_tpu.lifecycle import QueryCancelled
    from spark_rapids_tpu.obs.metrics import maybe_start_http_server
    from spark_rapids_tpu.obs.warehouse import drift_report, read_rows
    from spark_rapids_tpu.shuffle.integrity import read_sealed_file
    from spark_rapids_tpu.shuffle.partitioner import HashPartitioning
    wh_dir = os.path.join(out_dir, "warehouse")
    spill_dir = os.path.join(out_dir, "spill")
    with socket.socket() as s:  # a free port for the /status endpoint
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    base = {
        "spark.rapids.warehouse.dir": wh_dir,
        "spark.rapids.metrics.enabled": "true",  # workers flush deltas
        "spark.rapids.metrics.port": str(port),
        # q2's final stage stalls (user-cancelled below); q3's
        # committed spill files rot post-commit — the verified
        # read-back classifies the loss and the retry runs green
        "spark.rapids.tpu.test.injectFaults":
            "hang_query:q2r*:*:60;spill_corrupt:q3r*:0",
    }
    rbs = [pa.record_batch({"k": [i % 5 for i in range(n)],
                            "v": list(range(n))})
           for n in (300, 250)]
    green = TpuHashAggregateExec(
        [col("k")], [Alias(Sum(col("v")), "s")],
        TpuShuffleExchangeExec(HashPartitioning([col("k")], 4),
                               HostBatchSourceExec(rbs)))
    # a DIFFERENT plan shape for the doomed query: drift compares runs
    # of the same fingerprint, and a cancelled run (near-empty
    # counters) must not become the green plan's baseline
    hung = TpuHashAggregateExec(
        [col("v")], [Alias(Sum(col("k")), "s")],
        TpuShuffleExchangeExec(HashPartitioning([col("v")], 2),
                               HostBatchSourceExec(rbs)))
    rng = np.random.default_rng(11)
    sort_rbs = [pa.record_batch({
        "k": pa.array(rng.integers(0, 1 << 30, 1200).astype(np.int64)),
        "v": pa.array(rng.integers(0, 1000, 1200).astype(np.int64)),
    }) for _ in range(4)]
    spilly = TpuSortExec(
        [SortOrder(col("k"))],
        TpuShuffleExchangeExec(HashPartitioning([col("v")], 1),
                               HostBatchSourceExec(sort_rbs)))
    with TpuProcessCluster(n_workers=2, conf=RapidsConf(base)) as c:
        srv_port = maybe_start_http_server(c.conf) or port
        url = f"http://127.0.0.1:{srv_port}/status"
        # q1: green
        out = c.run_query(green)
        assert out.num_rows == 5, f"green query wrong: {out.num_rows}"
        # q2: hang_query holds the reduce stage; a watcher thread reads
        # /status mid-flight, then fires the user cancel
        seen = {}

        def _watch_then_cancel():
            deadline = time.time() + 45
            while time.time() < deadline:
                try:
                    with urllib.request.urlopen(url, timeout=5) as r:
                        assert r.headers.get_content_type() == \
                            "application/json", r.headers
                        doc = json.load(r)
                except (OSError, ValueError):
                    time.sleep(0.1)
                    continue
                if any(q.get("query_id") == "q2"
                       for q in doc.get("in_flight") or []):
                    seen.update(doc)
                    break
                time.sleep(0.1)
            while not c.cancel_running() and time.time() < deadline:
                time.sleep(0.1)

        w = threading.Thread(target=_watch_then_cancel, daemon=True)
        w.start()
        try:
            c.run_query(hung)
            raise AssertionError("hang_query query was not cancelled")
        except QueryCancelled as e:
            assert e.reason == "user", e
        w.join(timeout=60)
        live = seen.get("in_flight") or []
        assert any(q.get("query_id") == "q2" for q in live), \
            f"/status never showed q2 in flight: {seen or 'no doc'}"
        assert "phase" in live[0] and "memory" in seen, seen
        assert seen.get("warehouse_tail"), \
            "mid-hang /status missing the q1 warehouse row"
        # q3: tiny budgets push the reduce sort out-of-core; chaos rots
        # its committed spill files — classified retry, green finish
        out = c.run_query(spilly, conf=RapidsConf({
            **base,
            "spark.rapids.memory.device.budgetBytes": 1 << 14,
            "spark.rapids.memory.host.spillStorageSize": 1 << 12,
            "spark.rapids.memory.spillDir": spill_dir,
        }))
        assert out.num_rows == 4 * 1200, out.num_rows
        bit = [e for e in c.last_scheduler.events
               if e["event"] == "spill_read_failed"]
        assert bit, "spill_corrupt never bit the reduce task"
        # exactly three sealed rows, right outcome classes
        segs = sorted(os.listdir(wh_dir))
        assert segs and all(n.startswith("wh-") and n.endswith(".jsonl")
                            for n in segs), segs
        for n in segs:  # seals verify — salvage is for torn files only
            read_sealed_file(
                os.path.join(wh_dir, n),
                lambda kind, detail, _n=n: AssertionError(
                    f"segment {_n} unsealed: {kind} {detail}"))
        rows = read_rows(wh_dir)
        got = {r.get("query_id"): r for r in rows}
        assert len(rows) == 3 and set(got) == {"q1", "q2", "q3"}, \
            f"want one row per query: {[r.get('query_id') for r in rows]}"
        assert got["q1"]["outcome"] == "completed", got["q1"]
        assert got["q2"]["outcome"] == "cancelled" and \
            (got["q2"].get("cancel") or {}).get("reason") == "user", \
            got["q2"]
        assert got["q3"]["outcome"] == "completed", got["q3"]
        assert sum(int(v or 0) for v in
                   (got["q3"].get("spill") or {}).values()) > 0, \
            f"q3 spilled nothing: {got['q3'].get('spill')}"
        # q4: repeat the green query — same fingerprint, same
        # device_kind; the drift sentinel must stay silent
        out = c.run_query(green)
        assert out.num_rows == 5, f"repeat query wrong: {out.num_rows}"
    rep, rc = drift_report(wh_dir)
    assert rc == 0, f"drift not clean across repeat run (rc {rc}):\n{rep}"
    rows = read_rows(wh_dir)
    assert len(rows) == 4 and \
        rows[-1].get("fingerprint") == got["q1"].get("fingerprint"), \
        "repeat run did not land under the green plan's fingerprint"
    print(f"warehouse smoke OK: 3 sealed rows (completed / "
          f"cancelled:user / completed), /status live mid-hang, drift "
          f"clean on repeat ({len(segs)} segment(s))")


_PROFILE_KEYS = ("version", "profile_id", "ts", "query", "source",
                 "cluster", "wall_s", "fingerprint", "nodes", "ops")


def check_profile(path):
    """Query-profile schema: required keys, a non-empty plan node list,
    a non-empty per-operator aggregate with coherent totals (rows and
    opTime non-negative, per-task max <= total, tasks >= 1)."""
    errors = []
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        return [f"profile unreadable: {e}"]
    if not isinstance(doc, dict):
        return ["profile is not a JSON object"]
    for k in _PROFILE_KEYS:
        if k not in doc:
            errors.append(f"missing key {k}")
    if errors:
        return errors
    if not str(doc["profile_id"]).startswith("profile-"):
        errors.append(f"profile_id malformed: {doc['profile_id']!r}")
    if doc["source"] not in ("sql", "plan"):
        errors.append(f"bad source {doc['source']!r}")
    if doc["cluster"] not in ("local", "process"):
        errors.append(f"bad cluster {doc['cluster']!r}")
    if not isinstance(doc["nodes"], list) or not doc["nodes"]:
        errors.append("nodes (plan record) empty")
    ops = doc["ops"]
    if not isinstance(ops, dict) or not ops:
        errors.append("ops (per-operator aggregate) empty")
        return errors
    for key, st in ops.items():
        m = st.get("metrics", {})
        if st.get("tasks", 0) < 1:
            errors.append(f"{key}: tasks < 1")
        for name in ("rows", "opTime"):
            if m.get(name, 0) < 0:
                errors.append(f"{key}: negative {name}")
            mx = st.get("max", {}).get(name)
            if mx is not None and mx > m.get(name, 0) + 1e-9:
                errors.append(f"{key}: max {name} {mx} exceeds "
                              f"total {m.get(name, 0)}")
    return errors


def run_analyze_smoke(out_dir):
    """EXPLAIN ANALYZE CI gate: run NDS q3 FROM SQL over a 2-worker
    process cluster via ``session.sql('EXPLAIN ANALYZE ...')``; the
    returned text must annotate every source/join/aggregate node with
    nonzero rows, the run must persist a valid query-profile JSON
    under spark.rapids.history.dir, and a second run must compare
    cleanly through `profiling compare`. Returns the profile path."""
    import re as _re

    from spark_rapids_tpu.cluster import TpuProcessCluster
    from spark_rapids_tpu.config import RapidsConf
    from spark_rapids_tpu.session import TpuSession
    from spark_rapids_tpu.tools.nds import (SQL_QUERIES, build_query_sql,
                                            gen_tables)
    from spark_rapids_tpu.tools.profiling import compare_report
    history_dir = os.path.join(out_dir, "history")
    tables = gen_tables(n_sales=1 << 12)
    s = TpuSession(conf={"spark.sql.shuffle.partitions": "1"})
    build_query_sql("q3", s, tables)  # registers the corpus views
    conf = RapidsConf({"spark.rapids.history.dir": history_dir})
    with TpuProcessCluster(n_workers=2, conf=conf) as c:
        s.set_cluster(c)
        text = s.sql("EXPLAIN ANALYZE " + SQL_QUERIES["q3"])
        first_profile = c.last_profile_path
        s.sql("EXPLAIN ANALYZE " + SQL_QUERIES["q3"])  # second run
        second_profile = c.last_profile_path
    print(text)
    # every operator id appears exactly once
    ids = _re.findall(r"\(op(\d+)\)", text)
    assert ids and len(ids) == len(set(ids)), \
        f"operator ids not unique in EXPLAIN ANALYZE text: {ids}"
    # nonzero rows at every scan/join/agg node
    checked = 0
    for line in text.splitlines():
        if not any(op in line for op in
                   ("HostBatchSourceExec", "FileScanExec",
                    "ShuffledHashJoinExec", "HashAggregateExec")):
            continue
        m = _re.search(r"rows=(\d+)", line)
        assert m and int(m.group(1)) > 0, \
            f"scan/join/agg node without nonzero rows: {line!r}"
        checked += 1
    assert checked >= 4, f"too few scan/join/agg nodes checked: {text}"
    assert first_profile and os.path.exists(first_profile), \
        "no query profile written"
    assert second_profile and second_profile != first_profile, \
        "second run did not write its own profile"
    cmp_text = compare_report(first_profile, second_profile)
    assert "per-operator opTime" in cmp_text, cmp_text
    print(f"analyze smoke: {checked} scan/join/agg nodes with nonzero "
          f"rows; compare across 2 runs OK")
    return first_profile


def run_mesh_smoke(out_dir):
    """Multi-host mesh CI gate (ISSUE 16): bootstrap a 2-process mesh
    (jax.distributed across real worker processes), run one join+agg
    query whose shuffle exchanges ride the cross-process collective,
    and certify it dryrun_multichip-style — STRUCTURAL counters only
    (process count, collective epochs, bytes exchanged, device_kind),
    never wall-clock. The stitched driver trace must carry spans from
    both member processes. Returns the trace path."""
    import numpy as np
    import pyarrow as pa

    from spark_rapids_tpu.cluster import TpuProcessCluster
    from spark_rapids_tpu.config import RapidsConf
    from spark_rapids_tpu.distributed.runtime import read_mesh_markers
    from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec
    from spark_rapids_tpu.exec.base import (HostBatchSourceExec,
                                            collect_arrow_cpu)
    from spark_rapids_tpu.exec.exchange import TpuShuffleExchangeExec
    from spark_rapids_tpu.exec.joins import TpuShuffledHashJoinExec
    from spark_rapids_tpu.expr import Alias, UnresolvedColumn as col
    from spark_rapids_tpu.expr.aggregates import Count, Sum
    from spark_rapids_tpu.obs.metrics import read_worker_metrics
    from spark_rapids_tpu.shuffle.partitioner import HashPartitioning

    rng = np.random.default_rng(16)
    n_f, n_d = 1500, 40
    fact = pa.record_batch({
        "fk": pa.array(rng.integers(0, n_d, n_f).astype(np.int32)),
        "amt": pa.array(rng.integers(1, 100, n_f).astype(np.int64))})
    dim = pa.record_batch({
        "dk": pa.array(np.arange(n_d, dtype=np.int32)),
        "grp": pa.array((np.arange(n_d) % 6).astype(np.int32))})
    fact_src = HostBatchSourceExec([fact.slice(i * 375, 375)
                                    for i in range(4)])
    dim_src = HostBatchSourceExec([dim.slice(0, 20), dim.slice(20)])
    nparts = 4
    lex = TpuShuffleExchangeExec(HashPartitioning([col("fk")], nparts),
                                 fact_src)
    rex = TpuShuffleExchangeExec(HashPartitioning([col("dk")], nparts),
                                 dim_src)
    join = TpuShuffledHashJoinExec([col("fk")], [col("dk")], "inner",
                                   lex, rex)
    gex = TpuShuffleExchangeExec(HashPartitioning([col("grp")], nparts),
                                 join)
    plan = TpuHashAggregateExec(
        [col("grp")], [Alias(Sum(col("amt")), "total"),
                       Alias(Count(col("amt")), "n")], gex)

    conf = RapidsConf({
        "spark.rapids.tpu.mesh.enabled": "true",
        "spark.rapids.metrics.enabled": "true",
        "spark.rapids.trace.dir": os.path.join(out_dir, "traces")})
    with TpuProcessCluster(n_workers=2, conf=conf) as c:
        got = c.run_query(plan)
        evs = c.last_scheduler.events
        falls = [e for e in evs if e["event"] == "mesh_fallback"]
        assert not falls, f"mesh smoke fell back: {falls}"
        oks = [e for e in evs if e["event"] == "task_ok"]
        assert len(oks) == 2 and all("g0w" in e["task"] for e in oks), \
            f"expected one gang task per process: {oks}"
        # bootstrap markers: both processes joined ONE distributed mesh
        markers = read_mesh_markers(c.root, 2, 0)
        assert markers and all(
            d["ok"] and d["distributed"] for d in markers), markers
        kind = markers[0]["device_kind"]
        assert kind, "device_kind missing from mesh marker"
        assert all(int(d["num_processes"]) == 2 for d in markers)
        # structural collective counters, per process
        epochs, nbytes = {}, {}
        for tag, ms in read_worker_metrics(c.root):
            w = tag.split(".")[0]
            for fam_name, acc in (
                    ("rapids_mesh_collective_epochs_total", epochs),
                    ("rapids_mesh_collective_bytes_total", nbytes)):
                fam = ms.get(fam_name)
                if fam:
                    for _, v in fam["samples"].items():
                        acc[w] = max(acc.get(w, 0), int(v))
        assert len(epochs) == 2 and all(v >= 1 for v in epochs.values()), \
            f"both processes must run collective epochs: {epochs}"
        assert sum(nbytes.values()) > 0, \
            f"no bytes crossed the process boundary: {nbytes}"
        trace_path = c.last_trace_path
    # correctness: the gang result matches the in-process oracle
    from spark_rapids_tpu.columnar.arrow_bridge import arrow_schema
    want = collect_arrow_cpu(plan).cast(arrow_schema(plan.output_schema))
    key = lambda t: sorted(map(tuple, (r.values() for r in t.to_pylist())))  # noqa: E731
    assert key(got) == key(want), "gang result != oracle"
    # the stitched trace carries both member processes' spans
    assert trace_path and os.path.exists(trace_path), "no trace written"
    with open(trace_path) as f:
        doc = json.load(f)
    pids = {ev.get("pid") for ev in doc.get("traceEvents", [])
            if ev.get("ph") == "X"}
    assert {1, 2} <= pids, \
        f"trace not stitched across both worker processes: pids={pids}"
    print(f"mesh smoke: 2-process gang mesh ({kind}), "
          f"epochs={sum(epochs.values())}, "
          f"bytes={sum(nbytes.values())}, trace stitched from "
          f"pids={sorted(pids)}")
    return trace_path


def run_smoke(out_dir):
    """One tiny query with tracing + metrics on; returns (trace_path,
    prom_path)."""
    trace_dir = os.path.join(out_dir, "traces")
    from spark_rapids_tpu import TpuSession
    from spark_rapids_tpu.expr import UnresolvedColumn as col
    from spark_rapids_tpu.expr.aggregates import Sum
    from spark_rapids_tpu.obs.metrics import dump_prometheus
    s = TpuSession({
        "spark.rapids.trace.dir": trace_dir,
        "spark.rapids.eventLog.dir": os.path.join(out_dir, "events"),
    })
    df = s.create_dataframe({"k": [i % 3 for i in range(100)],
                             "v": list(range(100))})
    out = df.group_by(col("k")).agg(Sum(col("v"))).collect()
    assert out.num_rows == 3, f"smoke query wrong: {out}"
    traces = [os.path.join(trace_dir, n)
              for n in sorted(os.listdir(trace_dir))
              if n.endswith(".json")]
    assert traces, f"no trace JSON written under {trace_dir}"
    prom_path = os.path.join(out_dir, "metrics.prom")
    with open(prom_path, "w") as f:
        f.write(dump_prometheus())
    return traces[-1], prom_path


_SCAN_METRICS = ("assembleTime", "uploadTime", "uploadWaitTime",
                 "scanTime")
_SCAN_FAMILIES = ("rapids_scan_assemble_seconds",
                  "rapids_scan_upload_seconds")


def run_scan_smoke(out_dir, mixed=False):
    """Device-decode parquet scan smoke (CPU backend): run a small
    multi-row-group scan through the overlapped upload tunnel, check
    the rows against the host-decode oracle, assert the
    assemble/upload metric split exists, and dump the process metrics
    registry for Prometheus validation. With ``mixed`` the file
    exercises the WIDENED decode envelope — PLAIN BYTE_ARRAY strings,
    DATA_PAGE_V2 pages, DELTA_BINARY_PACKED ints and
    DELTA_LENGTH_BYTE_ARRAY strings in one scan — and the smoke
    asserts ZERO host-fallback chunks (the envelope-regression CI
    gate). Returns the prom path."""
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq

    from spark_rapids_tpu.columnar.arrow_bridge import device_to_arrow
    from spark_rapids_tpu.exec.base import ExecCtx
    from spark_rapids_tpu.io import TpuFileScanExec
    from spark_rapids_tpu.obs.metrics import dump_prometheus
    rng = np.random.default_rng(0)
    n = 6000
    if mixed:
        t = pa.table({
            # PLAIN strings (dictionary disabled): nulls + empties
            "ps": pa.array([None if i % 13 == 0 else
                            ["", f"plain-{i % 97}", "uni-β"][i % 3]
                            for i in range(n)]),
            # DELTA_BINARY_PACKED int64 with nulls, negative deltas
            "d64": pa.array(rng.integers(-500, 500, n).cumsum()
                            .astype(np.int64),
                            mask=rng.uniform(0, 1, n) < 0.2),
            # DELTA_LENGTH_BYTE_ARRAY strings
            "dls": pa.array([f"dl{i % 41}" + "x" * (i % 7)
                             for i in range(n)]),
            # plain int32 rides along
            "i": pa.array(rng.integers(0, 1 << 20, n).astype(np.int32)),
        })
        path = os.path.join(out_dir, "scan_envelope_smoke.parquet")
        # data_page_version 2.0 makes every data page a V2 page, so
        # the file covers all three new encoding classes at once
        pq.write_table(t, path, row_group_size=2048,
                       compression="snappy", use_dictionary=False,
                       data_page_version="2.0",
                       column_encoding={
                           "ps": "PLAIN",
                           "d64": "DELTA_BINARY_PACKED",
                           "dls": "DELTA_LENGTH_BYTE_ARRAY",
                           "i": "PLAIN"})
    else:
        t = pa.table({
            "i": pa.array(rng.integers(0, 9, n).astype(np.int32)),
            "f": pa.array(rng.uniform(0, 1, n)),
            "ni": pa.array(rng.integers(0, 40, n).astype(np.int64),
                           mask=rng.uniform(0, 1, n) < 0.2),
            "s": pa.array([f"v{i % 11}" for i in range(n)]),
        })
        path = os.path.join(out_dir, "scan_smoke.parquet")
        pq.write_table(t, path, row_group_size=1024,
                       compression="snappy")
    scan = TpuFileScanExec([path])
    ctx = ExecCtx()
    got = pa.Table.from_batches(
        [device_to_arrow(b) for b in scan.execute(ctx)])
    want = pa.Table.from_batches(
        list(TpuFileScanExec([path]).execute_cpu(ExecCtx())))
    assert got.to_pydict() == want.to_pydict(), \
        "device-decode scan disagrees with host decode"
    m = ctx.metrics[scan.node_label()]
    missing = [name for name in _SCAN_METRICS if name not in m]
    assert not missing, f"scan metrics missing: {missing}"
    assert m["uploadTime"].value >= 0 and m["assembleTime"].value >= 0
    assert "deviceChunks" in m and "fallbackChunks" in m, \
        "decode-coverage metrics missing"
    if mixed:
        assert m["fallbackChunks"].value == 0, \
            (f"widened-envelope smoke hit "
             f"{m['fallbackChunks'].value} host-fallback chunks")
        assert m["deviceChunks"].value > 0
    prom = dump_prometheus()
    missing = [f for f in _SCAN_FAMILIES if f + "_count" not in prom]
    assert not missing, f"obs families missing samples: {missing}"
    prom_path = os.path.join(out_dir, "scan_metrics.prom")
    with open(prom_path, "w") as f:
        f.write(prom)
    return prom_path


def run_fusion_smoke(out_dir):
    """Whole-stage-fusion CI gate (q6 from files): a multi-row-group
    parquet scan under a filter -> project -> partial-agg chain must
    run decode+filter+project+partial-agg as ONE spliced XLA program
    per coalesced batch — proven by the scan's ``fusedDispatches`` ==
    ``scanPrograms`` counters (>= 2 batches so coalescing is real),
    with ZERO host-fallback chunks, rows matching the host oracle
    EXACTLY, and fused-vs-unfused (stageFusion off) results bit-exact.
    EXPLAIN-ANALYZE-visible fusion membership (``fusedInto``) is
    asserted too. Returns the prom path."""
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq

    from spark_rapids_tpu import datatypes as dt
    from spark_rapids_tpu.config import RapidsConf
    from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec
    from spark_rapids_tpu.exec.base import (ExecCtx, collect_arrow,
                                            collect_arrow_cpu)
    from spark_rapids_tpu.exec.basic import TpuFilterExec, TpuProjectExec
    from spark_rapids_tpu.expr import (Alias, And, GreaterThanOrEqual,
                                       LessThan, Literal, Multiply)
    from spark_rapids_tpu.expr import UnresolvedColumn as col
    from spark_rapids_tpu.expr.aggregates import Sum
    from spark_rapids_tpu.io import TpuFileScanExec
    from spark_rapids_tpu.obs.metrics import dump_prometheus

    rng = np.random.default_rng(7)
    n = 8192
    t = pa.table({
        "l_quantity": pa.array(rng.integers(1, 51, n)
                               .astype(np.float32)),
        "l_extendedprice": pa.array(rng.uniform(900, 105000, n)
                                    .astype(np.float32)),
        "l_discount": pa.array((rng.integers(0, 11, n) / 100.0)
                               .astype(np.float32)),
        "l_shipdate": pa.array(rng.integers(8000, 10600, n)
                               .astype(np.int32)),
        "l_flag": pa.array(rng.integers(0, 4, n).astype(np.int64)),
    })
    path = os.path.join(out_dir, "fusion_smoke.parquet")
    pq.write_table(t, path, row_group_size=1024, compression="snappy")

    def build(conf):
        scan = TpuFileScanExec([path], conf=conf)
        f32 = lambda v: Literal(np.float32(v), dt.FLOAT32)  # noqa: E731
        cond = And(
            And(GreaterThanOrEqual(col("l_shipdate"),
                                   Literal(8766, dt.INT32)),
                LessThan(col("l_shipdate"), Literal(9131, dt.INT32))),
            LessThan(col("l_quantity"), f32(24.0)))
        proj = TpuProjectExec(
            [Alias(Multiply(col("l_extendedprice"), col("l_discount")),
                   "rev"), Alias(col("l_flag"), "l_flag")],
            TpuFilterExec(cond, scan))
        agg = TpuHashAggregateExec(
            [col("l_flag")], [Alias(Sum(col("rev")), "revenue")], proj)
        return scan, proj, agg

    # >1 coalesced batch: shrink the coalesce target below the file's
    # decoded size so the ONE-program-per-batch claim is tested per
    # batch, not degenerately on a single group
    conf = RapidsConf(
        {"spark.rapids.sql.scan.coalesceTargetBytes": str(16 << 10)})
    scan, proj, agg = build(conf)
    ctx = ExecCtx(conf)
    got = collect_arrow(agg, ctx).sort_by("l_flag")
    want = collect_arrow_cpu(build(conf)[2]).sort_by("l_flag")
    gd, wd = got.to_pydict(), want.to_pydict()
    assert gd["l_flag"] == wd["l_flag"], "fusion smoke keys diverge"
    assert np.allclose(gd["revenue"], wd["revenue"], rtol=1e-4), \
        "fusion smoke rows diverge from the host oracle"
    m = ctx.metrics[scan.node_label()]
    fused = int(m["fusedDispatches"].value)
    programs = int(m["scanPrograms"].value)
    assert fused >= 2, \
        f"expected >= 2 coalesced fused batches, got {fused}"
    assert fused == programs, \
        (f"dispatch granularity regressed: {programs} scan programs "
         f"but only {fused} fused — decode and chain ran as separate "
         "dispatches")
    assert int(m["fallbackChunks"].value) == 0, \
        f"fusion smoke hit {m['fallbackChunks'].value} fallback chunks"
    # fusion membership visible to EXPLAIN ANALYZE: scan, filter and
    # project all record the consumer program they fused into
    fused_nodes = [lbl for lbl, ms in ctx.metrics.items()
                   if "fusedInto" in ms]
    for want_op in ("FileScanExec", "FilterExec", "ProjectExec"):
        assert any(lbl.startswith(want_op) for lbl in fused_nodes), \
            f"{want_op} did not record fusedInto ({fused_nodes})"
    # bit-exactness: the same plan with stageFusion OFF must produce
    # the IDENTICAL table (not merely close) — fusion must never
    # change results
    conf_off = RapidsConf(
        {"spark.rapids.sql.scan.coalesceTargetBytes": str(16 << 10),
         "spark.rapids.sql.stageFusion.enabled": "false"})
    off = collect_arrow(build(conf_off)[2],
                        ExecCtx(conf_off)).sort_by("l_flag")
    assert off.to_pydict() == gd, \
        "fused vs unfused results are not bit-exact"
    print(f"fusion smoke: {fused}/{programs} scan programs fused "
          "(ONE dispatch per coalesced batch), rows match the oracle, "
          "zero fallback chunks, fused==unfused bit-exact")
    prom = dump_prometheus()
    prom_path = os.path.join(out_dir, "fusion_metrics.prom")
    with open(prom_path, "w") as f:
        f.write(prom)
    return prom_path


def run_sql_smoke(out_dir):
    """SQL-frontend CI gate: (1) parse + compile + plan-verify the FULL
    SQL corpus (tools/nds.py SQL_QUERIES) — zero parse failures, zero
    unexpected CPU fallbacks, verifier on; (2) run one SQL query end to
    end on a 2-worker process cluster against the pandas oracle;
    (3) a broken statement must leave a sql_parse_error event-log
    line."""
    from spark_rapids_tpu.cluster import TpuProcessCluster
    from spark_rapids_tpu.config import RapidsConf
    from spark_rapids_tpu.planner import TpuOverrides
    from spark_rapids_tpu.session import TpuSession
    from spark_rapids_tpu.sql import SqlParseError
    from spark_rapids_tpu.tools.event_log import read_event_logs
    from spark_rapids_tpu.tools.nds import (SQL_QUERIES,
                                            build_query_sql,
                                            gen_tables, pandas_oracle)
    tables = gen_tables(n_sales=1 << 13)
    s = TpuSession()
    plans = {}
    for name in sorted(SQL_QUERIES):
        df = build_query_sql(name, s, tables)  # parse + analyze
        pp = TpuOverrides(s.conf).apply(df._node)  # verifier is on
        fb = pp.fallback_nodes()
        assert not fb, f"{name}: unexpected CPU fallback {fb}"
        plans[name] = df
    print(f"sql corpus: {len(plans)} queries parsed, compiled and "
          "plan-verified clean")

    # one SQL query end to end across OS worker processes; one shuffle
    # partition so the plan's global sort+limit stays global (the
    # cluster applies the final stage per reduce partition)
    log_dir = os.path.join(out_dir, "events")
    s1 = TpuSession(conf={"spark.sql.shuffle.partitions": "1"})
    cdf = build_query_sql("q3", s1, tables)
    conf = RapidsConf({"spark.rapids.eventLog.dir": log_dir})
    with TpuProcessCluster(n_workers=2, conf=conf) as c:
        got = c.run_query(cdf._node).to_pandas()
    want = pandas_oracle("q3", tables).reset_index(drop=True)
    assert len(got) == len(want), (len(got), len(want))
    for ci, col_name in enumerate(want.columns):
        w = want[col_name].to_numpy()
        g = got.iloc[:, ci].to_numpy()
        import numpy as np
        if np.issubdtype(w.dtype, np.floating):
            assert np.allclose(g.astype(float), w, rtol=1e-6,
                               atol=1e-6), col_name
        else:
            assert (g == w).all(), col_name
    print("sql q3 end-to-end on the process cluster: rows match "
          "the oracle")

    # failure evidence: one sql_parse_error event line
    s2 = TpuSession(conf={"spark.rapids.eventLog.dir": log_dir})
    try:
        s2.sql("SELEKT broken FROM nowhere")
    except SqlParseError:
        pass
    else:
        raise AssertionError("broken SQL did not raise SqlParseError")
    evs = [e for e in read_event_logs(log_dir)
           if e.get("type") == "sql_parse_error"]
    assert len(evs) == 1 and evs[0]["line"] == 1, evs
    print("sql_parse_error event logged with line/col evidence")


def check_lint_report(path):
    """tpu-lint 2.0 JSON (schema 2): shape, rule names, count
    consistency, required reasons on allowlists, and the CI gate —
    zero unallowlisted, unbaselined violations."""
    errors = []
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        return [f"lint report unreadable: {e}"]
    from spark_rapids_tpu.analysis.lint import ALL_RULES, LINT_SCHEMA
    if doc.get("schema") != LINT_SCHEMA:
        errors.append(f"schema {doc.get('schema')!r} != {LINT_SCHEMA}")
    for key in ("findings", "violations", "allowlisted", "baselined",
                "files", "rules"):
        if key not in doc:
            errors.append(f"missing key {key!r}")
    if errors:
        return errors
    if doc["files"] <= 0:
        errors.append("no files were linted")
    if set(doc["rules"]) != set(ALL_RULES):
        errors.append(f"rules list drifted: {sorted(doc['rules'])}")
    hard = 0
    for i, f in enumerate(doc["findings"]):
        for key in ("rule", "path", "line", "message", "allowlisted",
                    "allow_reason", "baselined", "fingerprint"):
            if key not in f:
                errors.append(f"finding {i}: missing {key!r}")
                break
        else:
            if f["rule"] not in ALL_RULES:
                errors.append(f"finding {i}: unknown rule "
                              f"{f['rule']!r}")
            if f["allowlisted"] and not f["allow_reason"]:
                errors.append(f"finding {i}: allowlisted without a "
                              "reason")
            if not f["allowlisted"] and not f["baselined"]:
                hard += 1
    if hard != doc["violations"]:
        errors.append(f"violations={doc['violations']} but {hard} "
                      "unallowlisted+unbaselined findings")
    if doc["violations"] != 0:
        errors.append(f"{doc['violations']} unbaselined violation(s) "
                      "— fix them or accept via --write-baseline")
    return errors


def check_lockwatch(path):
    """Lock-order watchdog report(s): the named file plus any worker
    sibling reports (`<path>.w*`) must show a live watchdog with real
    acquisition traffic and ZERO inversions."""
    import glob
    errors = []
    paths = [path] + sorted(glob.glob(path + ".w*"))
    total_checked = 0
    for p in paths:
        try:
            with open(p) as f:
                doc = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            errors.append(f"{os.path.basename(p)}: unreadable: {e}")
            continue
        if not doc.get("installed"):
            errors.append(f"{os.path.basename(p)}: watchdog was not "
                          "installed")
        total_checked += (doc.get("counts") or {}).get("checked", 0)
        for inv in doc.get("inversions", []):
            errors.append(
                f"{os.path.basename(p)}: INVERSION {inv.get('why')} "
                f"at {inv.get('acquiring_site')} "
                f"(held: {inv.get('held')})")
    if not errors and total_checked <= 0:
        errors.append("watchdog saw zero checked acquisitions — the "
                      "run exercised no locks, which proves nothing")
    if not errors:
        print(f"lockwatch: {len(paths)} report(s), "
              f"{total_checked} checked acquisitions, 0 inversions")
    return errors


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--trace", help="Chrome trace JSON to validate")
    ap.add_argument("--prom", help="Prometheus text file to validate")
    ap.add_argument("--smoke", metavar="DIR",
                    help="run a tiny traced query, emit + validate")
    ap.add_argument("--scan-smoke", metavar="DIR", dest="scan_smoke",
                    help="run a device-decode parquet scan, check the "
                         "assemble/upload metric split, emit + validate")
    ap.add_argument("--mixed-encodings", action="store_true",
                    dest="mixed_encodings",
                    help="with --scan-smoke: the file exercises PLAIN "
                         "strings + DATA_PAGE_V2 + DELTA_* and the "
                         "smoke asserts zero host-fallback chunks")
    ap.add_argument("--flight", help="incident bundle JSON to validate")
    ap.add_argument("--flight-smoke", metavar="DIR", dest="flight_smoke",
                    help="run an injected-crash cluster query with "
                         "tracing disabled, assert exactly one valid "
                         "incident bundle")
    ap.add_argument("--shuffle-smoke", metavar="DIR",
                    dest="shuffle_smoke",
                    help="run a cluster shuffle query with injected "
                         "post-commit corruption, assert oracle rows "
                         "via exactly one map-stage rerun")
    ap.add_argument("--lifecycle-smoke", metavar="DIR",
                    dest="lifecycle_smoke",
                    help="run a deadline-exceeded cluster query under "
                         "chaos hang_query: exactly one classified "
                         "query_cancelled event + one incident bundle, "
                         "and a post-cancel query running green on the "
                         "same cluster")
    ap.add_argument("--spill-smoke", metavar="DIR", dest="spill_smoke",
                    help="run a reduce-side out-of-core sort with all "
                         "disk-spill writes hitting injected ENOSPC "
                         "(chaos disk_full): query green, classified "
                         "disk_pressure evidence, exactly one bundle, "
                         "planted orphan spill namespace reclaimed")
    ap.add_argument("--warehouse-smoke", metavar="DIR",
                    dest="warehouse_smoke",
                    help="run three queries on a 2-worker cluster "
                         "(green, user-cancelled under chaos "
                         "hang_query with /status read mid-flight, "
                         "spill_corrupt'd-then-retried): exactly three "
                         "sealed warehouse rows with correct outcome "
                         "classes, drift sentinel silent across a "
                         "repeat run")
    ap.add_argument("--fusion-smoke", metavar="DIR",
                    dest="fusion_smoke",
                    help="run q6-shaped scan->filter->project->"
                         "partial-agg from a multi-row-group parquet "
                         "file: the fusedDispatches/scanPrograms "
                         "counters must prove ONE spliced program per "
                         "coalesced batch, rows must match the oracle, "
                         "zero fallback chunks, fused==unfused "
                         "bit-exact")
    ap.add_argument("--sql-smoke", metavar="DIR", dest="sql_smoke",
                    help="parse + compile + plan-verify the full SQL "
                         "corpus (zero parse failures / fallbacks) and "
                         "run one SQL query end to end on the process "
                         "cluster")
    ap.add_argument("--profile", help="query-profile JSON to validate")
    ap.add_argument("--analyze-smoke", metavar="DIR",
                    dest="analyze_smoke",
                    help="EXPLAIN ANALYZE q3 from SQL on a 2-worker "
                         "process cluster: nonzero rows at every "
                         "scan/join/agg node, a valid profile json, "
                         "and a clean profiling compare of two runs")
    ap.add_argument("--mesh-smoke", metavar="DIR", dest="mesh_smoke",
                    help="bootstrap a 2-process jax.distributed mesh "
                         "over the worker fleet, run one gang join+agg "
                         "whose exchanges cross the process boundary, "
                         "gate on structural counters (process count, "
                         "collective epochs, bytes, device_kind — "
                         "never wall-clock) and validate the stitched "
                         "trace")
    ap.add_argument("--lint-report", dest="lint_report",
                    help="tpu-lint 2.0 JSON report to schema-validate "
                         "(and gate on zero unbaselined violations)")
    ap.add_argument("--lockwatch",
                    help="lock-order watchdog report JSON (plus "
                         "worker siblings <path>.w*) to gate on zero "
                         "inversions")
    args = ap.parse_args(argv)
    errors = []
    trace, prom = args.trace, args.prom
    # every bundle produced or named gets schema-checked — a smoke
    # must not shadow another smoke's (or the user's) bundle
    flights = [args.flight] if args.flight else []
    if args.smoke:
        os.makedirs(args.smoke, exist_ok=True)
        trace, prom = run_smoke(args.smoke)
        print(f"smoke outputs: {trace} {prom}")
    if args.scan_smoke:
        os.makedirs(args.scan_smoke, exist_ok=True)
        prom = run_scan_smoke(args.scan_smoke,
                              mixed=args.mixed_encodings)
        print(f"scan smoke output: {prom}")
    if args.fusion_smoke:
        os.makedirs(args.fusion_smoke, exist_ok=True)
        prom = run_fusion_smoke(args.fusion_smoke)
        print(f"fusion smoke output: {prom}")
    if args.flight_smoke:
        os.makedirs(args.flight_smoke, exist_ok=True)
        bundle = run_flight_smoke(args.flight_smoke)
        flights.append(bundle)
        print(f"flight smoke output: {bundle}")
    if args.shuffle_smoke:
        os.makedirs(args.shuffle_smoke, exist_ok=True)
        bundle = run_shuffle_smoke(args.shuffle_smoke)
        flights.append(bundle)
        print(f"shuffle smoke output: {bundle}")
    if args.lifecycle_smoke:
        os.makedirs(args.lifecycle_smoke, exist_ok=True)
        bundle = run_lifecycle_smoke(args.lifecycle_smoke)
        flights.append(bundle)
        print(f"lifecycle smoke output: {bundle}")
    if args.spill_smoke:
        os.makedirs(args.spill_smoke, exist_ok=True)
        bundle = run_spill_smoke(args.spill_smoke)
        flights.append(bundle)
        print(f"spill smoke output: {bundle}")
    ran_wh = False
    if args.warehouse_smoke:
        os.makedirs(args.warehouse_smoke, exist_ok=True)
        run_warehouse_smoke(args.warehouse_smoke)
        ran_wh = True
    ran_sql = False
    if args.sql_smoke:
        os.makedirs(args.sql_smoke, exist_ok=True)
        run_sql_smoke(args.sql_smoke)
        ran_sql = True
    profiles = [args.profile] if args.profile else []
    if args.analyze_smoke:
        os.makedirs(args.analyze_smoke, exist_ok=True)
        profiles.append(run_analyze_smoke(args.analyze_smoke))
        print(f"analyze smoke output: {profiles[-1]}")
    if args.mesh_smoke:
        os.makedirs(args.mesh_smoke, exist_ok=True)
        trace = run_mesh_smoke(args.mesh_smoke) or trace
        print(f"mesh smoke output: {trace}")
    if not trace and not prom and not flights and not ran_sql \
            and not ran_wh and not profiles and not args.lint_report \
            and not args.lockwatch:
        ap.error("nothing to do: pass --trace/--prom/--smoke/"
                 "--scan-smoke/--fusion-smoke/--flight/--flight-smoke/"
                 "--shuffle-smoke/--lifecycle-smoke/--spill-smoke/"
                 "--sql-smoke/--profile/"
                 "--analyze-smoke/--mesh-smoke/--warehouse-smoke/"
                 "--lint-report/--lockwatch")
    if args.lint_report:
        errors += [f"[lint] {e}"
                   for e in check_lint_report(args.lint_report)]
    if args.lockwatch:
        errors += [f"[lockwatch] {e}"
                   for e in check_lockwatch(args.lockwatch)]
    if trace:
        errors += [f"[trace] {e}" for e in check_trace(trace)]
    for fl in flights:
        errors += [f"[flight] {e}" for e in check_flight(fl)]
    for pf in profiles:
        errors += [f"[profile] {e}" for e in check_profile(pf)]
    if prom:
        try:
            with open(prom) as f:
                text = f.read()
        except OSError as e:
            errors.append(f"[prom] unreadable: {e}")
        else:
            errors += [f"[prom] {e}" for e in check_prometheus(text)]
    if errors:
        for e in errors:
            print(e, file=sys.stderr)
        return 1
    print("obs output OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
