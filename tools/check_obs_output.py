#!/usr/bin/env python
"""Schema checks for the observability files the engine writes.

A CPU-ONLY tool: it reads files and never needs a chip, so the platform
is pinned to the CPU below, before jax is imported, wherever the script
is started. Each validator is also the oracle of the tier-1 tests that
produce the same file (tests/test_obs.py, tests/test_flight.py, ...).

- ``--trace FILE``   validate a Chrome trace_event JSON written under
  ``spark.rapids.trace.dir`` (event shape, unique span ids, resolvable
  parent linkage, process-name metadata, trace_id consistency);
- ``--prom FILE``    validate Prometheus text exposition (sample-line
  grammar, TYPE declarations, histogram bucket monotonicity and
  _count/+Inf agreement);
- ``--flight FILE``  validate a flight-recorder incident bundle
  (required keys, monotonic timestamps, non-empty memory timeline);
- ``--profile FILE``  validate a query-profile JSON
  (``spark.rapids.history.dir`` output: required keys, non-empty plan
  record + per-operator aggregate, coherent totals/maxima);
- ``--lint-report FILE``  validate a tpu-lint 2.0 JSON report
  (schema 2: rule names, count consistency, required allowlist
  reasons) and fail on any unallowlisted, unbaselined violation;
- ``--lockwatch FILE``  validate lock-order watchdog report(s) (the
  file plus any ``<FILE>.w*`` worker siblings, as a run under
  ``RAPIDS_TPU_LOCKWATCH=1`` writes them): watchdog installed, nonzero
  checked acquisitions, ZERO inversions of the declared lock hierarchy.

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


def check_lint_report(path):
    """tpu-lint 2.0 JSON (schema 2): shape, rule names, count
    consistency, required reasons on allowlists, and zero
    unallowlisted, unbaselined violations."""
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
    ap.add_argument("--flight", help="incident bundle JSON to validate")
    ap.add_argument("--profile", help="query-profile JSON to validate")
    ap.add_argument("--lint-report", dest="lint_report",
                    help="tpu-lint 2.0 JSON report to schema-validate "
                         "(fails on any unbaselined violation)")
    ap.add_argument("--lockwatch",
                    help="lock-order watchdog report JSON (plus "
                         "worker siblings <path>.w*): fails on any "
                         "inversion")
    args = ap.parse_args(argv)
    checks = (("lint", args.lint_report, check_lint_report),
              ("lockwatch", args.lockwatch, check_lockwatch),
              ("trace", args.trace, check_trace),
              ("flight", args.flight, check_flight),
              ("profile", args.profile, check_profile))
    if not args.prom and not any(path for _, path, _ in checks):
        ap.error("nothing to do: pass --trace/--prom/--flight/--profile/"
                 "--lint-report/--lockwatch")
    errors = []
    for tag, path, check in checks:
        if path:
            errors += [f"[{tag}] {e}" for e in check(path)]
    if args.prom:
        try:
            with open(args.prom) as f:
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
