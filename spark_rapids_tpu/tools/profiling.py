"""Profiling tool: post-run analysis of an executed plan.

TPU analog of the reference's profiling tool (SURVEY.md §2.2-F: mines
event logs for per-op times and tuning recommendations; mount empty,
capability-built). Here it mines the metrics the engine itself
accumulated during collect() — run with
spark.rapids.sql.metrics.level=DEBUG for real device times — and emits
the annotated plan plus ranked hotspots and recommendations.
"""
from __future__ import annotations

import os
from typing import Dict, List, Optional

__all__ = ["profile_report", "profile_event_logs", "critical_path",
           "profile_trace", "triage_report", "history_report",
           "compare_report"]


def profile_report(pp, ctx=None) -> str:
    """`pp` is a PhysicalPlan whose collect() already ran (or pass the
    ExecCtx used)."""
    ctx = ctx or pp.last_ctx
    lines = ["=== TPU profile ===", pp.metrics_report(ctx)]
    if ctx is None:
        lines.append("(no metrics: run collect() first)")
        return "\n".join(lines)

    # ranked hotspots by opTime, keyed on the stable operator-INSTANCE
    # id the planner stamps (obs/opmetrics.assign_op_ids): AQE
    # re-planning deep-copies reused sub-plans WITH their ids, so
    # duplicated instances accumulate into one metric row at the store
    # itself — the old name-based dedup across fresh #ids is gone, and
    # two distinct instances of the same operator class now rank
    # separately (per-instance attribution, like the reference UI)
    from ..obs.opmetrics import fold_snapshots
    folded = fold_snapshots([{"ops": {
        label: {name: m.value for name, m in ms.items()}
        for label, ms in ctx.metrics.items()}}])
    hot = sorted(((st["metrics"]["opTime"], st["label"])
                  for st in folded.values()
                  if st["metrics"].get("opTime")), reverse=True)
    if hot:
        lines.append("hotspots:")
        total = sum(t for t, _ in hot) or 1.0
        for t, label in hot[:5]:
            lines.append(f"  {label:<28} {t * 1e3:9.2f}ms "
                         f"({t / total:.0%})")

    recs: List[str] = []
    if not ctx.sync_metrics:
        recs.append("set spark.rapids.sql.metrics.level=DEBUG for "
                    "device-time opTime (timings above are dispatch "
                    "cost only)")
    for label, ms in ctx.metrics.items():
        sp = ms.get("spillTime")
        if sp is not None and sp.value > 0.05:
            recs.append(f"{label}: {sp.value * 1e3:.0f}ms spilling — "
                        "raise spark.rapids.memory.device.budgetBytes "
                        "or reduce concurrency")
        asm = ms.get("assembleTime")
        if asm is not None and asm.value > 0.5:
            recs.append(f"{label}: {asm.value * 1e3:.0f}ms assembling "
                        "host blobs — raise "
                        "spark.rapids.sql.scan.uploadThreads or the "
                        "reader pool size")
        up = ms.get("uploadTime")
        if up is not None and up.value > 0.5:
            wait = ms.get("uploadWaitTime")
            scan_v = ms.get("scanTime")
            scan_v = scan_v.value if scan_v is not None else 0.0
            if wait is not None and up.value > 0:
                # uploadWaitTime is ALL consumer blocking on the next
                # batch — when planning (scanTime) outweighs uploadTime
                # the feeder was starved by the reads, not the
                # host->device link, and uploadThreads is the wrong
                # lever. Which part of a read: a walk that never found a
                # fetched row group waiting (fetchAheadMax 0) waited for
                # storage; else the one-at-a-time page walk is the limit
                hidden = max(0.0, 1.0 - wait.value / up.value)
                ahead = ms.get("fetchAheadMax")
                if hidden >= 0.5:
                    lever = "keep data device-resident between stages"
                elif scan_v > up.value and ahead is not None \
                        and ahead.value > 0:
                    lever = ("the wait is bound by the page walk (one "
                             "row group at a time under the interpreter "
                             "lock), not by fetching: more reader "
                             "threads or uploadThreads will not help")
                elif scan_v > up.value:
                    lever = ("the wait is planning-bound — raise the "
                             "parquet multiThreadedRead.numThreads "
                             "reader pool, not uploadThreads")
                else:
                    lever = ("raise spark.rapids.sql.scan.uploadThreads"
                             " / inFlightBatches to overlap more of it")
                recs.append(
                    f"{label}: {up.value * 1e3:.0f}ms uploading, "
                    f"~{hidden:.0%} hidden behind compute — " + lever)
            else:
                recs.append(f"{label}: {up.value * 1e3:.0f}ms uploading "
                            "— keep data device-resident between stages")
    fb = pp.fallback_nodes()
    if fb:
        recs.append("CPU fallbacks present: " + ", ".join(sorted(set(fb)))
                    + " (see explain NOT_ON_GPU)")
    if recs:
        lines.append("recommendations:")
        lines.extend(f"  - {r}" for r in recs)
    return "\n".join(lines)


# --- event-log profiling (the reference tool's actual mode) ----------------
# The reference's ProfileMain mines event logs of ACCELERATED runs:
# op coverage, metric rollups, cross-run comparison, config
# recommendations (SURVEY.md:212). Same here over the engine's JSONL
# query events.

def profile_event_logs(path: str) -> str:
    import collections

    from .event_log import read_event_logs
    all_events = list(read_event_logs(path))
    sched_events = [ev for ev in all_events
                    if ev.get("type") == "scheduler"]
    events = [ev for ev in all_events if ev.get("type") != "scheduler"]
    lines = ["=== TPU profile (event logs) ===",
             f"events: {len(events)} query, {len(sched_events)} scheduler"]
    if not all_events:
        return "\n".join(lines + ["(no events under the given path)"])

    # op coverage across every logged plan
    op_total = collections.Counter()
    op_dev = collections.Counter()
    reason_count = collections.Counter()
    for ev in events:
        for n in ev.get("nodes", []):
            op_total[n["op"]] += 1
            if n["on_device"]:
                op_dev[n["op"]] += 1
            for r in n.get("reasons", []):
                reason_count[r] += 1
    lines.append("operator coverage:")
    for op, tot in op_total.most_common():
        lines.append(f"  {op:<28} {op_dev[op]}/{tot} on device")

    # metric rollups (opTime / spillTime / upload) by operator class
    roll = collections.defaultdict(float)
    for ev in events:
        for label, ms in ev.get("metrics", {}).items():
            op = label.split("#")[0]
            for mname in ("opTime", "spillTime", "uploadTime",
                          "assembleTime", "uploadWaitTime", "scanTime"):
                v = ms.get(mname)
                if isinstance(v, (int, float)):
                    roll[(op, mname)] += float(v)
    hot = sorted(((v, k) for k, v in roll.items() if v > 0),
                 reverse=True)
    if hot:
        lines.append("metric rollups (summed across runs):")
        for v, (op, mname) in hot[:10]:
            lines.append(f"  {op:<28} {mname:<12} {v * 1e3:9.1f}ms")

    # cross-run regression: same plan fingerprint, wall-time spread
    by_fp = collections.defaultdict(list)
    for ev in events:
        by_fp[ev.get("fingerprint", "?")].append(ev.get("wall_s", 0.0))
    regressions = []
    for fp, walls in by_fp.items():
        if len(walls) >= 2 and min(walls) > 0 \
                and max(walls) / min(walls) > 1.5:
            regressions.append((max(walls) / min(walls), fp, walls))
    if regressions:
        regressions.sort(reverse=True)
        lines.append("wall-time spread across runs of the same query "
                     "(>1.5x):")
        for ratio, fp, walls in regressions[:5]:
            lines.append(
                f"  {fp}  {min(walls) * 1e3:.1f}ms .. "
                f"{max(walls) * 1e3:.1f}ms  ({ratio:.1f}x)")

    # scheduler rollup: retry overhead next to the hotspots it hides in
    recs = []
    if sched_events:
        tot = collections.Counter()
        retry_overhead = 0.0
        cluster_wall = 0.0
        for ev in sched_events:
            s = ev.get("summary", {})
            for k in ("tasks_ok", "failures", "speculative_launched",
                      "speculative_lost", "workers_respawned",
                      "workers_blacklisted", "fetch_failures",
                      "stage_reruns"):
                tot[k] += int(s.get(k, 0))
            retry_overhead += float(s.get("retry_overhead_s", 0.0))
            cluster_wall += float(ev.get("wall_s", 0.0))
        lines.append("scheduler (cluster queries):")
        lines.append(f"  tasks ok {tot['tasks_ok']}, failed attempts "
                     f"{tot['failures']}, speculative launched "
                     f"{tot['speculative_launched']} "
                     f"(lost {tot['speculative_lost']})")
        lines.append(f"  workers respawned {tot['workers_respawned']}, "
                     f"blacklisted {tot['workers_blacklisted']}")
        if tot["fetch_failures"] or tot["stage_reruns"]:
            lines.append(
                f"  shuffle fetch failures {tot['fetch_failures']}, "
                f"map-stage reruns {tot['stage_reruns']}")
        lines.append(f"  retry overhead {retry_overhead * 1e3:.1f}ms "
                     f"of {cluster_wall * 1e3:.1f}ms cluster wall")
        if cluster_wall > 0 and retry_overhead > 0.1 * cluster_wall:
            recs.append(
                f"{retry_overhead / max(cluster_wall, 1e-9):.0%} of "
                "cluster wall went to failed/duplicate attempts — "
                "check worker stability before tuning kernels")
        if tot["stage_reruns"]:
            recs.append(
                f"{tot['stage_reruns']} map-stage rerun(s) recovered "
                "lost/corrupt shuffle output — check the shuffle "
                "storage (disk, NFS) feeding the cluster root; "
                "`profiling triage <incident>` names the bad blocks")
    # trace rollups from embedded span summaries (queries that ran with
    # spark.rapids.trace.dir set; the full timeline is in the trace
    # JSON — `profiling <trace.json>` mines its critical path)
    tr_cats = collections.defaultdict(lambda: [0, 0.0])
    for ev in all_events:
        for cat, c in (ev.get("trace", {}).get("by_cat") or {}).items():
            tr_cats[cat][0] += int(c.get("spans", 0))
            tr_cats[cat][1] += float(c.get("total_s", 0.0))
    if tr_cats:
        lines.append("trace span rollup (by category):")
        for cat, (n, tot) in sorted(tr_cats.items(),
                                    key=lambda kv: -kv[1][1]):
            lines.append(f"  {cat:<12} {n:5d} spans {tot * 1e3:9.1f}ms")

    spill_total = sum(v for (op, m), v in roll.items()
                      if m == "spillTime")
    if spill_total > 0.1:
        recs.append(f"{spill_total * 1e3:.0f}ms total spill — raise "
                    "the device memory budget or lower concurrency")
    if reason_count:
        top = reason_count.most_common(1)[0]
        recs.append(f"most common fallback ({top[1]}x): {top[0]}")
    if recs:
        lines.append("recommendations:")
        lines.extend(f"  - {r}" for r in recs)
    return "\n".join(lines)


# --- critical-path analysis over a stitched trace ---------------------------
# The hotspot table answers "which operator burned the most device
# time"; the critical path answers the question a timeline viewer
# answers visually — WHAT was the wall time actually spent on, across
# processes: "62% of wall time is shuffle fetch wait on stage 2", or
# "the retry of q1s1m0 added 1.8s".

def critical_path(spans: List[dict]) -> List[dict]:
    """The longest parent->child chain through a span forest (dicts as
    produced by Tracer.drain / load_chrome_trace). Starting from the
    root span with the largest duration, descend into the child
    covering the most time, to a leaf. Each step reports its span
    fields plus ``self_s`` (duration not covered by the next step) and
    ``frac`` (self_s / root duration)."""
    children: Dict[str, List[dict]] = {}
    by_id = {}
    for s in spans:
        if s.get("span_id") is not None:
            by_id[s["span_id"]] = s
    for s in spans:
        p = s.get("parent_id")
        if p is not None and p in by_id:
            children.setdefault(p, []).append(s)
    roots = [s for s in spans
             if s.get("parent_id") not in by_id]
    if not roots:
        return []
    root = max(roots, key=lambda s: s.get("dur", 0.0))
    total = max(root.get("dur", 0.0), 1e-12)
    path = []
    node = root
    while node is not None:
        kids = children.get(node.get("span_id"), [])
        nxt = max(kids, key=lambda s: s.get("dur", 0.0)) if kids else None
        self_s = node.get("dur", 0.0) - (nxt.get("dur", 0.0) if nxt else 0)
        path.append(dict(node, self_s=max(self_s, 0.0),
                         frac=max(self_s, 0.0) / total))
        node = nxt
    return path


def format_critical_path(spans: List[dict]) -> List[str]:
    """Render the critical path plus the retry overhead it names."""
    path = critical_path(spans)
    if not path:
        return ["(no spans)"]
    total = max(path[0].get("dur", 0.0), 1e-12)
    lines = [f"critical path ({total * 1e3:.1f}ms wall):"]
    for depth, step in enumerate(path):
        where = "driver" if step.get("pid", 0) == 0 \
            else f"worker {step['pid'] - 1}"
        lines.append(
            f"  {'  ' * depth}{step['name']} [{step.get('cat', '?')}, "
            f"{where}]  {step['dur'] * 1e3:9.1f}ms  "
            f"self {step['self_s'] * 1e3:.1f}ms ({step['frac']:.0%})")
    top = max(path, key=lambda s: s["self_s"])
    lines.append(
        f"  => {top['frac']:.0%} of wall time is {top['name']} "
        f"({top.get('cat', '?')})")
    # name the retry overhead: attempt spans that ended err/lost are
    # pure waste the timeline hides inside stage spans
    wasted = [s for s in spans if s.get("cat") == "attempt"
              and (s.get("args") or {}).get("state") in ("err", "lost")]
    if wasted:
        w = sum(s.get("dur", 0.0) for s in wasted)
        names = sorted({s["name"] for s in wasted})
        lines.append(
            f"  retry overhead: {w * 1e3:.1f}ms "
            f"({w / total:.0%} of wall) across {len(wasted)} "
            f"failed/duplicate attempts: {', '.join(names[:5])}"
            + (" ..." if len(names) > 5 else ""))
    return lines


def profile_trace(path: str) -> str:
    """Mine one Chrome trace JSON (spark.rapids.trace.dir output):
    per-category rollup + the critical path."""
    import collections

    from ..obs.tracer import load_chrome_trace
    spans = load_chrome_trace(path)
    lines = [f"=== TPU trace profile ({path}) ===",
             f"spans: {len(spans)}"]
    if not spans:
        return "\n".join(lines)
    by_cat = collections.defaultdict(float)
    for s in spans:
        by_cat[s.get("cat", "?")] += s.get("dur", 0.0)
    lines.append("time by category (overlapping spans sum):")
    for cat, tot in sorted(by_cat.items(), key=lambda kv: -kv[1]):
        lines.append(f"  {cat:<12} {tot * 1e3:9.1f}ms")
    lines.extend(format_critical_path(spans))
    return "\n".join(lines)


# --- incident-bundle triage --------------------------------------------------
# The flight recorder (obs/recorder.py) dumps incident bundles when an
# anomaly fires; triage renders one for a human: what fired, the 30s of
# ring events preceding it per process, the HBM high-water curve, and
# per-stage straggler/attempt attribution.

_TRIAGE_WINDOW_S = 30.0


def _fmt_bytes(n: float) -> str:
    n = float(n)
    for unit in ("B", "KB", "MB", "GB", "TB"):
        if abs(n) < 1024 or unit == "TB":
            return f"{n:.1f}{unit}" if unit != "B" else f"{int(n)}B"
        n /= 1024
    return f"{n:.1f}TB"


def _fmt_ring_event(e: dict) -> str:
    kind = e.get("kind", "?")
    if kind == "sched":
        return (f"sched {e.get('event', '?')} {e.get('task', '')} "
                f"a{e.get('attempt', '?')} w{e.get('worker', '?')} "
                f"{e.get('reason', '')}").rstrip()
    if kind == "mem":
        ev = e.get("ev", "?")
        if ev in ("disk_pressure", "spill_read_failed",
                  "spill_write_failed"):
            return (f"mem SPILL-{ev.upper()} "
                    f"[{e.get('fail_kind', '?')}] "
                    f"{os.path.basename(e.get('path') or '')} "
                    f"{e.get('detail', '')}").rstrip()
        if ev == "spill_read_retry":
            return (f"mem spill-read-retry #{e.get('n', '?')} "
                    f"{e.get('error', '')}").rstrip()
        return (f"mem {ev} {_fmt_bytes(e.get('bytes', 0))} "
                f"(device {_fmt_bytes(e.get('device', 0))}, "
                f"host {_fmt_bytes(e.get('host', 0))})")
    if kind == "task":
        extra = e.get("error", "")
        return (f"task {e.get('ev', '?')} {e.get('task', '')} "
                f"a{e.get('attempt', '?')} {extra}").rstrip()
    if kind == "shuffle":
        ev = e.get("ev", "?")
        if ev == "fetch_failure":
            return (f"shuffle FETCH-FAILURE [{e.get('fail_kind', '?')}] "
                    f"s{e.get('sid', '?')} p{e.get('part', '?')} "
                    f"map {e.get('map', '?')} {e.get('path', '')}")
        if ev == "fetch_retry":
            return (f"shuffle fetch-retry #{e.get('n', '?')} "
                    f"s{e.get('sid', '?')} p{e.get('part', '?')} "
                    f"{e.get('error', '')}")
        return (f"shuffle {ev} s{e.get('sid', '?')} "
                f"p{e.get('part', '?')} wait "
                f"{e.get('wait_s', 0) * 1e3:.1f}ms")
    if kind == "span":
        return (f"span {e.get('name', '?')} [{e.get('cat', '?')}] "
                f"{e.get('dur', 0) * 1e3:.1f}ms")
    if kind == "plan":
        return (f"plan {e.get('n_fallbacks', 0)} CPU fallbacks "
                f"{e.get('fallbacks', '')}").rstrip()
    return f"{kind} {e}"


def _memory_curve(timeline: dict, width: int = 24) -> List[str]:
    """Text rendering of the HBM timeline: in-use device bytes after
    each transition, bar-scaled to the high-water mark. Every cluster
    process owns its own device runtime, so rows are labeled by
    process — occupancy values from different processes are separate
    series, not one curve."""
    evs = timeline.get("events") or []
    high = max(int(timeline.get("high_water_bytes", 0) or 0), 1)
    budget = int(timeline.get("budget_bytes", 0) or 0)
    lines = [f"  high water {_fmt_bytes(timeline.get('high_water_bytes', 0))}"
             + (f" of {_fmt_bytes(budget)} budget" if budget else "")
             + " (worst single process)"]
    for proc, p in sorted((timeline.get("per_proc") or {}).items()):
        if proc:
            lines.append(f"    {proc}: high water "
                         f"{_fmt_bytes(p.get('high_water_bytes', 0))}")
    if not evs:
        return lines + ["  (no memory-ledger transitions recorded)"]
    t_origin = evs[0].get("ts", 0.0)
    shown = evs if len(evs) <= 40 else evs[-40:]
    if len(evs) > 40:
        lines.append(f"  (last 40 of {len(evs)} transitions)")
    for e in shown:
        dev = int(e.get("device", 0) or 0)
        bar = "#" * max(0, round(width * dev / high))
        proc = e.get("proc", "")
        lines.append(
            f"  t+{e.get('ts', 0.0) - t_origin:7.3f}s "
            f"{(proc[:12] if proc else '-'):<12} "
            f"{_fmt_bytes(dev):>10} {e.get('ev', '?'):<10} {bar}")
    return lines


def triage_report(bundle) -> str:
    """Render one incident bundle (path or loaded dict) into a human
    report — the `triage` mode of this tool."""
    import json
    if isinstance(bundle, str):
        with open(bundle) as f:
            bundle = json.load(f)
    lines = [f"=== flight-recorder triage "
             f"({bundle.get('incident_id', '?')}) ===",
             f"query {bundle.get('query', '?')}"]

    anomalies = bundle.get("anomalies") or []
    lines.append(f"what fired ({len(anomalies)} anomal"
                 f"{'y' if len(anomalies) == 1 else 'ies'}):")
    for a in anomalies:
        where = a.get("proc", "?")
        w = a.get("worker", -1)
        if isinstance(w, int) and w >= 0:
            where += f" (worker {w})"
        lines.append(
            f"  [{a.get('kind', '?')}] {a.get('task', '')} "
            f"a{a.get('attempt', '?')} on {where}: "
            f"{(a.get('detail') or '').strip()[:160]}")
    if not anomalies:
        lines.append("  (none recorded — bundle written by hand?)")

    # the N seconds of ring events preceding the first trigger, per
    # process — the black-box playback
    t_fire = min((a.get("ts", 0.0) for a in anomalies),
                 default=bundle.get("ts", 0.0)) or bundle.get("ts", 0.0)
    lines.append(f"last {_TRIAGE_WINDOW_S:.0f}s before the first "
                 "trigger, per process:")
    for proc in sorted(bundle.get("rings") or {}):
        evs = [e for e in bundle["rings"][proc]
               if t_fire - _TRIAGE_WINDOW_S <= e.get("ts", 0.0)
               <= t_fire + 1.0]
        lines.append(f"  [{proc}] {len(evs)} events")
        for e in evs[-15:]:
            lines.append(f"    t{e.get('ts', 0.0) - t_fire:+8.3f}s "
                         + _fmt_ring_event(e))

    lines.append("HBM timeline:")
    lines.extend(_memory_curve(bundle.get("memory_timeline") or {}))

    lines.append("straggler / attempt attribution:")
    for stage, st in sorted((bundle.get("attempts") or {}).items()):
        lines.append(f"  stage {stage}: median ok "
                     f"{st.get('median_ok_s', 0.0) * 1e3:.1f}ms, "
                     f"straggler cut "
                     f"{st.get('straggler_cut_s', 0.0) * 1e3:.1f}ms")
        for a in st.get("attempts", []):
            mark = " <-- " + a["state"].upper() \
                if a in (st.get("flagged") or []) else ""
            lines.append(
                f"    {a.get('task', '?')} a{a.get('attempt', '?')} "
                f"w{a.get('worker', '?')} {a.get('state', '?'):<9} "
                f"{a.get('runtime_s', 0.0) * 1e3:9.1f}ms"
                f"{mark} {a.get('reason', '')[:80]}".rstrip())

    fbs = bundle.get("plan_fallbacks") or []
    if any(f.get("n_fallbacks") for f in fbs):
        lines.append("plan fallbacks:")
        for f in fbs:
            if f.get("n_fallbacks"):
                lines.append(f"  {f.get('fallbacks', '')[:200]}")
    delta = bundle.get("conf_delta") or {}
    if delta:
        lines.append("non-default conf:")
        for k in sorted(delta):
            lines.append(f"  {k} = {delta[k]}")
    return "\n".join(lines)


# --- query-profile history + cross-run comparison ----------------------------
# The persisted profile-<id>.json files (spark.rapids.history.dir,
# written by PhysicalPlan.collect and TpuProcessCluster.run_query via
# obs/opmetrics.py) are the offline record of per-operator runtime:
# `history` lists/inspects them, `compare` diffs two runs per OPERATOR
# so a BENCH-level regression (one opaque number) decomposes into
# "which node ate it". `compare` also accepts two BENCH_r0x.json files.

def history_report(path: str, profile_id: Optional[str] = None) -> str:
    """List the profiles under a history dir, or inspect one (by
    profile id, filename, or unique prefix): the annotated plan plus
    the per-operator aggregate table."""
    from ..obs.opmetrics import read_profiles
    profs = read_profiles(path)
    if not profs:
        return f"(no query profiles under {path})"
    if profile_id:
        matches = [(fp, doc) for fp, doc in profs
                   if profile_id in (doc.get("profile_id", ""),
                                     os.path.basename(fp))
                   or doc.get("profile_id", "").startswith(profile_id)]
        if not matches:
            return f"(no profile matching {profile_id!r} under {path})"
        return "\n\n".join(_render_profile(doc) for _, doc in matches)
    lines = [f"=== query-profile history ({path}) ===",
             f"{len(profs)} profiles (oldest first):"]
    for fp, doc in profs:
        sinks = sorted(doc.get("ops", {}).values(),
                       key=lambda st: -st.get("metrics", {})
                       .get("opTime", 0.0))
        top = "-"
        if sinks and sinks[0].get("metrics", {}).get("opTime"):
            top = sinks[0].get("label", "?")
        lines.append(
            f"  {doc.get('profile_id', os.path.basename(fp)):<28} "
            f"{doc.get('query', '') or '-':<6} "
            f"{doc.get('cluster', '?'):<8} {doc.get('source', '?'):<5} "
            f"{doc.get('wall_s', 0.0) * 1e3:9.1f}ms  top: {top}")
    return "\n".join(lines)


def _render_profile(doc: dict) -> str:
    lines = [f"=== {doc.get('profile_id', '?')} "
             f"(query {doc.get('query', '') or '-'}, "
             f"{doc.get('cluster', '?')}/{doc.get('source', '?')}, "
             f"{doc.get('wall_s', 0.0) * 1e3:.1f}ms, "
             f"fingerprint {doc.get('fingerprint', '?')}) ==="]
    from ..obs.opmetrics import _fold_key
    ops = doc.get("ops", {})
    by_label = {st.get("label", k): (k, st) for k, st in ops.items()}
    for n in doc.get("nodes", []):
        pad = "  " * int(n.get("depth", 0))
        st = by_label.get(n.get("label"), (None, None))[1]
        if st is None:
            st = ops.get(_fold_key(n.get("label", "")))
        ann = ""
        if st:
            m = st.get("metrics", {})
            bits = [f"rows={int(m.get('rows', 0))}",
                    f"opTime={m.get('opTime', 0.0) * 1e3:.2f}ms"]
            if st.get("tasks", 1) > 1:
                bits.append(f"tasks={st['tasks']} "
                            f"skew={st.get('skew', 1.0)}")
            ann = "  [" + ", ".join(bits) + "]"
        lines.append(f"{pad}{n.get('describe', n.get('op', '?'))}{ann}")
    return "\n".join(lines)


def _load_compare_doc(path: str) -> dict:
    import json
    with open(path) as f:
        doc = json.load(f)
    if isinstance(doc, dict) and isinstance(doc.get("parsed"), dict):
        return doc["parsed"]  # BENCH_r0x.json wrapper
    if isinstance(doc, dict) and "tail" in doc and "cmd" in doc:
        # wrapper whose parsed field was never filled: recover the
        # bench's one JSON line from the tail so the round still
        # carries its metrics AND its device_kind into the
        # comparability gate (a CPU-run round diffed against a TPU run
        # must REFUSE, not report a ~1000x fake regression)
        for line in reversed(str(doc.get("tail", "")).splitlines()):
            line = line.strip()
            if line.startswith("{"):
                try:
                    parsed = json.loads(line)
                except ValueError:
                    break
                if isinstance(parsed, dict):
                    return parsed
                break
    return doc if isinstance(doc, dict) else {}


def _device_kind_guard(a: dict, b: dict, a_path: str, b_path: str,
                       allow_cross_device: bool):
    """Comparability gate: numbers measured on different hardware are
    not comparable — a CPU-backend bench read against a TPU bench looks
    like a ~1000x 'regression' that is really a backend swap (exactly
    what a naive BENCH_r06-vs-r05 diff would report). Returns
    ``(refusal_or_None, warning_or_None)``; docs without a recorded
    device_kind (pre-guard profiles/benches) pass — absence of evidence
    is not a mismatch."""
    ka, kb = a.get("device_kind"), b.get("device_kind")
    if ka is None or kb is None or ka == kb:
        return None, None  # same device, or a pre-guard doc
    if allow_cross_device:
        return None, ("=== WARNING: device_kind mismatch "
                      f"({ka!r} vs {kb!r}) — cross-device diff "
                      "forced ===")
    return "\n".join([
        "=== compare REFUSED: device_kind mismatch ===",
        f"  A ({a_path}): device_kind={ka!r}",
        f"  B ({b_path}): device_kind={kb!r}",
        "  Numbers measured on different hardware are not "
        "comparable — a backend swap reads as a giant fake "
        "regression (or win).",
        "  Re-run both on the same device_kind, or pass "
        "--allow-cross-device to diff anyway."]), None


def compare_report(a_path: str, b_path: str,
                   threshold: float = 1.5,
                   allow_cross_device: bool = False) -> str:
    """Per-operator time/rows deltas between two query profiles (A =
    baseline, B = candidate); operators whose opTime grew by at least
    ``threshold``x (above a 1ms floor) are flagged REGRESSED. Two
    BENCH json files compare their shared scalar metrics instead.
    Comparisons across differing ``device_kind`` are REFUSED unless
    ``allow_cross_device`` (then the report leads with a warning)."""
    a, b = _load_compare_doc(a_path), _load_compare_doc(b_path)
    guard, warning = _device_kind_guard(a, b, a_path, b_path,
                                        allow_cross_device)
    warn = warning + "\n" if warning else ""
    if guard is not None:
        return guard
    if not (isinstance(a.get("ops"), dict)
            and isinstance(b.get("ops"), dict)):
        return warn + _compare_bench(a, b, a_path, b_path, threshold)
    lines = ([warn.rstrip()] if warn else []) + [
        f"=== profile compare (A={a.get('profile_id', a_path)}, "
        f"B={b.get('profile_id', b_path)}, "
        f"threshold {threshold}x) ==="]
    wa, wb = a.get("wall_s", 0.0), b.get("wall_s", 0.0)
    ratio = f"{wb / wa:.2f}x" if wa > 0 else "n/a"
    lines.append(f"wall: {wa * 1e3:.1f}ms -> {wb * 1e3:.1f}ms ({ratio})")
    if a.get("fingerprint") != b.get("fingerprint"):
        lines.append("NOTE: plan fingerprints differ — operator ids "
                     "may not describe the same plan shape")
    aops, bops = a["ops"], b["ops"]
    rows_out = []
    regressions = 0
    for key in sorted(set(aops) | set(bops),
                      key=lambda k: -(bops.get(k, aops.get(k, {}))
                                      .get("metrics", {})
                                      .get("opTime", 0.0))):
        sa, sb = aops.get(key), bops.get(key)
        label = (sb or sa).get("label", key)
        if sa is None:
            rows_out.append(f"  {label:<36} only in B")
            continue
        if sb is None:
            rows_out.append(f"  {label:<36} only in A")
            continue
        ta = sa.get("metrics", {}).get("opTime", 0.0)
        tb = sb.get("metrics", {}).get("opTime", 0.0)
        ra = int(sa.get("metrics", {}).get("rows", 0))
        rb = int(sb.get("metrics", {}).get("rows", 0))
        flag = ""
        if tb > max(ta * threshold, ta + 1e-3):
            flag = f"  <-- REGRESSED ({tb / ta:.1f}x)" if ta > 0 \
                else "  <-- REGRESSED (new time)"
            regressions += 1
        drows = f" rows {ra}->{rb}" if ra != rb else f" rows {ra}"
        rows_out.append(f"  {label:<36} {ta * 1e3:9.2f}ms -> "
                        f"{tb * 1e3:9.2f}ms{drows}{flag}")
    lines.append(f"per-operator opTime (A -> B), {regressions} "
                 f"regression(s):")
    lines.extend(rows_out)
    return "\n".join(lines)


def _compare_bench(a: dict, b: dict, a_path: str, b_path: str,
                   threshold: float) -> str:
    """Scalar diff of two BENCH json documents (shared numeric keys,
    ratio-sorted); changes beyond the threshold in either direction
    are flagged."""
    lines = [f"=== bench compare (A={a_path}, B={b_path}) ==="]
    keys = [k for k in a if k in b
            and isinstance(a[k], (int, float))
            and isinstance(b[k], (int, float))
            and not isinstance(a[k], bool)]
    if not keys:
        return "\n".join(lines + ["(no shared numeric metrics)"])

    def _ratio(k):
        return (b[k] / a[k]) if a[k] else float("inf")
    import math
    keys.sort(key=lambda k: -abs(math.log(max(_ratio(k), 1e-12)))
              if _ratio(k) not in (0, float("inf")) else float("-inf"))
    for k in keys:
        r = _ratio(k)
        flag = ""
        if r and r != float("inf") \
                and (r >= threshold or r <= 1.0 / threshold):
            flag = f"  <-- CHANGED ({r:.2f}x)"
        rtxt = f"{r:.3f}x" if r not in (0, float("inf")) else "n/a"
        lines.append(f"  {k:<40} {a[k]:>12} -> {b[k]:>12}  "
                     f"{rtxt}{flag}")
    return "\n".join(lines)


def _main(argv):
    import sys
    usage = ("usage: python -m spark_rapids_tpu.tools.profiling "
             "<event-log dir | trace-*.json | triage <incident.json> | "
             "history <dir> [profile-id] | "
             "compare <a.json> <b.json> [--threshold X] | "
             "warehouse <dir> | "
             "drift <dir> [--bytes-tolerance X] [--variant-bound N] "
             "[--allow-cross-device]>")
    if not argv:
        print(usage, file=sys.stderr)
        return 2
    if argv[0] == "triage":
        if len(argv) < 2:
            print("usage: profiling triage <incident-*.json>",
                  file=sys.stderr)
            return 2
        print(triage_report(argv[1]))
    elif argv[0] == "history":
        if len(argv) < 2:
            print("usage: profiling history <dir> [profile-id]",
                  file=sys.stderr)
            return 2
        print(history_report(argv[1],
                             argv[2] if len(argv) > 2 else None))
    elif argv[0] == "compare":
        rest = [a for a in argv[1:] if not a.startswith("--")]
        threshold = 1.5
        allow_cross = "--allow-cross-device" in argv
        for i, a in enumerate(argv):
            if a == "--threshold" and i + 1 < len(argv):
                threshold = float(argv[i + 1])
                rest = [x for x in rest if x != argv[i + 1]]
            elif a.startswith("--threshold="):
                threshold = float(a.split("=", 1)[1])
        if len(rest) != 2:
            print("usage: profiling compare <a.json> <b.json> "
                  "[--threshold X] [--allow-cross-device]",
                  file=sys.stderr)
            return 2
        report = compare_report(rest[0], rest[1], threshold=threshold,
                                allow_cross_device=allow_cross)
        print(report)
        if report.startswith("=== compare REFUSED"):
            return 3  # comparability gate tripped — not a diff result
    elif argv[0] == "warehouse":
        if len(argv) < 2:
            print("usage: profiling warehouse <dir>", file=sys.stderr)
            return 2
        from ..obs.warehouse import render_warehouse
        print(render_warehouse(argv[1]))
    elif argv[0] == "drift":
        rest = [a for a in argv[1:] if not a.startswith("--")]
        bytes_tol = None
        variant_bound = None
        allow_cross = "--allow-cross-device" in argv
        for i, a in enumerate(argv):
            if a == "--bytes-tolerance" and i + 1 < len(argv):
                bytes_tol = float(argv[i + 1])
                rest = [x for x in rest if x != argv[i + 1]]
            elif a.startswith("--bytes-tolerance="):
                bytes_tol = float(a.split("=", 1)[1])
            elif a == "--variant-bound" and i + 1 < len(argv):
                variant_bound = int(argv[i + 1])
                rest = [x for x in rest if x != argv[i + 1]]
            elif a.startswith("--variant-bound="):
                variant_bound = int(a.split("=", 1)[1])
        if len(rest) != 1:
            print("usage: profiling drift <dir> [--bytes-tolerance X] "
                  "[--variant-bound N] [--allow-cross-device]",
                  file=sys.stderr)
            return 2
        from ..obs.warehouse import drift_report
        report, rc = drift_report(rest[0], bytes_tolerance=bytes_tol,
                                  variant_bound=variant_bound,
                                  allow_cross_device=allow_cross)
        print(report)
        # rc 3 = cross-device_kind refusal (same gate as compare);
        # rc 1 = structural regressions flagged; rc 0 = clean
        return rc
    elif argv[0].endswith(".json"):
        print(profile_trace(argv[0]))
    else:
        print(profile_event_logs(argv[0]))
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(_main(sys.argv[1:]))
