"""Qualification tool: how much of a workload would run on TPU?

TPU analog of the reference's qualification tool (SURVEY.md §2.2-F:
offline analysis of which plans/operators accelerate; mount empty,
capability-built). Instead of parsing event logs, it runs the REAL
override pass over a plan tree in dry-run and scores the outcome.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional

from ..config import RapidsConf
from ..exec.base import TpuExec

__all__ = ["qualify", "QualificationReport", "qualify_event_logs",
           "AppQualification"]


@dataclasses.dataclass
class QualificationReport:
    total_ops: int
    on_device_ops: int
    fallback_reasons: List[str]
    score: float          # fraction of operators that accelerate

    def render(self) -> str:
        lines = [
            "=== TPU qualification report ===",
            f"operators on device : {self.on_device_ops}/{self.total_ops}"
            f"  (score {self.score:.0%})",
        ]
        if self.fallback_reasons:
            lines.append("not accelerated:")
            lines.extend(f"  - {r}" for r in self.fallback_reasons)
        else:
            lines.append("fully accelerated: every operator runs on TPU")
        rec = ("RECOMMENDED: this workload accelerates well"
               if self.score >= 0.75 else
               "PARTIAL: review the fallback reasons before migrating"
               if self.score >= 0.3 else
               "NOT RECOMMENDED: most operators fall back to CPU")
        lines.append(rec)
        return "\n".join(lines)


def qualify(plan: TpuExec,
            conf: Optional[RapidsConf] = None) -> QualificationReport:
    """Dry-run the override pass (wrap + tag only — no execution, no
    transition rewrite) and score device placement."""
    from ..planner import TpuOverrides
    ov = TpuOverrides(conf or RapidsConf())
    meta = ov._wrap(plan)
    ov._tag(meta)

    total = 0
    on_dev = 0
    reasons: List[str] = []

    def rec(m):
        nonlocal total, on_dev
        total += 1
        if m.on_device:
            on_dev += 1
        else:
            reasons.append(
                f"{m.node.pretty_name()}: {'; '.join(m.reasons)}")
        for c in m.children:
            rec(c)

    rec(meta)
    return QualificationReport(total, on_dev, reasons,
                               on_dev / max(total, 1))


# --- event-log qualification (the reference tool's actual mode) ------------
# The reference's QualificationMain parses event logs of CPU runs and
# estimates per-app speedup (SURVEY.md:211). Same here: feed it the
# JSONL logs of runs executed with spark.rapids.sql.enabled=false — the
# planner still tags what WOULD place on device — and it models the
# speedup per query with Amdahl over per-operator acceleration factors
# measured on this engine's own benchmarks.

# per-op speedup factors (device vs host) that NO chip run produced
# (ROADMAP D14: derive them from ledger cells or stop printing a
# speed-up); unknown ops use DEFAULT_FACTOR
_OP_FACTORS = {
    "HashAggregateExec": 40.0, "ShuffledHashJoinExec": 80.0,
    "BroadcastHashJoinExec": 80.0, "SortExec": 25.0,
    "WindowExec": 25.0, "FilterExec": 50.0, "ProjectExec": 50.0,
    "FileScanExec": 1.3, "ShuffleExchangeExec": 10.0,
    "TopNExec": 25.0, "ExpandExec": 30.0, "GenerateExec": 20.0,
}
_DEFAULT_FACTOR = 10.0


@dataclasses.dataclass
class AppQualification:
    queries: int
    total_wall_s: float
    est_speedup: float           # Amdahl-modelled app-level speedup
    per_query: List[dict]        # fingerprint, wall_s, eligible, est
    top_blockers: List[str]

    def render(self) -> str:
        lines = [
            "=== TPU qualification (event logs) ===",
            f"queries analyzed    : {self.queries}",
            f"total wall time     : {self.total_wall_s:.2f}s",
            f"estimated speedup   : {self.est_speedup:.1f}x",
        ]
        worst = sorted(self.per_query, key=lambda q: q["est_speedup"])
        lines.append("slowest-accelerating queries:")
        for q in worst[:5]:
            lines.append(
                f"  {q['fingerprint']}  wall {q['wall_s'] * 1e3:7.1f}ms"
                f"  eligible {q['eligible']:.0%}"
                f"  est {q['est_speedup']:.1f}x")
        if self.top_blockers:
            lines.append("top fallback reasons:")
            lines.extend(f"  - {r}" for r in self.top_blockers[:8])
        rec = ("RECOMMENDED" if self.est_speedup >= 3 else
               "PARTIAL" if self.est_speedup >= 1.5 else
               "NOT RECOMMENDED")
        lines.append(f"{rec}: modelled from per-op factors measured on "
                     "this engine's benchmarks")
        return "\n".join(lines)


def qualify_event_logs(path: str) -> AppQualification:
    """Analyze the JSONL query events under `path` (a CPU run's logs:
    placement tags recorded at plan time, wall times measured)."""
    import collections

    from .event_log import read_event_logs
    per_query: List[dict] = []
    blockers = collections.Counter()
    for ev in read_event_logs(path):
        nodes = ev.get("nodes", [])
        if not nodes:
            continue
        n_dev = sum(1 for n in nodes if n["on_device"])
        eligible = n_dev / len(nodes)
        # Amdahl with per-op factors: each node carries equal weight of
        # the query's wall time (event logs carry no per-op CPU times)
        inv = 0.0
        for n in nodes:
            f = _OP_FACTORS.get(n["op"], _DEFAULT_FACTOR) \
                if n["on_device"] else 1.0
            inv += (1.0 / len(nodes)) / f
            for r in n.get("reasons", []):
                blockers[r] += 1
        est = 1.0 / max(inv, 1e-9)
        per_query.append({"fingerprint": ev.get("fingerprint", "?"),
                          "wall_s": ev.get("wall_s", 0.0),
                          "eligible": eligible,
                          "est_speedup": round(est, 2)})
    total_wall = sum(q["wall_s"] for q in per_query)
    if total_wall > 0:
        accel_wall = sum(q["wall_s"] / q["est_speedup"]
                         for q in per_query)
        app_speedup = total_wall / max(accel_wall, 1e-9)
    else:
        app_speedup = 1.0
    return AppQualification(
        queries=len(per_query), total_wall_s=total_wall,
        est_speedup=round(app_speedup, 2), per_query=per_query,
        top_blockers=[r for r, _ in blockers.most_common(8)])


def _main(argv):
    import sys
    if not argv:
        print("usage: python -m spark_rapids_tpu.tools.qualification "
              "<event-log dir>", file=sys.stderr)
        return 2
    print(qualify_event_logs(argv[0]).render())
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(_main(sys.argv[1:]))
