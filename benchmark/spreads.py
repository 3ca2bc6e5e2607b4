"""Spreads of the warm window's candidate statistics over kept sets of runs.

    python3 benchmark/spreads.py benchmark/readings/pr34_after.jsonl [...]

Each line of a readings file is one run: ``cell``, ``tag`` (the set, A or
B), ``seed``, ``window_s``, ``walls`` (the run's stderr) and ``line`` (its
result line). Priming and traced runs (any other tag) are skipped. For each
cell and candidate statistic it prints, per set, the median over the set's
runs and the spread as a check reads it: the distance between the first and
third quartile (``statistics.quantiles(values, n=4)``) over the median, once
with all runs and once without the run farthest from the median where that
narrows it. PERF.md section 2 says which candidates became metrics, and how
the bounds follow from these numbers. No JAX, no chip: arithmetic on a file.
"""
import json
import statistics
import sys
from collections import defaultdict

from run import nearest_rank

LATE_START_S = 10.0  # "the walls that start after the first 10 s"


def late(walls):
    """The walls of the queries that START after ``LATE_START_S`` of a
    closed-loop window (a query starts when the one before it ends)."""
    start, out = 0.0, []
    for w in walls:
        if start >= LATE_START_S:
            out.append(w)
        start += w
    return out


def _of(statistic, values):
    return statistic(values) if values else None


CANDIDATES = {
    "window_s/n": lambda walls, window_s: window_s / len(walls),
    "median": lambda walls, window_s: statistics.median(walls),
    "mean.late": lambda walls, window_s: _of(statistics.fmean, late(walls)),
    "median.late": lambda walls, window_s: _of(statistics.median,
                                               late(walls)),
    "max": lambda walls, window_s: max(walls),
    "p95": lambda walls, window_s: nearest_rank(walls, 95),
    "p90": lambda walls, window_s: nearest_rank(walls, 90),
}


def spread(values):
    """Interquartile distance over the median."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def trimmed_spread(values):
    """The spread without the run farthest from the median, where that
    narrows it."""
    mid = statistics.median(values)
    far = max(range(len(values)), key=lambda i: abs(values[i] - mid))
    rest = values[:far] + values[far + 1:]
    return min(spread(values), spread(rest)) if len(rest) >= 2 \
        else spread(values)


def sets_of(runs):
    """``{cell: {set: [run, ...]}}`` of the runs that belong to a set."""
    by = defaultdict(lambda: defaultdict(list))
    for r in runs:
        if r.get("tag") in ("A", "B") and r.get("walls"):
            by[r["cell"]][r["tag"]].append(r)
    return by


def table(runs):
    """``{cell: {candidate: {set: (median, spread, trimmed, values)}}}``."""
    out = {}
    for cell, sets in sets_of(runs).items():
        out[cell] = {}
        for name, fn in CANDIDATES.items():
            out[cell][name] = {}
            for tag, rs in sorted(sets.items()):
                vals = [fn(r["walls"], r["window_s"]) for r in rs]
                if None in vals:  # a window too short for this candidate
                    continue
                out[cell][name][tag] = (statistics.median(vals), spread(vals),
                                        trimmed_spread(vals), vals)
    return out


def admits(sets):
    """The bounds a check of these two sets would admit, in percent: at
    least twice the mean of their trimmed spreads (or its runs spread by
    more than half the bound), at most eight times the wider spread."""
    return (200 * statistics.fmean(tr for _, _, tr, _ in sets.values()),
            800 * max(sp for _, sp, _, _ in sets.values()))


def main(argv):
    for path in argv:
        with open(path) as f:
            runs = [json.loads(ln) for ln in f if ln.strip()]
        by = sets_of(runs)
        for cell, cands in table(runs).items():
            counts = sorted(len(r["walls"]) for rs in by[cell].values()
                            for r in rs)
            print(f"\n{path}: {cell}, {len(counts)} runs of "
                  f"{counts[0]}-{counts[-1]} queries\n")
            print("| statistic | median s, set A / B (B over A) "
                  "| spread % A / B | trimmed % A / B | a check admits % |")
            print("|---|---|---|---|---|")
            for name, sets in cands.items():
                if sorted(sets) != ["A", "B"]:
                    continue
                (ma, sa, ta, _), (mb, sb, tb, _) = sets["A"], sets["B"]
                lo, hi = admits(sets)
                print(f"| {name} | {ma:.4f} / {mb:.4f} "
                      f"({100 * (mb / ma - 1):+.2f} %) "
                      f"| {100 * sa:.2f} / {100 * sb:.2f} "
                      f"| {100 * ta:.2f} / {100 * tb:.2f} "
                      f"| {lo:.1f}-{hi:.0f} |")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
