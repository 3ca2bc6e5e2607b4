"""SQL frontend and planner: median milliseconds per query around
``session.sql(text)`` plus ``TpuOverrides.apply`` (harness clock)."""
import statistics


def read(reading):
    samples = [q["plan_ms"] for q in reading["queries"] if "plan_ms" in q]
    return statistics.median(samples) if samples else None
