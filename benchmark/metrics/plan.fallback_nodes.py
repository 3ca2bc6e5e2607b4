"""Planner: operators per query that ``TpuOverrides`` left on the CPU
(the planned tree's ``fallback_nodes()``, plus one for a CPU root)."""


def read(reading):
    counts = [q["fallback_nodes"] for q in reading["queries"]]
    return sum(counts) / len(counts) if counts else None
