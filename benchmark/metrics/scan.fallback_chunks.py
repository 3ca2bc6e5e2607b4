"""Scan: column chunks per query that left the device decode path for
the host (ExecCtx counter ``fallbackChunks`` summed over the plan)."""


def read(reading):
    counts = [q["scan"]["fallbackChunks"] for q in reading["queries"]
              if "scan" in q]
    return sum(counts) / len(counts) if counts else None
