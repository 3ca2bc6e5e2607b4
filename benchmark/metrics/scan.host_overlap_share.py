"""Scan: of the seconds during which any thread was in one of the scan's
host stages (the union over threads of ``spark:scan.read`` / ``assemble``
/ ``upload`` / ``dispatch``), the share during which the chip was busy:
how much of the host's scan work hides under device work."""
import span_reduce


def read(reading):
    r = span_reduce.spans_of(reading)
    if r is None or r["scan_host"]["union_s"] <= 0:
        return None
    return 100.0 * r["scan_host"]["under_busy_s"] / r["scan_host"]["union_s"]
