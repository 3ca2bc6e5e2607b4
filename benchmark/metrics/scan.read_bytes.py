"""Scan: megabytes of encoded column chunks the traced query took from
the files: the sum of ``bytes`` over its ``spark:scan.read`` spans (the
encoded size of every chunk planned for the device, per row group)."""
import span_reduce


def read(reading):
    r = span_reduce.spans_of(reading)
    if r is None or "spark:scan.read" not in r["spans"]:
        return None
    return r["spans"]["spark:scan.read"]["args"].get("bytes", 0) / 1e6
