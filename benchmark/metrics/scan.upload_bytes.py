"""Scan: megabytes the traced query handed to the device: the sum of
``bytes`` over its ``spark:scan.upload`` spans, which the engine writes
where the transfer is issued (the ``nbytes`` of the staged blob given to
``jax.device_put``; an Arrow column uploaded beside it counts its Arrow
bytes)."""
import span_reduce


def read(reading):
    r = span_reduce.spans_of(reading)
    if r is None or "spark:scan.upload" not in r["spans"]:
        return None
    return r["spans"]["spark:scan.upload"]["args"].get("bytes", 0) / 1e6
