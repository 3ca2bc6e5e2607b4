"""Exchange: megabytes the traced query handed to the all-to-all: the sum
of ``bytes`` over its ``spark:exchange.ici`` spans, which the engine
writes around each collective epoch (the ``nbytes`` of every array given
to the program: lanes at the epoch's padded capacity, not live rows)."""
import span_reduce


def read(reading):
    r = span_reduce.spans_of(reading)
    if r is None or "spark:exchange.ici" not in r["spans"]:
        return None
    return r["spans"]["spark:exchange.ici"]["args"].get("bytes", 0) / 1e6
