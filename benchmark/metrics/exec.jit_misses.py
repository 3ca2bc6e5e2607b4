"""Fused stage / join / aggregate / sort: named programs the traced
query traced anew (its re-jit): the sum of ``miss`` over its
``spark:program`` spans, every thread (a gang's members each count
their own). The engine opens that span around each call of a named
program (``spark_rapids_tpu/programs.py``); ``miss`` is 1 where the call
traced the function. A program from before the span gives no reading."""
import span_reduce

SPAN = "spark:program"


def read(reading):
    r = span_reduce.spans_of(reading)
    if r is None or SPAN not in r["spans"]:
        return None
    return r["spans"][SPAN]["args"].get("miss", 0)
