"""Fused stage / join / aggregate / sort: seconds of the traced query,
averaged over the chips, in which a chip ran nothing while a
``spark:program`` span that MISSED (``miss`` >= 1: the call traced,
lowered and fetched or compiled its program) was open on any thread
(``program_jit.miss_idle_s``: the union of those spans). A program from
before the span gives no reading."""
import mesh_busy
import program_jit
import span_reduce


def read(reading):
    r = span_reduce.spans_of(reading)
    if r is None or program_jit.SPAN not in r["spans"]:
        return None
    return program_jit.miss_idle_s(mesh_busy.of(reading))
