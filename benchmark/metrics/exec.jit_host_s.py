"""Fused stage / join / aggregate / sort: host seconds the traced
query's re-jit took, summed over threads: ``trace_s + lower_s +
compile_s`` over its ``spark:program`` spans (jax's jaxpr trace, MLIR
lowering and backend compile request, the persistent-cache fetch
included, as ``jax.monitoring`` times them on the calling thread). A
program from before the span gives no reading."""
import span_reduce

SPAN = "spark:program"
PHASES = ("trace_s", "lower_s", "compile_s")


def read(reading):
    r = span_reduce.spans_of(reading)
    if r is None or SPAN not in r["spans"]:
        return None
    args = r["spans"][SPAN]["args"]
    return sum(args.get(k, 0.0) for k in PHASES)
