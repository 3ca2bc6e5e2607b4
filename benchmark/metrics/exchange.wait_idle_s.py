"""Exchange: seconds of the traced query, averaged over the chips, in
which a chip ran nothing while the exchange held it: under a
``spark:exchange.ici`` span (the collective epoch: assembly, launch, the
epoch's one readback) or a ``spark:exchange.wait`` span (a member waiting
for the others at the epoch), as ``mesh_busy.idle_under`` takes them."""
import mesh_busy
import span_reduce

SPANS = ("spark:exchange.ici", "spark:exchange.wait")


def read(reading):
    r = span_reduce.spans_of(reading)
    if r is None or not any(n in r["spans"] for n in SPANS):
        return None
    return mesh_busy.idle_under(mesh_busy.of(reading), SPANS)
