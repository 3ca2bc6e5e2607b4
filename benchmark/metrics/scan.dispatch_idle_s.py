"""Scan: seconds of the traced query in which the chip ran nothing and the
program was calling the jitted decode program on a scan-upload feeder
(on a cold run its compilation is inside the call): the idle seconds
``spark:scan.dispatch`` owns, as ``span_reduce.py`` shares them out."""
import span_reduce


def read(reading):
    return span_reduce.idle_owned_s(reading, "spark:scan.dispatch")
