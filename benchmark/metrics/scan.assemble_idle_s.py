"""Scan: seconds of the traced query in which the chip ran nothing and the
program was assembling a batch on a scan-upload feeder (merging plans,
building segments, filling the staging arena): the idle seconds
``spark:scan.assemble`` owns, as ``span_reduce.py`` shares them out."""
import span_reduce


def read(reading):
    return span_reduce.idle_owned_s(reading, "spark:scan.assemble")
