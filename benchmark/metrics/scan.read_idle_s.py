"""Scan: seconds of the traced query in which the chip ran nothing and the
program was reading a row group on a scan-plan pool thread (open, pread,
decompress, page walk): the idle seconds ``spark:scan.read`` owns, as
``span_reduce.py`` shares them out."""
import span_reduce


def read(reading):
    return span_reduce.idle_owned_s(reading, "spark:scan.read")
