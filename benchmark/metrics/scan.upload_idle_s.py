"""Scan: seconds of the traced query in which the chip ran nothing and the
program was handing the staged words to the device (jax.device_put) on a
scan-upload feeder: the idle seconds ``spark:scan.upload`` owns, as
``span_reduce.py`` shares them out."""
import span_reduce


def read(reading):
    return span_reduce.idle_owned_s(reading, "spark:scan.upload")
