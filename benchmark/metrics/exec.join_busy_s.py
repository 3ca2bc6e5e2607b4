"""Joins: device-busy seconds of the traced query in the join's programs
(XLA modules ``jit_join_*``: build analysis, build probe, the
unique-build probe per stream batch, and the staged count / gather;
``spark_rapids_tpu/programs.py``). Read from the run's own trace by
``module_busy.py``."""
import module_busy


def read(reading):
    return module_busy.family_busy_s(reading, "jit_join_")
