"""Sort and top-N: device-busy seconds of the traced query in the sort's
programs (XLA modules ``jit_sort_*``: one batch sorted and truncated, a
round of the out-of-core merge). Read from the run's own trace by
``module_busy.py``."""
import module_busy


def read(reading):
    return module_busy.family_busy_s(reading, "jit_sort_")
