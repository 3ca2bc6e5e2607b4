"""Aggregation: device-busy seconds of the traced query in the
aggregate's own programs (XLA modules ``jit_agg_*``: the final
aggregation, a merge of partials, an unfused partial). The per-batch
partial phase that is fused into the stage below it runs inside
``jit_fused_stage`` / ``jit_scan_decode_chain`` and is not counted here.
Read from the run's own trace by ``module_busy.py``."""
import module_busy


def read(reading):
    return module_busy.family_busy_s(reading, "jit_agg_")
