"""Kernels: the all-to-all's share of its roofline. The least time is
the bytes a chip must send to the others (``ici_bytes.py``: its partial
aggregates' rows before the limit, at the source's widths, less the share
that stays) at one chip's inter-chip peak (``ici_peaks.json``), the mean
over the chips; the time is ``exchange.collective_busy_s``, the program's
device-busy seconds averaged over the chips the same way. The
interconnect bounds it (no arithmetic in a shuffle). The bytes follow the
files and the query text, never what the program launches, so a share
over 100 % cannot come from padding. No device plane, no such module or
no data directory: no reading."""
import json
import os

import ici_bytes
import module_busy
import span_reduce

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = "tpcds-sf1-store-4chip"
QUERY = "tpcds/q3"
MODULE = "jit_exchange_all_to_all"  # as exchange.collective_busy_s reads it


def _load(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def read(reading):
    busy_s = module_busy.family_busy_s(reading, MODULE)
    peaks = _load("ici_peaks.json").get(reading["device"].get("kind"))
    if not busy_s or not peaks:
        return None
    config = _load("configs", CONFIG + ".json")
    paths = ici_bytes.newest_tables(
        os.path.dirname(span_reduce.TRACE_ROOT), config)
    if paths is None:
        return None
    schemas = {t: _load("schemas", s["schema"] + ".json")
               for t, s in config["tables"].items()}
    least = ici_bytes.least_bytes_per_chip(QUERY, schemas, paths,
                                           reading["device"]["count"])
    return 100.0 * least / (peaks["ici_gbs"] * 1e9) / busy_s
