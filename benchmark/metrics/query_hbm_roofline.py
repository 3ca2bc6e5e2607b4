"""Kernels: the least HBM time of the query over the device-busy
seconds of one traced execution.

The least time moves the bytes the query must read and write
(``query_bytes.py``: the decoded columns it names at the source's
widths, plus its result) once, at the chip's peak HBM bandwidth
(``peaks.json``). The bytes follow the configuration and the query
text, never what the program launches; HBM bounds it (a scan-filter-sum
does a few operations per byte). A trace with no device operation gives
no reading."""


def read(reading):
    t = reading.get("trace")
    traced = reading.get("traced_query")
    if not t or not traced or t["busy_s"] <= 0:
        return None
    least_s = traced["least_bytes"] / (reading["peaks"]["hbm_gbs"] * 1e9)
    return 100.0 * least_s / t["busy_s"]
