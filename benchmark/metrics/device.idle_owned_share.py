"""Device: of the seconds of the traced execution in which no operation
ran on the chip, the share that lies under a WORKING span of the
program (``span_reduce.WORKING``: scan read / assemble / arena wait /
upload / dispatch, admission, download, finish) — idle time that has an
owner. The remainder lies under a span that only contains or waits
(``spark:query``, ``spark:op``, ``spark:scan.wait``) or under none.
``device.idle_owned_share`` reads one warm execution,
``device.idle_owned_share.cold`` the whole cold query, compilation
included (under ``spark:scan.dispatch``)."""
import span_reduce


def read(reading):
    r = span_reduce.spans_of(reading)
    if r is None or r["idle_s"] <= 0:
        return None
    return 100.0 * r["idle_by_working_s"] / r["idle_s"]
