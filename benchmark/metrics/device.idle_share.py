"""Device: share of the traced execution's span in which no operation
ran on the chip (1 - union of device-op intervals over the traced
window). ``device.idle_share`` reads it over ONE warm execution;
``device.idle_share.cold`` over the whole cold query, where it is the
chip waiting for the host's compiler."""


def read(reading):
    t = reading.get("trace")
    if not t:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
