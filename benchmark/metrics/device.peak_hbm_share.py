"""Device: peak bytes in use on the fullest chip over its limit."""


def read(reading):
    d = reading["device"]
    if not d.get("memory_peak_bytes") or not d.get("bytes_limit"):
        return None
    return 100.0 * d["memory_peak_bytes"] / d["bytes_limit"]
