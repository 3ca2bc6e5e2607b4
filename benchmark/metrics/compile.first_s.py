"""Compile: what set-up's compile requests cost from an empty cache:
their seconds plus what their persistent-cache hits saved. The saved
seconds were stored when the cache entry was written, so this is ONE
sample per checkout: for the record, never a gate."""


def read(reading):
    c = reading["compile"]["setup"]
    return c["seconds"] + c["saved"] if c["requests"] else None
