"""Fused stage / join / aggregate / sort: backend-compile requests inside
the window over the queries it completed (``jax.monitoring``). Every
``collect()`` plans anew and re-jits its programs; warm, each request is
a persistent-cache hit."""


def read(reading):
    n = len(reading["queries"])
    return reading["compile"]["window"]["requests"] / n if n else None
