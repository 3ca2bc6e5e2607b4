"""Compile: backend-compile seconds inside the window, on this run's
clock (``jax.monitoring``): the cold query's share spent in the chip's
compiler."""


def read(reading):
    c = reading["compile"]["window"]
    return c["seconds"] if c["requests"] else None
