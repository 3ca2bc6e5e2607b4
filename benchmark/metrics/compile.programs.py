"""Compile: compile requests of set-up (hits or compiles): every program
the cell's queries need, each requested once by the warm-up execution.
The same for every seed, or a seed changes a program's shape."""


def read(reading):
    c = reading["compile"]["setup"]
    return float(c["requests"]) if c["requests"] else None
