"""Compile: compile requests inside the window: the programs a query
shape seen for the first time needs."""


def read(reading):
    return float(reading["compile"]["window"]["requests"])
