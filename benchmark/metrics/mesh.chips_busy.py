"""Mesh: the chips that ran at least one operation inside the traced
query (device planes with busy time in the window, ``mesh_busy.py``). A
gang of one task per chip reads the cell's ``chips``; one task that
consumes every landed partition on one device would read fewer through
the scan and the joins."""
import mesh_busy


def read(reading):
    profile = mesh_busy.of(reading)
    busy = profile and mesh_busy.per_chip_busy_s(profile)
    if not busy:
        return None
    return float(sum(1 for v in busy.values() if v > 0))
