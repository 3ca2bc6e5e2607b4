"""Mesh: the least over the largest of the chips' busy seconds in the
traced query, in percent (``mesh_busy.per_chip_busy_s``): 100 where every
chip did the same work, 0 where one did none."""
import mesh_busy


def read(reading):
    profile = mesh_busy.of(reading)
    busy = profile and mesh_busy.per_chip_busy_s(profile)
    if not busy or max(busy.values()) <= 0:
        return None
    return 100.0 * min(busy.values()) / max(busy.values())
