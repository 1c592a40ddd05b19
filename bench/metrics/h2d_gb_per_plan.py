"""Gigabytes (1e9 bytes) per plan the program placed on the device: the
``h2d_bytes`` counter its stages carry in the trace (``lib/stages``;
``replan/place_rows``: the realized demand and the scenario batch,
float32).  Nothing to read where the program counts none."""

from lib import stages


def read(record):
    return stages.gb_per_plan(record, __file__, "h2d_bytes")
