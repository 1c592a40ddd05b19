"""Gigabytes (1e9 bytes) per plan the program copied back from the
device: the ``d2h_bytes`` counter its stages carry in the trace
(``lib/stages``; ``replan/pull``: every scan output;
``replan/post/baselines``: the evaluated demand the hindsight baseline
pulls).  Nothing to read where the program counts none."""

from lib import stages


def read(record):
    return stages.gb_per_plan(record, __file__, "d2h_bytes")
