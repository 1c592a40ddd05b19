"""Device results per plan that the planner's per-scenario totals wait
for: the ``totals_pulls`` counter that its ``replan/post/totals`` stage
carries (``core/replan.py``), summed over the traced plans
(``lib/stages``).  1 where each plan reduces its totals in one device
result; nothing to read where the program counts none."""

from lib import stages


def read(record):
    got = stages.of(record, __file__)
    if got is None or "totals_pulls" not in got["counters"]:
        return None
    return got["counters"]["totals_pulls"] / record["plans"]
