"""Times per plan the program traced and lowered its replay's scan: the
``scan_lowerings`` counter that its ``replan/scan/lower`` and
``replan/post/baselines/lower`` stages carry (``core/replan.py``; the
rolling replay and the one-shot baseline), summed over the traced plans
(``lib/stages``).  0 where every traced plan reused the compiled replay;
nothing to read where the program counts none."""

from lib import stages


def read(record):
    got = stages.of(record, __file__)
    if got is None or "scan_lowerings" not in got["counters"]:
        return None
    return got["counters"]["scan_lowerings"] / record["plans"]
