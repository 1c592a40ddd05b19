"""Host seconds per plan in the program's ``replan/prepare/forecast``
stage (``core/replan.py``): the dispatch of the forecaster's prefix state
(design, prefix Gram, right-hand sides) and the migration share state,
on the trace's clock (``lib/stages``).  Nothing to read where the
program has no such stage."""

from lib import stages


def read(record):
    return stages.seconds_per_plan(record, __file__,
                                   "replan/prepare/forecast")
