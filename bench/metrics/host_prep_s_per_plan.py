"""Host seconds per plan in the program's ``replan/prepare`` stage
(``core/replan.py``): the eager set-up dispatches ahead of the scan
(option lines, handover fractiles, band set-ups, the forecaster's prefix
state), on the trace's clock (``lib/stages``).  Nothing to read where
the program has no such stage."""

from lib import stages


def read(record):
    return stages.seconds_per_plan(record, __file__, "replan/prepare")
