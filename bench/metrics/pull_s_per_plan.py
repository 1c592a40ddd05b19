"""Host seconds per plan in the program's ``replan/pull`` stage
(``core/replan.py``): every output of the weekly scan copied to the
host, which waits for the device first, on the trace's clock
(``lib/stages``).  Nothing to read where the program has no such
stage."""

from lib import stages


def read(record):
    return stages.seconds_per_plan(record, __file__, "replan/pull")
