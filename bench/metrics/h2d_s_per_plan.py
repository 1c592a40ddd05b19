"""Host seconds per plan in the program's ``replan/place_rows`` stage
(``core/replan.py``): the realized demand and the flattened scenario
batch made float32, contiguous and placed on the device, on the trace's
clock (``lib/stages``).  Nothing to read where the program has no such
stage."""

from lib import stages


def read(record):
    return stages.seconds_per_plan(record, __file__, "replan/place_rows")
