"""Host seconds per plan in the program's ``replan/post`` stage
(``core/replan.py``): tranche books, per-scenario totals, the report and
its telemetry layers, and the baselines where the request asks for them,
on the trace's clock (``lib/stages``).  Nothing to read where the
program has no such stage."""

from lib import stages


def read(record):
    return stages.seconds_per_plan(record, __file__, "replan/post")
