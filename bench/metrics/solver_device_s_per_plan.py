"""Device seconds per plan in the forecasting policy's weekly stack, spot
and convertible solve: the ops under ``jax.named_scope("solve")`` in the
scan body (``core/policy.py``, ``core/replan.py``), on the trace
(``lib/scopes``).  Both scans of a plan count: the rolling replay's and,
with ``compare`` on, the one-shot baseline's, which runs the same scan
body.  Nothing to read where the program's ops carry no such scope."""

from lib import scopes


def read(record):
    return scopes.seconds_per_plan(record, __file__, "solve")
