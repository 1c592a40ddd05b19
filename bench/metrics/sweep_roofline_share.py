"""Share of the roofline reached by the Pallas commitment sweep: the
launches' least time at the chip's published peaks (``lib/roofline``:
operations and bytes from the sweep's shape, bound by bytes on a v5e)
over the device time of the sweep's ``tpu_custom_call`` operations.  The
sweep's shape per launch is the grid solver's: rows x horizon weeks,
``num_grid`` levels, horizon hours.  Nothing to read where no sweep ran
(the quantile solver)."""

import re

from lib import roofline

OPS = re.compile(r"commitment_sweep|tpu_custom_call")


def read(record):
    tr = record["trace"]
    names = [k for k in tr["ops"] if OPS.search(k)]
    launches = sum(tr["op_counts"][k] for k in names)
    seconds = sum(tr["ops"][k] for k in names)
    if not launches or seconds <= 0:
        return None
    s = record["shape"]
    cost = roofline.sweep_cost(s["rows"] * s["horizon_weeks"],
                               s["num_grid"], s["horizon_hours"])
    value, _ = roofline.share(cost, launches, seconds, record["peaks"])
    return value
