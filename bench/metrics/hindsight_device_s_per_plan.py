"""Device seconds per plan in the hindsight baseline's exact stack solve
(``portfolio.optimal_portfolio_stack``, vmapped over rows in
``core/replan.py``), matched by module name."""

import re

MODULES = re.compile(r"^jit_optimal_portfolio_stack$")


def read(record):
    secs = [v for k, v in record["trace"]["modules"].items()
            if MODULES.match(k)]
    if not secs or not record["plans"]:
        return None
    return sum(secs) / record["plans"]
