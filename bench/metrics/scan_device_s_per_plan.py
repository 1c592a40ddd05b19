"""Device seconds per plan in the weekly scan: the compiled modules of
the eager ``jax.lax.scan`` calls in ``core/replan.py`` (the rolling and
the one-shot replay), matched by module name."""

import re

MODULES = re.compile(r"^(jit_)?scan$")


def read(record):
    secs = [v for k, v in record["trace"]["modules"].items()
            if MODULES.match(k)]
    if not secs or not record["plans"]:
        return None
    return sum(secs) / record["plans"]
