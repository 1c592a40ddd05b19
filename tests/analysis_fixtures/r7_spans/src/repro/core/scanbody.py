"""Planted R7 violation: a span stage inside a scan body — it opens once,
while the body is traced, so it times tracing and never a step."""

import jax
import jax.numpy as jnp

from repro.obs import spans as obs_spans


def body(carry, x):
    with obs_spans.stage("replan/step"):  # planted: stage inside a trace
        return carry + x, x


def run(xs):
    with obs_spans.stage("replan/scan"):  # fine: round the host call
        return jax.lax.scan(body, jnp.float32(0.0), xs)
