"""The trace reduction: busy union, idle share, module grouping, gap
labels, and the compile record."""

import json
import os

import pytest

from lib import trace as tr

DEV = "/device:TPU:0"
HOST = tr.HOST_PLANE


def ev(plane, line, name, start, dur):
    return {"plane": plane, "line": line, "name": name, "start_ns": start,
            "dur_ns": dur}


def test_union_grouping_and_gaps():
    events = [
        ev(HOST, "python", "plan", 0, 1000),
        ev(HOST, "python", "scenario_batch", 100, 300),
        ev(DEV, tr.MODULES_LINE, "jit_scan(7)", 0, 100),
        ev(DEV, tr.MODULES_LINE, "jit_scan(9)", 500, 200),
        ev(DEV, tr.MODULES_LINE, "jit_optimal_portfolio_stack(3)", 800, 100),
        ev(DEV, tr.OPS_LINE, "fusion.1", 0, 60),
        ev(DEV, tr.OPS_LINE, "fusion.2", 40, 60),     # overlaps fusion.1
        ev(DEV, tr.OPS_LINE, "sort.3", 500, 200),
        ev(DEV, tr.OPS_LINE, "sort.4", 800, 100),
        ev(DEV, tr.OPS_LINE, "late", 990, 50),         # clipped to 10 ns
    ]
    red = tr.reduce(events, (0, 1000))
    assert red["window_s"] == pytest.approx(1e-6)
    assert red["busy_s"] == pytest.approx((100 + 200 + 100 + 10) / 1e9)
    assert red["modules"] == pytest.approx(
        {"jit_scan": 300e-9, "jit_optimal_portfolio_stack": 100e-9})
    assert red["op_counts"]["fusion.1"] == 1
    gaps = red["breakdown"]["idle_gaps"]
    # Longest gap first: 100..500, its middle inside scenario_batch.
    assert gaps[0] == ["scenario_batch", pytest.approx(400e-9)]
    assert [g[1] for g in gaps] == sorted((g[1] for g in gaps), reverse=True)
    assert gaps[1][0] == "plan"


def test_window_and_spans():
    events = [ev(HOST, "t", "plan", 10, 90), ev(HOST, "t", "plan", 200, 50),
              ev(HOST, "t", "scenario_batch", 20, 30),
              ev(HOST, "t", "scenario_batch", 400, 30)]
    span = tr.window_of(events, "plan")
    assert span == (10, 250)
    assert tr.host_spans(events, "scenario_batch", span) == [30e-9]
    assert tr.window_of(events, "missing") is None


def test_recorded_trace():
    """A trace recorded on a TPU v5e: three jitted 512x512 matmuls under
    one ``plan`` annotation."""
    path = os.path.join(os.path.dirname(__file__), "data", "trace_small.json")
    with open(path) as f:
        events = json.load(f)
    span = tr.window_of(events, "plan")
    red = tr.reduce(events, span)
    assert red["devices"] == 1
    assert 0 < red["busy_s"] < red["window_s"]
    ops = [e for e in events if e["line"] == tr.OPS_LINE
           and span[0] <= e["start_ns"] < span[1]]
    assert sum(red["op_counts"].values()) == len(ops)
    mods = [m for m in red["modules"] if m.startswith("jit_")]
    assert mods and all("(" not in m for m in red["modules"])
    # The busy union never exceeds the sum of op durations.
    assert red["busy_s"] <= sum(red["ops"].values()) + 1e-12


def test_compile_watch_counts():
    import jax
    import jax.numpy as jnp

    watch = tr.CompileWatch()
    import time

    lo = time.time()
    jax.jit(lambda x: jnp.sin(x) * 3.0 + 1.25)(jnp.arange(7.0)).block_until_ready()
    rec = watch.summary(lo, time.time())
    assert rec["backend_compiles"] >= 1
    assert rec["union_s"] > 0
    assert rec["union_s"] <= (rec["jaxpr_trace_duration"]
                              + rec["jaxpr_to_mlir_module_duration"]
                              + rec["backend_compile_duration"]) + 1e-9
    empty = watch.summary(time.time() + 10, time.time() + 20)
    assert empty["backend_compiles"] == 0 and empty["union_s"] == 0
