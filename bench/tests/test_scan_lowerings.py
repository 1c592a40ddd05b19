"""``scan_lowerings_per_plan``: how often a plan traced and lowered the
replay's scan, read off the ``scan_lowerings`` counter of the program's
stages (``lib/stages``)."""

import dataclasses
import os

import pytest

from lib import harness
from lib import stages
from lib import trace as tr

HOST = tr.HOST_PLANE
METRICS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "metrics")
NAME = "scan_lowerings_per_plan"


def ev(name, start, dur, **stats):
    return {"plane": HOST, "line": "python", "name": name,
            "start_ns": start, "dur_ns": dur, "stats": stats}


def _record(events, plans):
    lo, hi = tr.window_of(events, "plan")
    return {"plans": plans, "trace": {"window_s": (hi - lo) / 1e9}}


def test_nothing_to_read_without_a_profile_or_the_counter(monkeypatch,
                                                          tmp_path):
    """No profile, or a program whose stages carry no ``scan_lowerings``
    (as before the replay was kept): nothing to read, and no raise."""
    events = [ev("plan", 0, 100), ev("replan", 1, 98),
              ev("replan/scan", 2, 50),
              ev("replan/pull", 60, 10, d2h_bytes=8)]
    record = _record(events, 1)
    monkeypatch.setattr(stages, "TRACE_DIR", str(tmp_path / "none"))
    assert harness.read_metric(METRICS, NAME, record) is None
    monkeypatch.setattr(stages, "load_host", lambda log_dir: events)
    assert harness.read_metric(METRICS, NAME, record) is None
    counted = events + [ev("replan/scan/lower", 52, 0, scan_lowerings=0)]
    monkeypatch.setattr(stages, "load_host", lambda log_dir: counted)
    assert harness.read_metric(METRICS, NAME, record) == 0.0


def test_counter_sum_over_plans_from_a_cpu_profile(monkeypatch, tmp_path):
    """Two rolling plans of one shape class with baselines, under a CPU
    profile: the first traces and lowers the replay and the one-shot
    baseline, the second reuses both, so the reader gives 2 / 2 plans."""
    import jax

    from repro.core import replan
    from repro.data import traces

    pools = traces.synthetic_pool_set(num_pools=2, num_hours=24 * 7 * 10)
    jax.clear_caches()
    log_dir = tmp_path / "profile"
    jax.profiler.start_trace(str(log_dir))
    try:
        for scale in (1.0, 1.1):
            with jax.profiler.TraceAnnotation("plan"):
                replan.replan_fleet_pools(
                    dataclasses.replace(pools, demand=pools.demand * scale),
                    cadence_weeks=2, start_weeks=4, horizon_weeks=2,
                )
    finally:
        jax.profiler.stop_trace()
    monkeypatch.setattr(stages, "TRACE_DIR", str(log_dir))
    events = stages.load_host(str(log_dir))
    window = tr.window_of(events, "plan")
    assert stages.counters(events, window)["scan_lowerings"] == 2
    assert harness.read_metric(
        METRICS, NAME, _record(events, 2)) == pytest.approx(1.0)
