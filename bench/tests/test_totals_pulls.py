"""``totals_pulls_per_plan``: how many device results a plan's
per-scenario totals wait for, read off the ``totals_pulls`` counter of the
program's stages (``lib/stages``)."""

import os

import pytest

from lib import harness
from lib import stages
from lib import trace as tr

HOST = tr.HOST_PLANE
METRICS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "metrics")
NAME = "totals_pulls_per_plan"


def ev(name, start, dur, **stats):
    return {"plane": HOST, "line": "python", "name": name,
            "start_ns": start, "dur_ns": dur, "stats": stats}


def _record(events, plans):
    lo, hi = tr.window_of(events, "plan")
    return {"plans": plans, "trace": {"window_s": (hi - lo) / 1e9}}


def test_nothing_to_read_without_a_profile_or_the_counter(monkeypatch,
                                                          tmp_path):
    """No profile, or a program whose totals stage carries no
    ``totals_pulls`` (as before the totals were one reduction): nothing
    to read, and no raise."""
    events = [ev("plan", 0, 100), ev("replan", 1, 98),
              ev("replan/post", 60, 30), ev("replan/post/totals", 70, 10)]
    record = _record(events, 1)
    monkeypatch.setattr(stages, "TRACE_DIR", str(tmp_path / "none"))
    assert harness.read_metric(METRICS, NAME, record) is None
    monkeypatch.setattr(stages, "load_host", lambda log_dir: events)
    assert harness.read_metric(METRICS, NAME, record) is None
    counted = events[:3] + [ev("replan/post/totals", 70, 10,
                               totals_pulls=1)]
    monkeypatch.setattr(stages, "load_host", lambda log_dir: counted)
    assert harness.read_metric(METRICS, NAME, record) == 1.0


def test_one_pull_a_plan_from_a_cpu_profile(monkeypatch, tmp_path):
    """Two scenario plans of different sizes under a CPU profile: each
    waits for one device result, so the reader gives 2 / 2 plans."""
    import jax

    from repro.core import replan
    from repro.data import scenarios as sc
    from repro.data import traces

    pools = traces.synthetic_pool_set(num_pools=2, num_hours=24 * 7 * 10)
    log_dir = tmp_path / "profile"
    jax.profiler.start_trace(str(log_dir))
    try:
        for n in (2, 3):
            with jax.profiler.TraceAnnotation("plan"):
                replan.replan_fleet_pools(
                    pools, cadence_weeks=2, start_weeks=4, horizon_weeks=2,
                    compare=False,
                    scenarios=sc.ScenarioConfig(n_scenarios=n,
                                                family="growth"),
                )
    finally:
        jax.profiler.stop_trace()
    monkeypatch.setattr(stages, "TRACE_DIR", str(log_dir))
    events = stages.load_host(str(log_dir))
    window = tr.window_of(events, "plan")
    assert stages.counters(events, window)["totals_pulls"] == 2
    assert harness.read_metric(
        METRICS, NAME, _record(events, 2)) == pytest.approx(1.0)
