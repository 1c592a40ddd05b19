"""The program's own stages in the trace: stage seconds and byte counters
(``lib/stages``), and the six readers that report them."""

import json
import os

import pytest

from lib import harness
from lib import stages
from lib import trace as tr

HOST = tr.HOST_PLANE
METRICS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "metrics")
READERS = ("host_prep_s_per_plan", "h2d_s_per_plan", "pull_s_per_plan",
           "host_post_s_per_plan", "h2d_gb_per_plan", "d2h_gb_per_plan")


def ev(name, start, dur, **stats):
    return {"plane": HOST, "line": "python", "name": name,
            "start_ns": start, "dur_ns": dur, "stats": stats}


def test_program_spans_and_counters_inside_the_window():
    events = [
        ev("replan", 0, 100),
        ev("replan/pull", 10, 20, d2h_bytes=7),
        ev("replan", 200, 100),
        ev("replan/pull", 210, 30, d2h_bytes=5),
        ev("replan/pull", 900, 30, d2h_bytes=1000),   # outside
        ev("scenario_batch", 220, 5, d2h_bytes=99),   # not a stage
        ev("replanner", 230, 5, h2d_bytes=99),        # not a stage
        ev("replan/post", 250, 40, note="text"),
    ]
    window = (0, 500)
    assert stages.spans(events, window) == {
        "replan": [100e-9, 100e-9], "replan/pull": [20e-9, 30e-9],
        "replan/post": [40e-9]}
    assert stages.counters(events, window) == {"d2h_bytes": 12}


def test_the_call_is_a_stage_round_the_replay():
    """The replay's locals are freed after ``replan`` closes, inside the
    program's ``api/plan``."""
    events = [ev("plan", 0, 1000), ev("api/plan", 2, 990),
              ev("replan", 5, 900)]
    assert stages.spans(events, (0, 1000)) == {
        "api/plan": [990e-9], "replan": [900e-9]}


@pytest.fixture(scope="module")
def recorded():
    """The host events of a trace recorded on a TPU v5e: one replan of 2
    pools x 2 growth futures over 12 weeks (start week 4, 16 options,
    baselines on) under one ``plan`` annotation; times from the
    annotation's start."""
    path = os.path.join(os.path.dirname(__file__), "data",
                        "trace_stages.json")
    with open(path) as f:
        return json.load(f)


def _record(events):
    lo, hi = tr.window_of(events, "plan")
    return {"plans": 1, "trace": {"window_s": (hi - lo) / 1e9}}


def test_recorded_stages_cover_the_plan(recorded):
    window = tr.window_of(recorded, "plan")
    got = stages.spans(recorded, window)
    assert sum(got["replan"]) >= 0.95 * (window[1] - window[0]) / 1e9
    assert set(got) == {"replan", "replan/scenarios", "replan/place_rows",
                        "replan/prepare", "replan/scan", "replan/pull",
                        "replan/post", "replan/post/books",
                        "replan/post/totals", "replan/post/report",
                        "replan/post/baselines"}


def test_readers_on_the_recorded_trace(recorded, monkeypatch):
    monkeypatch.setattr(stages, "load_host", lambda log_dir: recorded)
    record = _record(recorded)
    got = {m: harness.read_metric(METRICS, m, record) for m in READERS}
    # (2 realized + 2 x 2 scenario) rows x 12 weeks x 168 h, float32.
    assert got["h2d_gb_per_plan"] == 6 * 12 * 168 * 4 / 1e9
    # The scan's outputs over 8 weeks x 4 rows: three (8, 4, 16) and
    # three (8, 4) float32 arrays and 8 decision flags; the hindsight
    # baseline's pull of the evaluated demand, 4 rows x 8 weeks x 168 h.
    pull = 3 * 8 * 4 * 16 * 4 + 3 * 8 * 4 * 4 + 8
    assert got["d2h_gb_per_plan"] == (pull + 4 * 8 * 168 * 4) / 1e9
    spans = stages.spans(recorded, tr.window_of(recorded, "plan"))
    for m, stage in (("host_prep_s_per_plan", "replan/prepare"),
                     ("h2d_s_per_plan", "replan/place_rows"),
                     ("pull_s_per_plan", "replan/pull"),
                     ("host_post_s_per_plan", "replan/post")):
        assert got[m] == pytest.approx(sum(spans[stage]))
        assert 0 < got[m] < sum(spans["replan"])


@pytest.mark.parametrize("name", READERS)
def test_readers_find_nothing_without_stages(name, recorded, monkeypatch,
                                             tmp_path):
    """No profile, a program that marks no stages, or a profile of
    another run: the readers say there is nothing to read rather than
    raise."""
    monkeypatch.setattr(stages, "TRACE_DIR", str(tmp_path / "none"))
    record = _record(recorded)
    assert harness.read_metric(METRICS, name, record) is None
    bare = [e for e in recorded if not stages.PROGRAM.match(e["name"])]
    monkeypatch.setattr(stages, "load_host", lambda log_dir: bare)
    assert harness.read_metric(METRICS, name, record) is None
    monkeypatch.setattr(stages, "load_host", lambda log_dir: recorded)
    assert harness.read_metric(METRICS, name, record) is not None
    other = {"plans": 1, "trace": {"window_s": 2.0}}
    assert harness.read_metric(METRICS, name, other) is None


def test_host_events_keep_their_stats(tmp_path):
    """A stage's counts reach the profile as its event's stats."""
    import jax

    log_dir = str(tmp_path / "profile")
    jax.profiler.start_trace(log_dir)
    with jax.profiler.TraceAnnotation("replan/place_rows", h2d_bytes=123):
        jax.numpy.zeros(4).block_until_ready()
    jax.profiler.stop_trace()
    got = [e for e in stages.load_host(log_dir)
           if e["name"] == "replan/place_rows"]
    assert [e["stats"] for e in got] == [{"h2d_bytes": 123}]
    assert stages.load_host(str(tmp_path / "none")) == []


def test_traced_run_reports_the_stage_metrics(tmp_path):
    """A whole traced run (on the CPU, at the tiny size) reports the six
    metrics from the profile the harness leaves under the checkout."""
    from tests import tiny

    root = tiny.make_root(str(tmp_path))
    result, _ = harness.run(
        tiny.args("paper_estate.hedge_growth", trace=1), root=root,
        require_accelerator=False)
    got = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(READERS) <= set(got)
    # 12 pools x (1 realized + 2 futures) x 40 weeks x 168 h, float32.
    assert got["h2d_gb_per_plan"] == 12 * 3 * 40 * 168 * 4 / 1e9
    assert got["d2h_gb_per_plan"] > 0
