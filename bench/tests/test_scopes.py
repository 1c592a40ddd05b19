"""Device time per named scope (``lib/scopes``) and the three readers of
the forecasting replay: ``forecast_device_s_per_plan``,
``solver_device_s_per_plan`` (device ops under the program's
``jax.named_scope``) and ``forecast_prep_s_per_plan`` (the host stage
``replan/prepare/forecast``)."""

import os

import pytest

from lib import harness, scopes, stages
from lib import trace as tr

METRICS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "metrics")
TPU0, TPU1 = "/device:TPU:0", "/device:TPU:1"
READERS = ("forecast_device_s_per_plan", "solver_device_s_per_plan",
           "forecast_prep_s_per_plan")

#: One module's HLO as a profile keeps it: a scoped fusion, a fusion
#: without an op_name calling a scoped computation, the enclosing loop,
#: and an op in a scope whose name only starts like "forecast".
HLO = """HloModule jit_scan

%fused_exp (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %exp.1 = f32[8]{0} exponential(%param_0), metadata={op_name="jit(scan)/while/body/forecast/exp"}
}

%body (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %fusion.1 = f32[8]{0} fusion(%p), kind=kLoop, calls=%fused_exp
  %dot.2 = f32[8]{0} dot(%fusion.1, %fusion.1), metadata={op_name="jit(scan)/while/body/forecast/dot_general"}
  %sort.3 = f32[8]{0} sort(%dot.2), metadata={op_name="jit(scan)/while/body/solve/sort"}
  ROOT %add.4 = f32[8]{0} add(%sort.3, %p), metadata={op_name="jit(scan)/while/body/forecaster/add"}
}

ENTRY %main (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  ROOT %while.5 = f32[8]{0} while(%x), condition=%cond, body=%body, metadata={op_name="jit(scan)/while"}
}
"""


def test_scoped_instructions_of_a_module():
    assert scopes.scoped_instructions(HLO, "forecast") == {
        "exp.1", "fusion.1", "dot.2"}
    assert scopes.scoped_instructions(HLO, "solve") == {"sort.3"}
    assert scopes.scoped_instructions(HLO, "irls") == set()


def op(name, start, dur, plane=TPU0):
    return {"plane": plane, "name": f"%{name} = f32[8]{{0}} op(%p)",
            "start_ns": start, "dur_ns": dur}


def mod(name, start, dur, plane=TPU0):
    return {"plane": plane, "name": name, "start_ns": start, "dur_ns": dur}


MODULES = [mod("jit_scan(7)", 0, 200), mod("jit_other(9)", 300, 100),
           mod("jit_scan(7)", 0, 200, plane=TPU1)]
TEXTS = {"jit_scan(7)": HLO, "jit_other(9)": HLO.replace("forecast", "x")}
OPS = [
    op("while.5", 0, 200),              # the enclosing loop: no scope
    op("fusion.1", 10, 30),
    op("dot.2", 30, 20),                # overlaps the fusion: counted once
    op("sort.3", 60, 10),
    op("add.4", 80, 10),                # "forecaster": not the scope
    op("dot.2", 190, 30),               # runs past the module, kept
    op("dot.2", 310, 10),               # another module, out of scope
    op("dot.2", 500, 10),               # no module round it
    op("fusion.1", 10, 20, plane=TPU1),
]


def test_ops_in_scope_and_their_seconds():
    got = scopes.in_scope(OPS, MODULES, TEXTS, "forecast")
    assert [(e["plane"], e["start_ns"]) for e in got] == [
        (TPU0, 10), (TPU0, 30), (TPU0, 190), (TPU1, 10)]
    # TPU0 covers 10..50 and 190..200 in the window (50 ns), TPU1 20 ns.
    assert scopes.seconds(got, (0, 200)) == pytest.approx(35e-9)
    solve = scopes.in_scope(OPS, MODULES, TEXTS, "solve")
    assert scopes.seconds(solve, (0, 200)) == pytest.approx(10e-9)
    assert scopes.seconds(scopes.in_scope(OPS, MODULES, {}, "solve"),
                          (0, 200)) is None


def test_hlo_of_a_profile_carries_the_scopes(tmp_path):
    """A CPU profile of a jitted function with two named scopes keeps its
    module's HLO, op names included, under ``/host:metadata``."""
    import glob

    import jax
    import jax.numpy as jnp

    def f(x):
        with jax.named_scope("forecast"):
            y = jnp.sin(x) @ x
        with jax.named_scope("solve"):
            return jnp.sort(y, axis=-1)

    g = jax.jit(f)
    x = jnp.ones((8, 8))
    g(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    g(x).block_until_ready()
    jax.profiler.stop_trace()
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    with open(path, "rb") as fh:
        texts = scopes.hlo_texts(fh.read())
    (text,) = [t for name, t in texts.items() if name.startswith("jit_f(")]
    assert scopes.scoped_instructions(text, "forecast")
    assert scopes.scoped_instructions(text, "solve")
    assert not (scopes.scoped_instructions(text, "forecast")
                & scopes.scoped_instructions(text, "solve"))
    assert scopes.load(str(tmp_path / "none")) == ([], [], {})


@pytest.fixture
def profile(monkeypatch):
    """A two-plan traced run: the ``plan`` annotations, the program's
    forecast stage, and one module's scoped device ops per plan."""
    host_events = [
        {"plane": tr.HOST_PLANE, "line": "python", "name": name,
         "start_ns": start, "dur_ns": dur, "stats": stats}
        for name, start, dur, stats in (
            ("plan", 0, 1000, {}), ("plan", 1000, 1000, {}),
            ("replan/prepare/forecast", 100, 40, {"prefix_bytes": 64}),
            ("replan/prepare/forecast", 1100, 60, {"prefix_bytes": 64}))]
    device = ([op("fusion.1", 210, 300), op("sort.3", 600, 50),
               op("fusion.1", 1210, 100), op("sort.3", 1600, 150)],
              [mod("jit_scan(7)", 200, 700), mod("jit_scan(7)", 1200, 700)],
              {"jit_scan(7)": HLO})
    monkeypatch.setattr(stages, "load_host", lambda log_dir: host_events)
    monkeypatch.setattr(scopes, "load", lambda log_dir: device)
    return {"plans": 2, "trace": {"window_s": 2000e-9}}


def test_readers_on_a_traced_run(profile):
    got = {m: harness.read_metric(METRICS, m, profile) for m in READERS}
    assert got == pytest.approx({
        "forecast_device_s_per_plan": 200e-9,
        "solver_device_s_per_plan": 100e-9,
        "forecast_prep_s_per_plan": 50e-9})


@pytest.mark.parametrize("name", READERS)
def test_readers_find_nothing_to_read(name, profile, monkeypatch,
                                      tmp_path):
    """Another run's profile, a program without the scopes and the stage
    (the parent's), or no profile at all: nothing, and no exception."""
    assert harness.read_metric(METRICS, name, profile) is not None
    other = {"plans": 2, "trace": {"window_s": 3.0}}
    assert harness.read_metric(METRICS, name, other) is None
    plans = [e for e in stages.load_host("") if e["name"] == "plan"]
    ops, modules, _ = scopes.load("")
    bare = HLO.replace("forecast", "f").replace("solve", "s")
    monkeypatch.setattr(stages, "load_host", lambda log_dir: plans)
    monkeypatch.setattr(scopes, "load", lambda log_dir: (
        ops, modules, {"jit_scan(7)": bare}))
    assert harness.read_metric(METRICS, name, profile) is None
    monkeypatch.undo()
    monkeypatch.setattr(stages, "TRACE_DIR", str(tmp_path / "none"))
    assert harness.read_metric(METRICS, name, profile) is None
