"""The replay's compiled program is kept between requests.

``replan_fleet_pools`` runs its scan through one jitted program whose
static key is the request's settings (``_ReplaySpec``) and whose
arguments are every array the step reads.  Each call marks how many
times it traced and lowered that program with the ``scan_lowerings``
count of a ``.../lower`` stage.  These tests hold the program to three
things: a request of a known shape class with other demand lowers
nothing; what it answers is what a fresh program answers (no request's
data is baked into the kept program); and a change of what the step's
structure depends on lowers a new program.
"""

from __future__ import annotations

import dataclasses

import jax
import numpy as np
import pytest

from repro.core import policy as pol
from repro.core import replan
from repro.data import scenarios as sc
from repro.data import traces
from repro.obs import SpanRecorder, recording

WEEKS, START = 12, 4
BASE = dict(cadence_weeks=1, start_weeks=START, horizon_weeks=4,
            compare=False)


@pytest.fixture(scope="module")
def pools():
    return traces.synthetic_pool_set(num_pools=2,
                                     num_hours=24 * 7 * WEEKS)


def scaled(pools, seed: int):
    """The fleet with each pool's demand scaled by a seeded factor: the
    same shapes, other data."""
    f = np.random.default_rng(seed).lognormal(0.0, 0.2, pools.num_pools)
    return dataclasses.replace(
        pools, demand=pools.demand * f[:, None].astype(np.float32))


def lowerings(pools, **kw):
    """The report and the ``scan_lowerings`` of each stage that counts."""
    rec = SpanRecorder()
    with recording(rec):
        rep = replan.replan_fleet_pools(pools, **{**BASE, **kw})
    return rep, {sp.name: sp.counts["scan_lowerings"] for sp in rec.spans
                 if "scan_lowerings" in sp.counts}


def assert_reports_equal(a, b):
    """Every numeric field of two reports, bit for bit."""
    for f in dataclasses.fields(a):
        x, y = getattr(a, f.name), getattr(b, f.name)
        if isinstance(x, (np.ndarray, float, int)) and not isinstance(
                x, bool):
            assert np.array_equal(np.asarray(x), np.asarray(y)), f.name


CASES = {
    "plain": {},
    "bands": dict(spot=True, convertible=True, telemetry=True),
    "hedge": dict(policy="deterministic_hedge",
                  scenarios=sc.ScenarioConfig(n_scenarios=2,
                                              family="growth")),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_other_demand_reuses_the_program_and_answers_as_a_fresh_one(
        pools, case):
    """Two requests of one shape class with different demand lower the
    scan once; the second answers bit for bit what it answers first
    thing in a fresh program cache."""
    kw = CASES[case]
    jax.clear_caches()
    _, first = lowerings(scaled(pools, 1), **kw)
    second, again = lowerings(scaled(pools, 2), **kw)
    assert first == {"replan/scan/lower": 1}
    assert again == {"replan/scan/lower": 0}
    jax.clear_caches()
    fresh, lowered = lowerings(scaled(pools, 2), **kw)
    assert lowered == {"replan/scan/lower": 1}
    assert_reports_equal(second, fresh)


VARIANTS = {
    "cadence": (dict(cadence_weeks=1), dict(cadence_weeks=2)),
    "band": (dict(), dict(spot=True)),
    "telemetry": (dict(), dict(telemetry=True)),
    "hedge_grid_size": (
        dict(policy=pol.DeterministicHedgePolicy(grid_size=8)),
        dict(policy=pol.DeterministicHedgePolicy(grid_size=16)),
    ),
}


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_a_change_of_structure_lowers_a_new_program(pools, variant):
    """A change of cadence, of a band or of a policy parameter changes the
    step, so it lowers a program of its own, which is kept in turn."""
    base, changed = VARIANTS[variant]
    jax.clear_caches()
    assert lowerings(pools, **base)[1] == {"replan/scan/lower": 1}
    assert lowerings(scaled(pools, 3), **base)[1] == {
        "replan/scan/lower": 0}
    assert lowerings(pools, **changed)[1] == {"replan/scan/lower": 1}
    assert lowerings(scaled(pools, 4), **changed)[1] == {
        "replan/scan/lower": 0}


def test_the_one_shot_baseline_is_lowered_once(pools):
    """``compare=True`` runs the one-shot baseline through the same kept
    program, under a spec of its own (cadence 0, the rolling policy)."""
    jax.clear_caches()
    _, first = lowerings(scaled(pools, 5), compare=True)
    rep, second = lowerings(scaled(pools, 6), compare=True)
    assert first == {"replan/scan/lower": 1,
                     "replan/post/baselines/lower": 1}
    assert second == {"replan/scan/lower": 0,
                      "replan/post/baselines/lower": 0}
    assert rep.one_shot_cost is not None


def test_equal_chunks_share_one_program(pools):
    """A scenario batch split into equal chunks lowers one program for
    all of them."""
    jax.clear_caches()
    rec = SpanRecorder()
    with recording(rec):
        replan.replan_fleet_pools(
            pools, **BASE,
            scenarios=sc.ScenarioConfig(n_scenarios=4, family="growth",
                                        chunk=2),
        )
    assert [sp.counts["scan_lowerings"] for sp in rec.spans
            if sp.name == "replan/scan/lower"] == [1, 0]


@pytest.mark.parametrize("name", sorted(pol.POLICIES))
def test_registry_policies_compare_by_value(name):
    """``get_policy(name)`` twice gives equal, equal-hashing policies, so
    both hit one kept program; policies of other kinds or parameters
    differ."""
    a, b = pol.get_policy(name), pol.get_policy(name)
    assert a is not b
    assert a == b and hash(a) == hash(b)
    others = [pol.get_policy(n) for n in pol.POLICIES if n != name]
    assert all(a != o for o in others)
    if isinstance(a, pol.DeterministicHedgePolicy):
        assert a != dataclasses.replace(a, grid_size=a.grid_size + 1)
        assert a != dataclasses.replace(
            a, top_multiplier=a.top_multiplier * 2)
