"""The plain reference against the program on the CPU, at the tiny size,
for every traffic mix in ``bench/traffic`` (the forecasting mixes kept for
later cells included).  On the CPU the program's float32 replay sits
~1e-4 of a pool's mean demand from the float64 reference on targets and
~1e-6 on totals; the tolerances here are ten to a hundred times that."""

import json
import os

import numpy as np
import pytest

from lib import compare, data, harness, reference
from tests import tiny

MIXES = sorted(n[:-5] for n in os.listdir(os.path.join(tiny.BENCH,
                                                       "traffic")))
CONFIGS = sorted(n[:-5] for n in os.listdir(os.path.join(tiny.BENCH,
                                                         "configs")))


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("bench")))


@pytest.mark.parametrize("config", CONFIGS)
@pytest.mark.parametrize("mix", MIXES)
def test_program_matches_reference_on_cpu(root, config, mix):
    from repro.core import api
    from repro.core.demand import PoolSet

    bench = os.path.join(root, "bench")
    with open(os.path.join(bench, "configs", f"{config}.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(bench, "traffic", f"{mix}.json")) as f:
        traffic = json.load(f)
    seed = 2**31 + 3
    keys, base = data.fleet(cfg, seed)
    demand = base * data.plan_scales(seed, 1, len(keys), 0.1)[:, None]
    pools = PoolSet(keys=keys, demand=demand)
    s_seed = data.scenario_seed(seed, 1)
    preq = harness.request_for(cfg, traffic, pools, s_seed)
    rep = api.plan(preq)
    ref = reference.plan(cfg, harness.reference_request(preq, traffic),
                         keys, demand)
    names = ["rolling", "total", "row_p90"]
    if not traffic["request"].get("policy"):
        names += ["targets", "purchases", "one_shot", "hindsight"]
    if (traffic["request"]["telemetry"] or {}).get("ledger"):
        names.append("ledger")
    gaps = compare.gaps(compare.answer(rep), ref, names)
    for k, v in gaps.items():
        assert v <= (1e-2 if k in ("targets", "purchases") else 1e-4), (k, v)


def _hedge_week(ratio):
    """A one-row hedge with two open bands whose accrued spend stands at
    ``ratio`` (two values) of their price; no spend this week."""
    hg = reference.Hedge.__new__(reference.Hedge)
    hg.dg = np.asarray([1.0])
    hg.levels = hg.dg[:, None] * np.arange(hg.GRID)[None]
    hg.price = np.asarray([100.0])
    hg.kstar = np.asarray([0])
    hg.k_n, hg.od = 1, 1.0
    accrued = np.zeros((1, hg.GRID))
    accrued[0, :2] = 100.0 * np.asarray(ratio)
    active = np.zeros((1, 1))
    d_prev = np.zeros((1, 4))
    return hg, accrued, active, d_prev


@pytest.mark.parametrize("ratio,want,bands", [
    ((1.0 - 1e-7, 0.5), 1, 1),       # a tie below the price: followed
    ((1.0 + 1e-7, 0.5), 0, 0),       # a tie above the price: followed
    ((1.0 + 1e-7, 1.0 + 1e-7), 1, 1),
    ((1.0 - 1e-3, 0.5), 1, 0),       # no tie: the rule stands
    ((1.0 + 1e-3, 0.5), 0, 1),
    ((1.0 - 1e-7, 0.5), 2, 0),       # only one band is a tie: the rule
])
def test_hedge_follows_the_program_on_ties_only(ratio, want, bands):
    hg, accrued, active, d_prev = _hedge_week(ratio)
    ties = []
    _, targets = hg.decide(accrued, active, d_prev, np.asarray([want]), ties)
    assert targets[0, 0] == bands
    assert len(ties) == (bands != int(ratio[0] >= 1.0) + int(ratio[1] >= 1.0))
