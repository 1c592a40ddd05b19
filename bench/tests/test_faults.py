"""A run with the timed path broken underneath comes out not correct.

Drives the whole harness on the CPU at a tiny size (the look for a chip
is skipped), once sound and once per fault the planner can have:

* a step that returns its state unchanged: the weekly scan's carry (the
  committed stack and its roll-off schedule) never advances;
* half of the batch left out: the second half of the pools is planned on
  no demand;
* an answer altered where it is produced: the plan's total is off by a
  part in a thousand;
* answers altered in a handful of rows where they are produced: in a
  hedging cell, four rows commit only at twice the break-even spend.

One chip holds each cell, so there is no exchange between chips to leave
out."""

import dataclasses
import json
import os

import jax
import numpy as np
import pytest

from lib import harness
from tests import tiny

with open(os.path.join(tiny.ROOT, "BENCHMARK.json")) as _f:
    _CELLS = json.load(_f)["workloads"]
CELLS = [w["name"] for w in _CELLS]


def _policy(cell):
    with open(os.path.join(tiny.BENCH, "traffic",
                           f"{cell['traffic']}.json")) as f:
        return json.load(f)["request"].get("policy")


HEDGE_CELLS = [w["name"] for w in _CELLS
               if _policy(w) == "deterministic_hedge"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("bench")))


def run(root, workload):
    result, _ = harness.run(tiny.args(workload), root=root,
                            require_accelerator=False)
    return result


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(root, workload):
    result = run(root, workload)
    assert result["correct"] is True
    assert list(result)[-1] == "checks"
    assert result["metrics"]["plan_s"]["value"] > 0


def _frozen_scan(scan):
    def frozen(f, init, xs=None, *a, **k):
        return scan(lambda c, x: (c, f(c, x)[1]), init, xs, *a, **k)
    return frozen


def _half_left_out(plan):
    def planned(req):
        d = np.array(req.pools.demand)
        d[d.shape[0] // 2:] = 0.0
        return plan(dataclasses.replace(
            req, pools=dataclasses.replace(req.pools, demand=d)))
    return planned


def _altered(plan):
    def planned(req):
        rep = plan(req)
        if rep.scenario_cost is not None:
            rep.scenario_cost = rep.scenario_cost * 1.001
        rep.total_cost *= 1.001
        return rep
    return planned


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_altered"])
def test_fault_is_not_correct(root, workload, fault, monkeypatch):
    from repro.core import api

    if fault == "state_unchanged":
        monkeypatch.setattr(jax.lax, "scan", _frozen_scan(jax.lax.scan))
    elif fault == "half_batch":
        monkeypatch.setattr(api, "plan", _half_left_out(api.plan))
    else:
        monkeypatch.setattr(api, "plan", _altered(api.plan))
    result = run(root, workload)
    assert result["correct"] is False
    assert result["failed"] >= 1


def _late_rows(thresholds, rows=4):
    def late(self, num_pools):
        return thresholds(self, num_pools).at[:rows].set(2.0)
    return late


@pytest.mark.parametrize("workload", HEDGE_CELLS)
def test_few_rows_late_is_not_correct(root, workload, monkeypatch):
    from repro.core import policy

    hedge = policy.DeterministicHedgePolicy
    monkeypatch.setattr(hedge, "_thresholds", _late_rows(hedge._thresholds))
    result = run(root, workload)
    assert result["correct"] is False
    assert result["checks"]["row_max"]["value"] > \
        result["checks"]["row_max"]["limit"]
