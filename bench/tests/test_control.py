"""The control, the plain reference one precision step below what the
cell's configuration states, comes out not correct under the cell's
limits.

On the chip the control was read at each cell's own size on three seeds
or more (``PERF.md``); here it runs at the tiny size of the other tests,
where its gaps are of the same order."""

import json
import os

import pytest

from lib import compare, data, harness, reference
from tests import tiny


def cells():
    with open(os.path.join(tiny.ROOT, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


def _reference_request(cfg, traffic, keys, demand, s_seed):
    from repro.core.demand import PoolSet

    preq = harness.request_for(cfg, traffic,
                               PoolSet(keys=keys, demand=demand), s_seed)
    return harness.reference_request(preq, traffic)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("bench")))


@pytest.mark.parametrize("workload", cells())
def test_control_is_not_correct(root, workload):
    parts = harness.load_cell(root, workload)
    cfg, traffic, limits = parts["config"], parts["traffic"], parts["limits"]
    with open(os.path.join(root, "bench", "cells", f"{workload}.json")) as f:
        kind = json.load(f)["control"]
    failed = []
    for seed in (3, 2**31 + 11, 97):
        keys, base = data.fleet(cfg, seed)
        demand = base * data.plan_scales(
            seed, 1, len(keys), traffic["plan_scale_sigma"])[:, None]
        req = _reference_request(cfg, traffic, keys, demand,
                                 data.scenario_seed(seed, 1))
        ctl = compare.reference_answer(reference.plan(
            cfg, req, keys, demand, reference.Numerics(kind)))
        ref = reference.plan(cfg, req, keys, demand,
                             follow=compare.row_buys(ctl))
        gaps = compare.gaps(ctl, ref, list(limits))
        failed.append(any(gaps[k] > limits[k] for k in limits))
    assert all(failed)


@pytest.mark.parametrize("workload", cells())
def test_reference_agrees_with_itself(root, workload):
    """The float64 reference against itself reads 0 on every number."""
    parts = harness.load_cell(root, workload)
    cfg, traffic, limits = parts["config"], parts["traffic"], parts["limits"]
    keys, base = data.fleet(cfg, 5)
    req = _reference_request(cfg, traffic, keys, base,
                             data.scenario_seed(5, 1))
    ref = reference.plan(cfg, req, keys, base)
    again = compare.reference_answer(reference.plan(cfg, req, keys, base))
    gaps = compare.gaps(again, ref, list(limits))
    assert all(v == 0.0 for v in gaps.values())
