"""The traffic file reaches the program as it stands: every field of
``request.rolling``, ``request.telemetry`` and ``request.scenarios``
lands in the ``PlanRequest``, and the per-layer readers see the shape of
the request that ran."""

import json
import os

import numpy as np

from lib import data, harness
from tests import tiny


def _request(rolling, telemetry=None, scenarios=None):
    from repro.core.demand import PoolSet

    with open(os.path.join(tiny.BENCH, "configs", "paper_estate.json")) as f:
        cfg = json.load(f)
    keys, _ = data.turnover_pools(12, cfg["pricing"]["generations"])[:2]
    keys = tuple(sorted(keys))
    demand = np.ones((len(keys), 4 * 168), np.float32)
    traffic = {"request": {"spot": False, "migration": False,
                           "convertible": False, "rolling": rolling,
                           "telemetry": telemetry, "scenarios": scenarios}}
    pools = PoolSet(keys=keys, demand=demand)
    return cfg, traffic, harness.request_for(cfg, traffic, pools, 17)


def test_rolling_fields_reach_the_request():
    rolling = {"solver": "grid", "use_kernel": True, "num_grid": 64,
               "cadence": "breach", "breach_tolerance": 3.0,
               "compare": False}
    _, traffic, preq = _request(rolling)
    for k, v in rolling.items():
        assert getattr(preq.rolling, k) == v, k
    ref = harness.reference_request(preq, traffic)
    assert (ref["solver"], ref["cadence"]) == ("grid", "breach")
    assert harness.shape_of(preq)["num_grid"] == 64


def test_start_weeks_from_config_unless_traffic_gives_it():
    cfg, _, preq = _request({})
    assert preq.rolling.start_weeks == cfg.get("start_weeks")
    _, _, preq = _request({"start_weeks": 30})
    assert preq.rolling.start_weeks == 30


def test_telemetry_and_scenarios_reach_the_request():
    _, traffic, preq = _request(
        {}, telemetry={"ledger": False, "calibration": True,
                       "provenance": True},
        scenarios={"n_scenarios": 3, "family": "growth"})
    assert (preq.telemetry.calibration, preq.telemetry.provenance,
            preq.telemetry.ledger) == (True, True, False)
    assert (preq.scenarios.n_scenarios, preq.scenarios.family,
            preq.scenarios.seed) == (3, "growth", 17)
    assert harness.shape_of(preq)["rows"] == 36
    assert harness.reference_request(preq, traffic)["scenarios"]["n"] == 3


def test_reference_refuses_what_it_does_not_replay():
    import pytest

    from lib import reference

    cfg, traffic, preq = _request({"cadence": "breach"})
    req = harness.reference_request(preq, traffic)
    with pytest.raises(NotImplementedError, match="cadence"):
        reference.plan(cfg, req, preq.pools.keys,
                       np.asarray(preq.pools.demand))
