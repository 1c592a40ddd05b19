"""Tests for the unified ``PlanRequest`` API and the (N scenarios x P
pools) batched rolling replay.

Four contracts, all golden-anchored:

* **request/legacy parity** — ``api.plan(PlanRequest(...))`` and the
  legacy ``plan_fleet_pools`` kwarg spelling are bit-identical (the shim
  builds the request, so parity is structural — these goldens keep it
  that way through future refactors), and loose rolling kwargs emit a
  ``DeprecationWarning``.
* **scenario batching is free** — ``scenarios=None`` and
  ``n_scenarios=1`` replays are bit-identical to the pre-scenario golden
  replay for every registry policy, and at N > 1 scenario 0 (the realized
  trace) stays bit-identical to the unbatched run with every band
  enabled.
* **batched replay correctness** — chunked runs merge bit-identically,
  the batched scan matches the loop-backend oracle, and per-scenario
  competitive ratios stay >= 1 for the hedge policy (hypothesis).
* **incremental IRLS carry** — ``irls_carry=True`` tracks the exact
  per-week IRLS refit far more closely than skipping IRLS entirely, and
  degenerates to the bit-exact base replay at ``irls_iters=0``.
"""

from __future__ import annotations

import os
import subprocess
import sys
import warnings

import numpy as np
import pytest

from repro.core import api
from repro.core import planner as pln
from repro.core import policy as pol
from repro.core import replan as rp
from repro.data import scenarios as sc
from repro.data import traces
from repro.launch import mesh as mesh_mod

GOLDEN_POOLS = dict(num_pools=3, num_hours=24 * 7 * 20)
GOLDEN_ROLLING = dict(cadence_weeks=2, start_weeks=6, horizon_weeks=4)
# The golden replay (shared with test_policy / test_spot): the scenario
# axis and the PlanRequest front door must not move it by one ulp.  Its
# totals are held to the python-loop replay, which differs from the scan
# only in the float32 summation order of the prefix normal equations.
LOOP_RTOL = 2e-4
GOLDEN_WEEKS = np.arange(6, 20)
GOLDEN_DECISIONS = np.arange(14) % 2 == 0


@pytest.fixture(scope="module")
def pools():
    return traces.synthetic_pool_set(**GOLDEN_POOLS)


class TestPlanRequestValidation:
    def test_unknown_mode(self, pools):
        with pytest.raises(ValueError, match="unknown mode"):
            api.PlanRequest(pools=pools, mode="streaming")

    def test_policy_is_rolling_only(self, pools):
        with pytest.raises(ValueError, match="rolling"):
            api.PlanRequest(pools=pools, policy="deterministic_hedge")

    def test_scenarios_is_rolling_only(self, pools):
        with pytest.raises(ValueError, match="rolling"):
            api.PlanRequest(pools=pools, scenarios=4)

    def test_rolling_knobs_on_one_shot(self, pools):
        with pytest.raises(ValueError, match="one_shot"):
            api.PlanRequest(
                pools=pools, rolling=api.RollingConfig(cadence_weeks=2)
            )

    def test_unknown_policy_name(self, pools):
        with pytest.raises(ValueError, match="unknown policy"):
            api.PlanRequest(pools=pools, mode="rolling", policy="zzz")

    def test_bool_scenarios_rejected(self, pools):
        with pytest.raises(TypeError, match="bool"):
            api.PlanRequest(pools=pools, mode="rolling", scenarios=True)

    def test_bad_rolling_config_fields(self):
        with pytest.raises(ValueError, match="cadence_weeks"):
            api.RollingConfig(cadence_weeks=0)
        with pytest.raises(ValueError, match="solver"):
            api.RollingConfig(solver="newton")
        with pytest.raises(ValueError, match="backend"):
            api.RollingConfig(backend="while")

    def test_rolling_takes_config_not_dict(self, pools):
        with pytest.raises(TypeError, match="RollingConfig"):
            api.PlanRequest(
                pools=pools, mode="rolling",
                rolling={"cadence_weeks": 2},
            )

    def test_plan_takes_request(self, pools):
        with pytest.raises(TypeError, match="PlanRequest"):
            api.plan(pools)

    def test_request_is_frozen(self, pools):
        req = api.PlanRequest(pools=pools)
        with pytest.raises(Exception):
            req.mode = "rolling"


class TestRequestLegacyParityGolden:
    """Both spellings hit the pinned golden outputs bit-for-bit."""

    def test_rolling_request_matches_legacy_golden(self, pools):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            legacy = pln.plan_fleet_pools(
                pools, mode="rolling", **GOLDEN_ROLLING
            )
        req = api.plan(api.PlanRequest(
            pools=pools, mode="rolling",
            horizon_weeks=GOLDEN_ROLLING["horizon_weeks"],
            rolling=api.RollingConfig(
                cadence_weeks=GOLDEN_ROLLING["cadence_weeks"],
                start_weeks=GOLDEN_ROLLING["start_weeks"],
            ),
        ))
        assert legacy.total_cost == req.total_cost
        assert np.array_equal(legacy.targets, req.targets)
        assert np.array_equal(legacy.increments, req.increments)
        np.testing.assert_array_equal(req.weeks, GOLDEN_WEEKS)
        np.testing.assert_array_equal(req.decision_mask, GOLDEN_DECISIONS)
        loop = rp.replan_fleet_pools(
            pools, backend="loop", compare=False, **GOLDEN_ROLLING
        )
        np.testing.assert_allclose(
            req.total_cost, loop.total_cost, rtol=LOOP_RTOL
        )

    def test_one_shot_request_matches_legacy(self, pools):
        legacy = pln.plan_fleet_pools(pools, horizon_weeks=4)
        req = api.plan(api.PlanRequest(pools=pools, horizon_weeks=4))
        assert legacy.total_cost == req.total_cost
        assert np.array_equal(legacy.widths, req.widths)
        assert np.array_equal(legacy.levels, req.levels)

    def test_loose_rolling_kwargs_warn(self, pools):
        with pytest.warns(DeprecationWarning, match="RollingConfig"):
            pln.plan_fleet_pools(
                pools, mode="rolling", **GOLDEN_ROLLING
            )

    def test_scenarios_none_disabled_path_golden(self, pools):
        rep = rp.replan_fleet_pools(
            pools, scenarios=None, compare=False, **GOLDEN_ROLLING
        )
        base = rp.replan_fleet_pools(pools, compare=False, **GOLDEN_ROLLING)
        assert rep.total_cost == base.total_cost
        assert np.array_equal(rep.targets, base.targets)
        np.testing.assert_array_equal(rep.decision_mask, GOLDEN_DECISIONS)
        assert rep.n_scenarios == 1
        assert rep.scenario_family is None
        assert rep.targets.ndim == 3  # no scenario axis


class TestScenarioIdentityGolden:
    """``n_scenarios=1`` IS the unbatched replay — for every policy."""

    @pytest.mark.parametrize("name", sorted(pol.POLICIES))
    def test_n1_bit_identical_per_policy(self, pools, name):
        base = rp.replan_fleet_pools(
            pools, policy=name, compare=False, **GOLDEN_ROLLING
        )
        scen = rp.replan_fleet_pools(
            pools, policy=name, scenarios=1, compare=False, **GOLDEN_ROLLING
        )
        assert base.total_cost == scen.total_cost
        assert np.array_equal(base.targets, scen.targets)
        assert np.array_equal(base.active, scen.active)
        assert scen.n_scenarios == 1
        assert scen.scenario_cost.shape == (1,)
        assert float(scen.scenario_cost[0]) == base.total_cost

    def test_n1_golden_total(self, pools):
        """With the baselines on, the N=1 batch reports the unbatched
        replay's rolling, one-shot and hindsight totals exactly, and the
        rolling total agrees with the python-loop replay."""
        rep = rp.replan_fleet_pools(
            pools, scenarios=sc.ScenarioConfig(n_scenarios=1),
            **GOLDEN_ROLLING,
        )
        base = rp.replan_fleet_pools(pools, **GOLDEN_ROLLING)
        assert rep.total_cost == base.total_cost
        assert rep.one_shot_cost == base.one_shot_cost
        assert rep.hindsight_cost == base.hindsight_cost
        assert rep.scenario_one_shot_cost.shape == (1,)
        np.testing.assert_array_equal(rep.weeks, GOLDEN_WEEKS)
        loop = rp.replan_fleet_pools(
            pools, backend="loop", compare=False, **GOLDEN_ROLLING
        )
        np.testing.assert_allclose(
            rep.total_cost, loop.total_cost, rtol=LOOP_RTOL
        )

    def test_scenario0_anchors_realized_all_bands(self, pools):
        """At N > 1 with spot+migration+convertible all on, scenario 0
        stays bit-identical to the unbatched replay."""
        kw = dict(
            spot=True, migration=True, convertible=True,
            compare=False, **GOLDEN_ROLLING,
        )
        base = rp.replan_fleet_pools(pools, **kw)
        scen = rp.replan_fleet_pools(
            pools,
            scenarios=sc.ScenarioConfig(n_scenarios=3, family="regime"),
            **kw,
        )
        assert np.array_equal(scen.targets[:, 0], base.targets)
        assert np.array_equal(scen.conv_active[:, 0], base.conv_active)
        assert np.array_equal(scen.spot_cost[:, 0], base.spot_cost)
        assert np.array_equal(scen.spot_floor[:, 0], base.spot_floor)


class TestScenarioBatchedReplay:
    def test_report_shapes_and_summary(self, pools):
        n = 4
        rep = rp.replan_fleet_pools(
            pools,
            scenarios=sc.ScenarioConfig(n_scenarios=n, family="growth"),
            **GOLDEN_ROLLING,
        )
        s, p = rep.targets.shape[0], GOLDEN_POOLS["num_pools"]
        assert rep.targets.shape[:2] == (s, n)
        assert rep.targets.shape[2] == p
        assert rep.weekly_cost.shape == (s, n)
        for field in ("scenario_cost", "scenario_one_shot_cost",
                      "scenario_hindsight_cost", "scenario_cr",
                      "scenario_regret"):
            assert getattr(rep, field).shape == (n,), field
        assert rep.hindsight_widths.shape[0] == n
        summ = rep.summary()
        assert summ["n_scenarios"] == n
        for k in ("scenario_cost_mean", "scenario_cost_p95",
                  "scenario_cr_mean", "scenario_cr_p95",
                  "scenario_regret_mean", "scenario_regret_p95"):
            assert k in summ, k
        # Scalar aggregates are means over scenarios.
        np.testing.assert_allclose(
            rep.total_cost, rep.scenario_cost.mean(), rtol=1e-6
        )

    def test_chunked_merge_bit_identical(self, pools):
        cfg = sc.ScenarioConfig(n_scenarios=4, family="growth")
        full = rp.replan_fleet_pools(pools, scenarios=cfg, **GOLDEN_ROLLING)
        chunked = rp.replan_fleet_pools(
            pools,
            scenarios=sc.ScenarioConfig(
                n_scenarios=4, family="growth", chunk=3
            ),
            **GOLDEN_ROLLING,
        )
        assert np.array_equal(full.targets, chunked.targets)
        assert np.array_equal(full.scenario_cost, chunked.scenario_cost)
        assert np.array_equal(full.scenario_cr, chunked.scenario_cr)
        assert full.total_cost == chunked.total_cost
        assert chunked.n_scenarios == 4

    def test_batched_scan_matches_loop_oracle(self, pools):
        cfg = sc.ScenarioConfig(n_scenarios=3, family="regime")
        kw = dict(scenarios=cfg, compare=False, **GOLDEN_ROLLING)
        scan = rp.replan_fleet_pools(pools, backend="scan", **kw)
        loop = rp.replan_fleet_pools(pools, backend="loop", **kw)
        np.testing.assert_allclose(
            scan.targets, loop.targets, rtol=2e-4, atol=1e-3
        )
        np.testing.assert_allclose(
            scan.scenario_cost, loop.scenario_cost, rtol=2e-4
        )

    def test_per_scenario_cr_at_least_one_property(self, pools):
        """Per-scenario competitive ratios of the hedge policy stay >= 1
        against each scenario's own hindsight-optimal constant stack."""
        hypothesis = pytest.importorskip("hypothesis")
        st = pytest.importorskip("hypothesis.strategies")

        @hypothesis.settings(max_examples=4, deadline=None)
        @hypothesis.given(
            family=st.sampled_from(("regime", "growth", "scale", "burst")),
            seed=st.integers(0, 100),
        )
        def run(family, seed):
            rep = rp.replan_fleet_pools(
                pools, policy="deterministic_hedge",
                scenarios=sc.ScenarioConfig(
                    n_scenarios=3, family=family, seed=seed
                ),
                **GOLDEN_ROLLING,
            )
            assert (rep.scenario_cr >= 1.0 - 1e-5).all(), rep.scenario_cr

        run()


class TestIrlsCarry:
    def test_carry_at_zero_iters_is_base(self, pools):
        base = rp.replan_fleet_pools(pools, compare=False, **GOLDEN_ROLLING)
        carry = rp.replan_fleet_pools(
            pools, irls_carry=True, compare=False, **GOLDEN_ROLLING
        )
        assert base.total_cost == carry.total_cost
        assert np.array_equal(base.targets, carry.targets)

    @pytest.mark.parametrize("iters", [1, 2])
    def test_carry_tracks_exact_refit(self, pools, iters):
        kw = dict(compare=False, **GOLDEN_ROLLING)
        base = rp.replan_fleet_pools(pools, **kw)
        exact = rp.replan_fleet_pools(pools, irls_iters=iters, **kw)
        carry = rp.replan_fleet_pools(
            pools, irls_iters=iters, irls_carry=True, **kw
        )
        rel = abs(carry.total_cost - exact.total_cost) / exact.total_cost
        assert rel < 2e-3
        # The frozen-weights carry is closer to the exact IRLS refit than
        # not reweighting at all — otherwise it isn't carrying anything.
        rel_base = abs(base.total_cost - exact.total_cost) / exact.total_cost
        assert rel < rel_base

    def test_carry_via_request(self, pools):
        rep = api.plan(api.PlanRequest(
            pools=pools, mode="rolling",
            horizon_weeks=GOLDEN_ROLLING["horizon_weeks"],
            rolling=api.RollingConfig(
                cadence_weeks=GOLDEN_ROLLING["cadence_weeks"],
                start_weeks=GOLDEN_ROLLING["start_weeks"],
                irls_iters=1, irls_carry=True, compare=False,
            ),
        ))
        assert np.isfinite(rep.total_cost)


class TestShardRows:
    def test_single_device_noop(self):
        import jax
        import jax.numpy as jnp

        x = jnp.arange(12.0).reshape(6, 2)
        y = mesh_mod.shard_rows(x)
        assert np.array_equal(np.asarray(x), np.asarray(y))
        if len(jax.devices()) == 1:
            assert y.sharding == x.sharding

    def test_host_rows_become_a_device_array(self):
        import jax

        x = np.arange(12.0, dtype=np.float32).reshape(6, 2)
        y = mesh_mod.shard_rows(x)
        assert isinstance(y, jax.Array)
        assert np.array_equal(x, np.asarray(y))

    def test_multi_device_sharded_replay_matches(self):
        """On a forced 2-device host, the scenario-flattened rows shard
        and the replay output matches the 1-device run."""
        script = r"""
import os
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=2"
)
import jax
import numpy as np
from repro.data import traces, scenarios as sc
from repro.core import replan as rp
from repro.launch import mesh as mesh_mod

assert len(jax.devices()) == 2
x = jax.numpy.arange(8.0).reshape(4, 2)
y = mesh_mod.shard_rows(x)
assert len(y.sharding.device_set) == 2
# host rows go straight to their shards; a pinned device keeps them whole
y = mesh_mod.shard_rows(np.arange(8.0).reshape(4, 2))
assert len(y.sharding.device_set) == 2
with jax.default_device(jax.devices()[1]):
    y = mesh_mod.shard_rows(np.arange(8.0).reshape(4, 2))
assert y.devices() == {jax.devices()[1]}
pools = traces.synthetic_pool_set(num_pools=2, num_hours=24 * 7 * 10)
rep = rp.replan_fleet_pools(
    pools, cadence_weeks=2, start_weeks=4, horizon_weeks=2,
    compare=False,
    scenarios=sc.ScenarioConfig(n_scenarios=3, family="growth"),
)
print(float(rep.total_cost))
"""
        env = dict(os.environ)
        env["PYTHONPATH"] = os.pathsep.join(
            [os.path.join(os.path.dirname(__file__), "..", "src"),
             env.get("PYTHONPATH", "")]
        )
        out = subprocess.run(
            [sys.executable, "-c", script],
            capture_output=True, text=True, env=env, timeout=600,
        )
        assert out.returncode == 0, out.stderr
        sharded_total = float(out.stdout.strip().splitlines()[-1])
        pools = traces.synthetic_pool_set(num_pools=2, num_hours=24 * 7 * 10)
        rep = rp.replan_fleet_pools(
            pools, cadence_weeks=2, start_weeks=4, horizon_weeks=2,
            compare=False,
            scenarios=sc.ScenarioConfig(n_scenarios=3, family="growth"),
        )
        np.testing.assert_allclose(rep.total_cost, sharded_total, rtol=1e-5)
