"""Policy framework + tournament: the default rolling policy is
bit-identical to the pre-policy replay (hardcoded golden outputs with
``policy=None``), the degenerate policies reproduce the report baselines,
the Ambati et al. hedging rules honor their per-band ski-rental
mechanics and classical competitive-ratio bounds on steady fleets, the
rolling planner beats both hedges on the declining fleet by a pinned
margin, and the tournament rig's scan replay agrees with its Python-loop
oracle."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.capacity import pricing
from repro.core import planner as pl
from repro.core import policy as pol
from repro.core import portfolio as pf
from repro.core import tournament as tn
from repro.core.demand import HOURS_PER_WEEK
from repro.data import scenarios as sc
from repro.data import traces

WK = HOURS_PER_WEEK

GOLDEN_POOLS = dict(num_pools=3, num_hours=24 * 7 * 20)
GOLDEN_ROLLING = dict(cadence_weeks=2, start_weeks=6, horizon_weeks=4)
# Same scenario as tests/test_spot.py::TestSpotDisabledBitIdentical.  The
# toolchain and the CPU move its totals, so they are held to the
# python-loop replay, which differs from the scan only in the float32
# summation order of the prefix normal equations.
LOOP_RTOL = 2e-4


class TestPolicyDefaultGolden:
    """Tentpole acceptance: ``policy=None`` is the paper's policy — it
    decides on the cadence weeks and its totals hold to the loop replay —
    and every spelling of the default policy compiles to the same
    numbers."""

    @pytest.fixture(scope="class")
    def pools(self):
        return traces.synthetic_pool_set(**GOLDEN_POOLS)

    def test_rolling_default_policy_golden(self, pools):
        rep, loop = (
            pl.plan_fleet_pools(
                pools, mode="rolling", compare=False, policy=None,
                backend=backend, **GOLDEN_ROLLING,
            )
            for backend in ("scan", "loop")
        )
        np.testing.assert_array_equal(rep.weeks, np.arange(6, 20))
        np.testing.assert_array_equal(
            rep.decision_mask, np.arange(14) % 2 == 0
        )
        np.testing.assert_allclose(
            rep.total_cost, loop.total_cost, rtol=LOOP_RTOL
        )
        np.testing.assert_allclose(
            float(rep.targets.sum()), float(loop.targets.sum()),
            rtol=LOOP_RTOL,
        )
        np.testing.assert_allclose(
            float(rep.increments.sum()), float(loop.increments.sum()),
            rtol=LOOP_RTOL,
        )
        assert rep.policy_name == "rolling_portfolio"

    def test_policy_spellings_bit_identical(self, pools):
        reps = [
            pl.plan_fleet_pools(
                pools, mode="rolling", compare=False, policy=p,
                **GOLDEN_ROLLING,
            )
            for p in (None, "rolling_portfolio", pol.RollingPortfolioPolicy())
        ]
        for rep in reps[1:]:
            assert rep.total_cost == reps[0].total_cost
            np.testing.assert_array_equal(rep.targets, reps[0].targets)
            np.testing.assert_array_equal(rep.increments, reps[0].increments)


class TestPolicyInterface:
    def test_get_policy_none_is_rolling(self):
        p = pol.get_policy(None)
        assert isinstance(p, pol.RollingPortfolioPolicy)
        assert p.name == "rolling_portfolio"

    def test_get_policy_by_name(self):
        for name, cls in pol.POLICIES.items():
            p = pol.get_policy(name)
            assert isinstance(p, cls)
            assert p.name == name

    def test_get_policy_instance_passthrough(self):
        p = pol.DeterministicHedgePolicy(grid_size=4)
        assert pol.get_policy(p) is p

    def test_get_policy_unknown_name_raises(self):
        with pytest.raises(ValueError, match="unknown policy"):
            pol.get_policy("martingale")

    def test_get_policy_bad_type_raises(self):
        with pytest.raises(TypeError, match="policy must be"):
            pol.get_policy(42)

    def test_non_forecasting_policy_rejects_bands(self):
        pools = traces.synthetic_pool_set(num_pools=2, num_hours=24 * 7 * 12)
        with pytest.raises(ValueError, match="forecast"):
            pl.plan_fleet_pools(
                pools, mode="rolling", compare=False, spot=True,
                policy="deterministic_hedge", start_weeks=6,
                horizon_weeks=4,
            )

    def test_one_shot_mode_rejects_policy(self):
        pools = traces.synthetic_pool_set(num_pools=2, num_hours=24 * 7 * 12)
        with pytest.raises(TypeError, match="rolling"):
            pl.plan_fleet_pools(pools, policy="one_shot", horizon_weeks=4)

    def test_hedge_constructor_validation(self):
        with pytest.raises(ValueError, match="grid_size"):
            pol.DeterministicHedgePolicy(grid_size=0)
        with pytest.raises(ValueError, match="top_multiplier"):
            pol.DeterministicHedgePolicy(top_multiplier=0.0)


class TestDegeneratePolicies:
    """The one-shot and hindsight policies replayed through the SAME scan
    harness reproduce the report's analytic baselines exactly."""

    @pytest.fixture(scope="class")
    def pools(self):
        return traces.synthetic_pool_set(**GOLDEN_POOLS)

    @pytest.fixture(scope="class")
    def baseline(self, pools):
        return pl.plan_fleet_pools(
            pools, mode="rolling", compare=True, **GOLDEN_ROLLING
        )

    def test_one_shot_policy_matches_baseline(self, pools, baseline):
        rep = pl.plan_fleet_pools(
            pools, mode="rolling", compare=False, policy="one_shot",
            **GOLDEN_ROLLING,
        )
        assert rep.policy_name == "one_shot"
        assert rep.total_cost == baseline.one_shot_cost

    def test_hindsight_policy_matches_baseline(self, pools, baseline):
        rep = pl.plan_fleet_pools(
            pools, mode="rolling", compare=False, policy="hindsight",
            **GOLDEN_ROLLING,
        )
        assert rep.policy_name == "hindsight"
        assert rep.total_cost == baseline.hindsight_cost

    def test_hedge_policy_runs_full_harness(self, pools):
        rep = pl.plan_fleet_pools(
            pools, mode="rolling", compare=False,
            policy="deterministic_hedge", **GOLDEN_ROLLING,
        )
        assert rep.policy_name == "deterministic_hedge"
        assert np.isfinite(rep.total_cost) and rep.total_cost > 0
        assert float(rep.increments.sum()) > 0  # it does commit


def _hedge_ctx(demand, *, grid_size=3, start_weeks=4, clouds=None):
    clouds = clouds if clouds is not None else ("aws",) * demand.shape[0]
    return pol.make_context(
        jnp.asarray(demand, jnp.float32),
        pf.options_from_pricing(),
        clouds=clouds,
        od_rate=pricing.on_demand_premium(),
        start_weeks=start_weeks,
        cadence_weeks=1,
        horizon_weeks=2,
    )


def _run_hedge(policy, ctx):
    """Replay the hedge through the harness purchase rule eagerly,
    recording (accrued, active) after every week."""
    pstate, decide = policy.setup(ctx)
    active = jnp.zeros((ctx.num_pools, ctx.num_options), jnp.float32)
    hist = []
    for w in range(ctx.start_weeks, ctx.total_weeks):
        d_prev = ctx.demand[:, (w - 1) * WK: w * WK]
        pstate, dec = decide(
            pstate, pol.Observation(jnp.int32(w), active, d_prev)
        )
        inc = jnp.maximum(dec.targets - active, 0.0)
        active = active + jnp.where(inc > 1e-9, inc, 0.0)
        hist.append((np.asarray(pstate), np.asarray(active)))
    return hist


class TestHedgeMechanics:
    """Unit mechanics of the per-band ski rental (Ambati et al. 2004.04302)."""

    def test_deterministic_thresholds_are_one(self):
        z = pol.DeterministicHedgePolicy(grid_size=8)._thresholds(3)
        np.testing.assert_array_equal(np.asarray(z), 1.0)

    def test_randomized_thresholds_distribution(self):
        p = pol.RandomizedHedgePolicy(grid_size=64, seed=7)
        z = np.asarray(p._thresholds(4))
        assert z.shape == (4, 64)
        assert (z > 0.0).all() and (z <= 1.0).all()
        z2 = np.asarray(pol.RandomizedHedgePolicy(
            grid_size=64, seed=7)._thresholds(4))
        np.testing.assert_array_equal(z, z2)  # seed-reproducible
        z3 = np.asarray(pol.RandomizedHedgePolicy(
            grid_size=64, seed=8)._thresholds(4))
        assert not np.array_equal(z, z3)

    def test_hedge_threshold_is_inverse_cdf(self):
        u = jnp.linspace(0.0, 1.0, 11)
        z = np.asarray(pol._hedge_threshold(u))
        assert z[0] == pytest.approx(0.0)
        assert z[-1] == pytest.approx(1.0)
        assert (np.diff(z) > 0).all()  # monotone: a valid inverse CDF
        # density e^z/(e-1): CDF(z) = (e^z - 1)/(e - 1), so the inverse
        # at u=0.5 is log(1 + 0.5(e-1))
        assert z[5] == pytest.approx(np.log1p(0.5 * (np.e - 1.0)))

    def test_break_even_commits_occupied_bands_only(self):
        """Constant demand 10 against top=15 split into 3 bands of 5:
        the two occupied bands commit once their accrued on-demand spend
        crosses the band buy price; the empty top band never does."""
        demand = np.full((1, 12 * WK), 10.0, np.float32)
        ctx = _hedge_ctx(demand, grid_size=3, start_weeks=4)
        hist = _run_hedge(pol.DeterministicHedgePolicy(grid_size=3), ctx)
        final = hist[-1][1].sum()
        assert final == pytest.approx(10.0, abs=1e-4)   # bands [0,5),[5,10)
        assert all(a.sum() <= 10.0 + 1e-4 for _, a in hist)  # never band 3

    def test_break_even_week_matches_analytic(self):
        """The commit fires the first decision week where accrued od
        spend >= band price, with start-1 weeks pre-accrued at setup."""
        demand = np.full((1, 12 * WK), 10.0, np.float32)
        ctx = _hedge_ctx(demand, grid_size=3, start_weeks=4)
        od = ctx.od
        rate_eff = np.where(
            np.asarray(ctx.avail[0]), np.asarray(ctx.rates), np.inf
        )
        kstar = int(rate_eff.argmin())
        eff_term = min(
            int(ctx.term_weeks[kstar]), ctx.total_weeks - ctx.start_weeks
        )
        dg = 15.0 / 3
        band_price = float(ctx.rates[kstar]) * eff_term * WK * dg
        weekly_accrual = od * dg * WK     # fully occupied band, one week
        # the decision at week w has seen weeks 0..w-1 on the meter:
        # [0, start-1) pre-accrued at setup plus d_prev each week since
        want_week = next(
            w for w in range(ctx.start_weeks, ctx.total_weeks)
            if w * weekly_accrual >= band_price
        )
        commits = [
            w for (w, (_, a)) in zip(
                range(ctx.start_weeks, ctx.total_weeks), _run_hedge(
                    pol.DeterministicHedgePolicy(grid_size=3), ctx)
            ) if a.sum() > 1e-6
        ]
        assert commits and commits[0] == want_week

    def test_accrual_resets_on_commit_and_covered_bands_stop(self):
        demand = np.full((1, 12 * WK), 10.0, np.float32)
        ctx = _hedge_ctx(demand, grid_size=3, start_weeks=4)
        hist = _run_hedge(pol.DeterministicHedgePolicy(grid_size=3), ctx)
        committed = [i for i, (_, a) in enumerate(hist) if a.sum() > 1e-6]
        i0 = committed[0]
        accrued_after = hist[i0][0]
        # both occupied bands commit together (same price, same accrual):
        # their meters reset to 0 and, now covered, never accrue again
        np.testing.assert_allclose(accrued_after[0, :2], 0.0)
        for acc, _ in hist[i0:]:
            np.testing.assert_allclose(acc[0, :2], 0.0)
        # the empty band's meter stays at zero spend forever
        assert all(acc[0, 2] == 0.0 for acc, _ in hist)

    def test_designated_option_is_cheapest_available(self):
        demand = np.full((2, 12 * WK), 10.0, np.float32)
        ctx = _hedge_ctx(demand, clouds=("aws", "gcp"))
        hist = _run_hedge(pol.DeterministicHedgePolicy(grid_size=3), ctx)
        active = hist[-1][1]
        rate_eff = np.where(
            np.asarray(ctx.avail), np.asarray(ctx.rates)[None, :], np.inf
        )
        for p in range(2):
            kstar = int(rate_eff[p].argmin())
            assert active[p, kstar] > 0
            others = np.delete(active[p], kstar)
            np.testing.assert_allclose(others, 0.0)

    def test_targets_stay_within_candidate_grid(self):
        demand = np.full((1, 12 * WK), 10.0, np.float32)
        ctx = _hedge_ctx(demand, grid_size=4, start_weeks=4)
        p = pol.DeterministicHedgePolicy(grid_size=4)
        pstate, decide = p.setup(ctx)
        top = 15.0  # 1.5 x history peak
        active = jnp.zeros((1, ctx.num_options), jnp.float32)
        for w in range(ctx.start_weeks, ctx.total_weeks):
            d_prev = ctx.demand[:, (w - 1) * WK: w * WK]
            pstate, dec = decide(
                pstate, pol.Observation(jnp.int32(w), active, d_prev)
            )
            t = np.asarray(dec.targets)
            assert (t >= 0).all() and np.isfinite(t).all()
            assert t.sum() <= float(active.sum()) + top + 1e-3
            inc = jnp.maximum(dec.targets - active, 0.0)
            active = active + inc


class TestTournament:
    @pytest.fixture(scope="class")
    def small(self):
        kw = dict(
            num_pools=2, num_weeks=16, num_seeds=2, start_weeks=8,
            cadence_weeks=2, horizon_weeks=4,
            families=("steady", "burst"),
            policies=("deterministic_hedge", "rolling_portfolio"),
        )
        return kw, tn.run_tournament(**kw)

    def test_report_shapes(self, small):
        kw, rep = small
        npol, nf, ns = 2, 2, kw["num_seeds"]
        assert rep.cost.shape == (npol, nf, ns)
        assert rep.hindsight_cost.shape == (nf, ns)
        assert rep.competitive_ratio.shape == (npol, nf, ns)
        assert rep.regret.shape == (npol, nf, ns)
        assert rep.policies == ("deterministic_hedge", "rolling_portfolio")
        assert rep.families == ("steady", "burst")

    def test_competitive_ratio_at_least_one(self, small):
        _, rep = small
        assert (rep.competitive_ratio >= 1.0 - 1e-6).all()
        assert (rep.regret >= -1e-2).all()
        np.testing.assert_allclose(
            rep.regret, rep.cost - rep.hindsight_cost[None], rtol=1e-12
        )

    def test_scan_matches_loop(self, small):
        """Acceptance: the vmapped scan replay == the Python-loop oracle
        (loop uses the direct prefix solve, hence float tolerance)."""
        kw, rep = small
        loop = tn.run_tournament(**kw, backend="loop")
        np.testing.assert_allclose(loop.cost, rep.cost, rtol=1e-4)

    def test_reproducible(self, small):
        kw, rep = small
        again = tn.run_tournament(**kw)
        np.testing.assert_array_equal(again.cost, rep.cost)
        np.testing.assert_array_equal(
            again.hindsight_cost, rep.hindsight_cost
        )

    def test_family_stats_and_summary(self, small):
        _, rep = small
        st = rep.family_stats("rolling_portfolio", "steady")
        assert set(st) == {
            "cr_mean", "cr_p95", "cr_max", "regret_mean", "regret_max"
        }
        assert st["cr_mean"] <= st["cr_max"] + 1e-9
        assert st["cr_p95"] <= st["cr_max"] + 1e-9
        summ = rep.summary()
        assert set(summ) == set(rep.policies)
        assert set(summ["rolling_portfolio"]) == set(rep.families)

    def test_markdown_table(self, small):
        _, rep = small
        md = rep.to_markdown()
        lines = md.splitlines()
        assert lines[0].startswith("| policy |")
        assert len(lines) == 2 + len(rep.policies)
        for p in rep.policies:
            assert any(p in ln for ln in lines)

    def test_policy_instances_accepted(self):
        rep = tn.run_tournament(
            policies=(pol.DeterministicHedgePolicy(grid_size=8),),
            families=("steady",), num_pools=2, num_weeks=12, num_seeds=1,
            start_weeks=6, horizon_weeks=2,
        )
        assert rep.policies == ("deterministic_hedge",)
        assert np.isfinite(rep.cost).all()


class TestTournamentAcceptance:
    """The PR's headline numbers: classical hedging bounds hold on the
    steady family, and the paper's forecasting planner beats both
    forecast-free hedges on the declining fleet by a clear margin."""

    MARGIN = 0.1

    @pytest.fixture(scope="class")
    def rep(self):
        return tn.run_tournament(
            policies=(
                "rolling_portfolio", "deterministic_hedge",
                "randomized_hedge",
            ),
            families=("steady", "declining"),
            num_seeds=8,
        )

    def test_deterministic_bound_on_steady(self, rep):
        st = rep.family_stats("deterministic_hedge", "steady")
        assert st["cr_max"] <= pol.DETERMINISTIC_CR_BOUND

    def test_randomized_bound_on_steady(self, rep):
        st = rep.family_stats("randomized_hedge", "steady")
        assert st["cr_mean"] <= pol.RANDOMIZED_CR_BOUND

    def test_rolling_beats_hedges_on_declining(self, rep):
        roll = rep.family_stats("rolling_portfolio", "declining")["cr_mean"]
        det = rep.family_stats(
            "deterministic_hedge", "declining")["cr_mean"]
        rnd = rep.family_stats("randomized_hedge", "declining")["cr_mean"]
        assert roll + self.MARGIN <= det
        assert roll + self.MARGIN <= rnd


class TestPolicyProperties:
    """Hypothesis property tests on the policy contract."""

    def _ctx(self, family, seed):
        demand = sc.scenario_path(
            family, num_pools=2, num_weeks=12, seed=seed
        )
        return pol.make_context(
            demand, pf.options_from_pricing(),
            clouds=tuple(c for c, _, _ in sc.scenario_keys(2)),
            od_rate=pricing.on_demand_premium(),
            start_weeks=6, cadence_weeks=1, horizon_weeks=2,
        )

    def test_hedge_cost_at_least_hindsight_property(self):
        hypothesis = pytest.importorskip("hypothesis")
        st = pytest.importorskip("hypothesis.strategies")

        @hypothesis.settings(max_examples=5, deadline=None)
        @hypothesis.given(
            family=st.sampled_from(sc.FAMILIES),
            seed=st.integers(0, 500),
        )
        def run(family, seed):
            ctx = self._ctx(family, seed)
            cost = float(tn._lean_replay(
                pol.DeterministicHedgePolicy(grid_size=8), ctx, "scan"
            ))
            hind = float(tn._hindsight_cost(
                ctx.demand, options=ctx.options, clouds=ctx.clouds,
                od=ctx.od, start_weeks=ctx.start_weeks,
            ))
            assert cost >= hind * (1.0 - 1e-5)  # CR >= 1

        run()

    def test_decide_purchases_nonnegative_property(self):
        hypothesis = pytest.importorskip("hypothesis")
        st = pytest.importorskip("hypothesis.strategies")

        @hypothesis.settings(max_examples=5, deadline=None)
        @hypothesis.given(
            seed=st.integers(0, 500),
            name=st.sampled_from(
                ("rolling_portfolio", "one_shot", "deterministic_hedge",
                 "randomized_hedge", "hindsight")
            ),
        )
        def run(seed, name):
            ctx = self._ctx("unpredictable", seed)
            policy = pol.get_policy(name)
            pstate, decide = policy.setup(ctx)
            active = jnp.zeros((2, ctx.num_options), jnp.float32)
            w = ctx.start_weeks
            d_prev = (
                ctx.demand[:, (w - 1) * WK: w * WK]
                if policy.needs_prev_demand else None
            )
            _, dec = decide(
                pstate, pol.Observation(jnp.int32(w), active, d_prev)
            )
            t = np.asarray(dec.targets)
            assert t.shape == (2, ctx.num_options)
            assert np.isfinite(t).all() and (t >= 0).all()
            assert bool(dec.is_decision)  # week start is always a decision

        run()

    def test_randomized_threshold_samples_match_density(self):
        hypothesis = pytest.importorskip("hypothesis")
        st = pytest.importorskip("hypothesis.strategies")

        @hypothesis.settings(max_examples=5, deadline=None)
        @hypothesis.given(seed=st.integers(0, 10_000))
        def run(seed):
            z = np.asarray(
                pol.RandomizedHedgePolicy(grid_size=256, seed=seed)
                ._thresholds(1)
            )
            assert (z > 0.0).all() and (z <= 1.0).all()
            # E[z] under e^z/(e-1) on (0,1] is 1/(e-1) ~ 0.582
            assert abs(z.mean() - 1.0 / (np.e - 1.0)) < 0.12

        run()
