"""Hardware-generation turnover subsystem: pricing tables + invariants,
logistic adoption scan vs loop, driver decomposition recovery, share-based
forecasting, convertible commitments in the one-shot and rolling planners —
plus the no-regression guarantee that migration=None / convertible=None
paths stay bit-identical to the default program (goldens held to float64
and loop-replay references)."""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.capacity import generations as gn
from repro.capacity import pricing
from repro.core import forecast as fc
from repro.core import ladder as ld
from repro.core import migration as mg
from repro.core import planner as pl
from repro.core import portfolio as pf
from repro.core.demand import HOURS_PER_WEEK
from repro.data import traces

WK = HOURS_PER_WEEK

# Two planted turnovers with epochs that differ from the pricing table —
# recovery tests must prove the fits come from the data, not the table.
PLANT = gn.MigrationConfig(generations=(
    pricing.Generation("aws", "C6i", "C7i", 20, 30.0, 0.25),
    pricing.Generation("gcp", "N2-Standard", "N4-Standard", 55, 26.0, 0.50),
))


class TestPricingTables:
    def test_tables_validate(self):
        pricing.validate_tables(force=True)  # the shipped data must be clean

    def test_corrupted_savings_plan_raises(self, monkeypatch):
        bad = pricing.SavingsPlan("aws", "C6i", 0.60, 0.52)  # 1y > 3y
        monkeypatch.setattr(
            pricing, "SAVINGS_PLANS", [bad] + pricing.SAVINGS_PLANS[1:]
        )
        with pytest.raises(ValueError, match="monotone in term"):
            pricing.validate_tables(force=True)

    def test_corrupted_spot_market_raises(self, monkeypatch):
        bad = pricing.SpotMarket("oraclecloud", 0.5, 0.05, 0.5, 0.1)
        monkeypatch.setattr(
            pricing, "SPOT_MARKETS", pricing.SPOT_MARKETS + [bad]
        )
        with pytest.raises(ValueError, match="unknown cloud"):
            pricing.validate_tables(force=True)

    def test_corrupted_generation_raises(self, monkeypatch):
        bad = pricing.Generation("aws", "C6i", "NotASku", 26, 40.0, 0.25)
        monkeypatch.setattr(
            pricing, "GENERATIONS", pricing.GENERATIONS + [bad]
        )
        with pytest.raises(ValueError, match="Table-2"):
            pricing.validate_tables(force=True)

    def test_chained_generation_raises(self, monkeypatch):
        chain = pricing.Generation("aws", "C7i", "C6i", 10, 10.0, 0.1)
        monkeypatch.setattr(
            pricing, "GENERATIONS", pricing.GENERATIONS + [chain]
        )
        with pytest.raises(ValueError, match="chained"):
            pricing.validate_tables(force=True)

    def test_unsorted_transitions_raise(self, monkeypatch):
        monkeypatch.setattr(
            pricing, "HARDWARE_TRANSITIONS",
            list(reversed(pricing.HARDWARE_TRANSITIONS)),
        )
        with pytest.raises(ValueError, match="date-sorted"):
            pricing.validate_tables(force=True)

    def test_convertible_discounts_haircut(self):
        for c in sorted(pricing.known_clouds()):
            d1, d3 = pricing.convertible_discounts(c)
            rows = [p for p in pricing.SAVINGS_PLANS if p.cloud == c]
            m1 = sum(p.discount_1y for p in rows) / len(rows)
            m3 = sum(p.discount_3y for p in rows) / len(rows)
            assert d1 < m1 and d3 < m3       # flexibility is never free
            assert 0.0 < d1 < d3 < 1.0

    def test_generation_midpoint(self):
        g = pricing.Generation("aws", "C6i", "C7i", 10, 20.0, 0.25)
        assert g.midpoint_week == 20.0


class TestMigrationEdges:
    def test_edges_matched_by_region(self):
        keys = [
            ("aws", "region_0", "C6i"), ("aws", "region_0", "C7i"),
            ("aws", "region_1", "C6i"),      # successor absent -> no edge
            ("gcp", "region_0", "N2-Standard"),
            ("gcp", "region_0", "N4-Standard"),
        ]
        edges = gn.migration_edges(keys, PLANT)
        assert edges.num_edges == 2
        np.testing.assert_array_equal(np.asarray(edges.src), [0, 3])
        np.testing.assert_array_equal(np.asarray(edges.dst), [1, 4])
        np.testing.assert_allclose(np.asarray(edges.uplift), [0.25, 0.5])
        np.testing.assert_allclose(
            np.asarray(edges.inv_gain), [1 / 1.25, 1 / 1.5]
        )

    def test_legacy_fleet_has_no_edges(self):
        pools = traces.synthetic_pool_set(num_pools=3, num_hours=24 * 7)
        assert gn.migration_edges(pools.keys).num_edges == 0

    def test_custom_config_validates_structure(self):
        """Planted rows must satisfy the same structural invariants as the
        static table — a duplicate source would scatter >100% of a pool's
        volume away (negative demand)."""
        with pytest.raises(ValueError, match="duplicate generation source"):
            gn.MigrationConfig(generations=(
                pricing.Generation("aws", "C6i", "C7i", 20, 28.0, 0.25),
                pricing.Generation("aws", "C6i", "M7GD", 20, 28.0, 0.30),
            ))
        with pytest.raises(ValueError, match="chained"):
            gn.MigrationConfig(generations=(
                pricing.Generation("aws", "C6i", "C7i", 20, 28.0, 0.25),
                pricing.Generation("aws", "C7i", "M7GD", 40, 28.0, 0.30),
            ))
        with pytest.raises(ValueError, match="duplicate generation succ"):
            gn.MigrationConfig(generations=(
                pricing.Generation("aws", "C6i", "C7i", 20, 28.0, 0.25),
                pricing.Generation("aws", "C7GD", "C7i", 20, 28.0, 0.30),
            ))
        with pytest.raises(ValueError, match="positive"):
            gn.MigrationConfig(generations=(
                pricing.Generation("aws", "C6i", "C7i", 20, -1.0, 0.25),
            ))
        with pytest.raises(ValueError, match="turnover fleet"):
            traces.synthetic_base_pool_set(
                num_pools=4, num_hours=24, migration=False
            )

    def test_resolve_migration_variants(self):
        assert gn.resolve_migration(None) is None
        assert gn.resolve_migration(False) is None
        assert isinstance(gn.resolve_migration(True), gn.MigrationConfig)
        assert gn.resolve_migration(PLANT) is PLANT
        with pytest.raises(TypeError):
            gn.resolve_migration("yes")


class TestMigrateScan:
    @pytest.fixture(scope="class")
    def setup(self):
        base = traces.synthetic_base_pool_set(
            num_pools=4, num_hours=24 * 7 * 12, seed=2, migration=PLANT
        )
        edges = gn.migration_edges(base.keys, PLANT)
        return base, edges

    def test_scan_matches_loop_bitwise(self, setup):
        """The compiled scan and the per-hour jitted-step replay must
        produce bit-identical demand matrices (acceptance)."""
        base, edges = setup
        d = jnp.asarray(base.demand)
        scan = gn.migrate_demand(d, edges)
        loop = gn.migrate_demand_loop(d, edges)
        np.testing.assert_array_equal(
            np.asarray(scan), np.asarray(loop)
        )

    def test_matches_closed_form(self, setup):
        """The scan's hazard walk IS the closed-form logistic: src keeps
        (1 - s) of its base, dst gains s / (1 + uplift), everyone is
        deflated by the software-efficiency curve."""
        base, edges = setup
        d = np.asarray(gn.migrate_demand(jnp.asarray(base.demand), edges))
        t = jnp.arange(base.num_hours)
        s = np.asarray(gn.adoption_shares(edges, t))
        eff = np.asarray(gn.software_deflator(
            t, PLANT.software_efficiency_per_year
        ))
        src = np.asarray(edges.src)
        dst = np.asarray(edges.dst)
        up = np.asarray(edges.uplift)
        for g in range(edges.num_edges):
            b_src = base.demand[src[g]]
            b_dst = base.demand[dst[g]]
            np.testing.assert_allclose(
                d[src[g]], b_src * (1 - s[g]) * eff, rtol=3e-4, atol=1e-4
            )
            np.testing.assert_allclose(
                d[dst[g]],
                (b_dst + b_src * s[g] / (1 + up[g])) * eff,
                rtol=3e-4, atol=1e-4,
            )

    def test_volume_conservation(self, setup):
        """Perf-adjusted volume (successors x (1 + uplift), deflator
        undone) equals the base volume: turnover moves demand, it does
        not create or destroy it."""
        base, edges = setup
        d = np.asarray(gn.migrate_demand(jnp.asarray(base.demand), edges))
        eff = np.asarray(gn.software_deflator(
            jnp.arange(base.num_hours), PLANT.software_efficiency_per_year
        ))
        perf = np.ones(base.num_pools, np.float32)
        perf[np.asarray(edges.dst)] = 1.0 + np.asarray(edges.uplift)
        got = ((d / eff) * perf[:, None]).sum()
        np.testing.assert_allclose(got, base.demand.sum(), rtol=1e-4)

    def test_no_edges_is_pure_deflation(self):
        pools = traces.synthetic_pool_set(num_pools=2, num_hours=24 * 7 * 2)
        edges = gn.migration_edges(pools.keys)
        out = np.asarray(
            gn.migrate_demand(jnp.asarray(pools.demand), edges)
        )
        eff = np.asarray(gn.software_deflator(
            jnp.arange(pools.num_hours), pricing.SOFTWARE_EFFICIENCY_PER_YEAR
        ))
        np.testing.assert_allclose(out, pools.demand * eff, rtol=1e-5)

    def test_turnover_fleet_shape(self):
        pools = traces.synthetic_pool_set(
            num_pools=8, num_hours=24 * 7 * 2, migration=True
        )
        assert pools.num_pools == 8
        families = {k[2] for k in pools.keys}
        table = {f for g in pricing.GENERATIONS
                 for f in (g.old_family, g.new_family)}
        assert families <= table

    def test_turnover_fleet_rejects_odd_pool_counts(self):
        with pytest.raises(ValueError, match="even"):
            traces.synthetic_pool_set(
                num_pools=13, num_hours=24 * 7, migration=True
            )
        with pytest.raises(ValueError, match="even"):
            traces.synthetic_base_pool_set(num_pools=1, num_hours=24 * 7)


class TestDriverDecomposition:
    @pytest.fixture(scope="class")
    def fleet(self):
        base = traces.synthetic_base_pool_set(
            num_pools=4, num_hours=24 * 7 * 104, seed=3, migration=PLANT
        )
        pools = gn.migrate_pool_set(base, PLANT)
        return base, pools

    def test_recovers_planted_logistics(self, fleet):
        """Fitted midpoints/spans must match the planted S-curves even
        though the decomposer only sees the table's *structure* (which
        pairs exist), not its epochs (acceptance)."""
        base, pools = fleet
        dec = mg.decompose_drivers(pools, migration=PLANT)
        for ef, g in zip(dec.edge_fits, PLANT.generations):
            assert ef.midpoint_weeks == pytest.approx(
                g.midpoint_week, abs=1.0
            )
            assert ef.span_weeks == pytest.approx(g.span_weeks, rel=0.05)

    def test_decompose_rejects_disabled_migration(self, fleet):
        _, pools = fleet
        with pytest.raises(ValueError, match="successor structure"):
            mg.decompose_drivers(pools, migration=False)

    def test_recovers_efficiency_drift(self, fleet):
        base, pools = fleet
        dec = mg.decompose_drivers(
            pools, migration=PLANT, user_volume=base.demand.sum(0)
        )
        assert dec.efficiency_per_year == pytest.approx(
            PLANT.software_efficiency_per_year, rel=0.05
        )

    def test_hardware_index_falls_with_adoption(self, fleet):
        _, pools = fleet
        dec = mg.decompose_drivers(pools, migration=PLANT)
        # Both uplifts > 0: once adoption is underway the fleet needs
        # fewer VMs per old-equivalent VM of work.
        assert dec.hardware_index[-1] < dec.hardware_index[0] - 0.05

    def test_share_prefix_matches_full_fit(self, fleet):
        """solve_share_prefix at the final week must equal the full-window
        fit_share (same moments, gathered vs summed)."""
        _, pools = fleet
        edges = gn.migration_edges(pools.keys, PLANT)
        d = jnp.asarray(pools.demand)
        t_max = float(pools.num_hours - 1)
        a_full, b_full = mg.fit_share(d, edges, t_max=t_max)
        state = mg.share_prefix_state(d, edges, t_max=t_max)
        a_pre, b_pre = mg.solve_share_prefix(
            state, pools.num_hours // WK
        )
        np.testing.assert_allclose(
            np.asarray(a_pre), np.asarray(a_full), rtol=2e-4, atol=2e-4
        )
        np.testing.assert_allclose(
            np.asarray(b_pre), np.asarray(b_full), rtol=2e-4, atol=2e-4
        )

    def test_prior_dominates_pre_launch(self):
        """Before launch the data carries ~no signal, so a prior-weighted
        fit must reproduce the announced curve; a data-only fit must not
        invent one."""
        base = traces.synthetic_base_pool_set(
            num_pools=4, num_hours=24 * 7 * 10, seed=5, migration=PLANT
        )
        pools = gn.migrate_pool_set(base, PLANT)  # 10 weeks << launch 20
        edges = gn.migration_edges(pools.keys, PLANT)
        t_max = float(pools.num_hours - 1)
        a, b = mg.fit_share(
            jnp.asarray(pools.demand), edges, t_max=t_max,
            prior_weight=100.0,
        )
        t_mid = jnp.asarray([
            g.midpoint_week * WK for g in PLANT.generations
        ])
        s_mid = mg.predict_share(a, b, t_mid, t_max)
        # at the announced midpoint the prior-backed fit predicts ~50%
        np.testing.assert_allclose(
            np.asarray(jnp.diagonal(s_mid)), 0.5, atol=0.1
        )

    def test_transform_and_compose_roundtrip(self, fleet):
        """compose_forecast(transform totals, true shares) reproduces the
        per-pool series."""
        _, pools = fleet
        edges = gn.migration_edges(pools.keys, PLANT)
        d = jnp.asarray(pools.demand)
        totals = mg.transform_for_fit(d, edges)
        z, _ = mg.share_observations(d, edges)
        shares = jax.nn.sigmoid(z)
        out = np.asarray(mg.compose_forecast(totals, shares, edges))
        np.testing.assert_allclose(
            out, np.asarray(d), rtol=1e-3, atol=1e-2
        )


class TestShareForecast:
    def test_reduces_error_on_migrating_pools(self):
        """Acceptance: mid-migration, the share-based forecaster beats the
        raw per-pool structural fit on the migrating pools (summed
        weighted MAPE over each turnover pair)."""
        pools = traces.synthetic_pool_set(
            num_pools=4, num_hours=24 * 7 * 80, seed=3, migration=PLANT
        )
        edges = gn.migration_edges(pools.keys, PLANT)
        h = 8 * WK
        hist = jnp.asarray(pools.demand[:, :-h], jnp.float32)
        actual = jnp.asarray(pools.demand[:, -h:], jnp.float32)
        t_fut = hist.shape[-1] + jnp.arange(h)
        cfg = fc.ForecastConfig()

        raw = fc.predict_batched(fc.fit_batched(hist, cfg), t_fut)

        t_max = float(hist.shape[-1] - 1)
        tot = fc.predict_batched(
            fc.fit_batched(mg.transform_for_fit(hist, edges), cfg), t_fut
        )
        a, b = mg.fit_share(hist, edges, t_max=t_max, prior_weight=100.0)
        sh = mg.predict_share(a, b, t_fut, t_max)
        composed = mg.compose_forecast(jnp.asarray(tot), sh, edges)

        err_raw = np.asarray(fc.weighted_mape(actual, jnp.asarray(raw)))
        err_mig = np.asarray(fc.weighted_mape(actual, composed))
        migrating = sorted(
            set(np.asarray(edges.src)) | set(np.asarray(edges.dst))
        )
        # A pair whose turnover already completed forecasts ~identically
        # either way; what must improve is the migrating fleet as a whole.
        assert err_mig[migrating].sum() < err_raw[migrating].sum()


class TestConvertibleOptions:
    def test_rates_carry_the_haircut(self):
        conv = pf.convertible_options_from_pricing(["aws"])
        std = pf.options_from_pricing(clouds=["aws"])
        for term in (52, 156):
            c = [o for o in conv if o.term_weeks == term]
            s = [o for o in std if o.term_weeks == term]
            assert len(c) == 1 and all(o.convertible for o in c)
            # convertible is pricier than the cloud's mean standard rate
            mean_std = sum(o.rate for o in s) / len(s)
            assert c[0].rate > mean_std
        # but still far below on-demand
        assert all(o.rate < 2.0 for o in conv)

    def test_resolve_variants(self):
        clouds = ("aws", "gcp", "aws")
        assert pf.resolve_convertible(None, clouds) is None
        assert pf.resolve_convertible(False, clouds) is None
        got = pf.resolve_convertible(True, clouds)
        assert {o.cloud for o in got} == {"aws", "gcp"}
        assert pf.resolve_convertible(got, clouds) == got
        # an empty list means "no convertible SKUs" = disabled, not a
        # zero-option solve that would crash downstream
        assert pf.resolve_convertible([], clouds) is None
        with pytest.raises(TypeError):
            pf.resolve_convertible(pf.options_from_pricing(), clouds)

    def test_allocate_convertible_scarce(self):
        """Width below the cloud's total need: everything is handed out,
        proportionally, never past any pool's need, never across clouds."""
        member = jnp.asarray([[1.0, 1.0, 0.0], [0.0, 0.0, 1.0]])
        need = np.asarray([4.0, 20.0, 2.0])
        alloc = np.asarray(pf.allocate_convertible(
            jnp.asarray([12.0, 1.5]), jnp.asarray(need), member,
        ))
        assert (alloc <= need + 1e-5).all()
        np.testing.assert_allclose(
            np.asarray(member) @ alloc, [12.0, 1.5], atol=1e-4
        )

    def test_allocate_convertible_surplus_idles(self):
        """Width beyond the cloud's need: every pool is filled to its need
        and the leftover stays unallocated (it bills either way)."""
        member = jnp.asarray([[1.0, 1.0, 0.0], [0.0, 0.0, 1.0]])
        need = np.asarray([2.0, 20.0, 2.0])
        alloc = np.asarray(pf.allocate_convertible(
            jnp.asarray([30.0, 5.0]), jnp.asarray(need), member,
        ))
        np.testing.assert_allclose(alloc, need, atol=1e-3)

    def test_convertible_ladder_book_keys(self):
        targets = np.zeros((2, 3, 1), np.float32)
        targets[:, 0, 0] = [5.0, 7.0]
        book = ld.convertible_ladder_book(
            targets, np.asarray([52 * WK]), ["aws", "gcp"]
        )
        assert book.keys == (
            ("aws", "*", "convertible"), ("gcp", "*", "convertible"),
        )
        np.testing.assert_allclose(
            book.option_widths(0, 1)[:, 0], [5.0, 7.0]
        )


class TestRollingMigrationConvertible:
    @pytest.fixture(scope="class")
    def fleet(self):
        plant = gn.MigrationConfig(generations=(
            pricing.Generation("aws", "C6i", "C7i", 8, 12.0, 0.25),
            pricing.Generation(
                "gcp", "N2-Standard", "N4-Standard", 16, 10.0, 0.50
            ),
        ))
        pools = traces.synthetic_pool_set(
            num_pools=4, num_hours=24 * 7 * 30, seed=3, migration=plant
        )
        return plant, pools

    @pytest.fixture(scope="class")
    def report(self, fleet):
        plant, pools = fleet
        return pl.plan_fleet_pools(
            pools, mode="rolling", cadence_weeks=2, start_weeks=8,
            horizon_weeks=6, compare=False, migration=plant,
            convertible=True,
        )

    def test_report_fields_and_accounting(self, report):
        s, c, kc = report.conv_targets.shape
        assert s == len(report.weeks)
        assert c == len(report.conv_clouds)
        assert kc == len(report.conv_options)
        assert report.conv_alloc.shape == report.committed_cost.shape
        want = float(
            report.committed_cost.sum() + report.on_demand_cost.sum()
            + report.conv_committed_cost.sum()
        )
        assert report.total_cost == pytest.approx(want, rel=1e-6)
        assert report.weekly_cost.sum() == pytest.approx(want, rel=1e-6)
        assert report.migration_edges.num_edges == 2

    def test_conv_ladder_reconciles_with_scan(self, report):
        """Acceptance: the cloud-level convertible book's live widths must
        equal the scan's carried cloud-level stack every week."""
        for i, w in enumerate(report.weeks):
            want = report.conv_active[i]
            got = report.conv_ladders.option_widths(
                int(w) * WK, len(report.conv_options)
            )
            np.testing.assert_allclose(got, want, atol=1e-4)

    def test_standard_ladder_reconciles_under_suppression(self, report):
        """Live convertible capacity suppresses standard purchases, so the
        book replays the realized stack — and must still match it."""
        for i, w in enumerate(report.weeks):
            got = report.ladders.option_widths(
                int(w) * WK, len(report.options)
            )
            np.testing.assert_allclose(got, report.active[i], atol=1e-4)

    def test_conv_allocation_stays_inside_cloud(self, report):
        member = np.asarray([
            [1.0 if c == k[0] else 0.0 for k in report.keys]
            for c in report.conv_clouds
        ])
        for i in range(len(report.weeks)):
            per_cloud = member @ report.conv_alloc[i]
            width = report.conv_active[i].sum(-1)
            assert (per_cloud <= width + 1e-3).all()

    def test_scan_matches_loop(self, fleet):
        plant, pools = fleet
        kw = dict(
            mode="rolling", cadence_weeks=2, start_weeks=8,
            horizon_weeks=4, compare=False, migration=plant,
            convertible=True,
        )
        scan = pl.plan_fleet_pools(pools, backend="scan", **kw)
        loop = pl.plan_fleet_pools(pools, backend="loop", **kw)
        np.testing.assert_allclose(
            scan.total_cost, loop.total_cost, rtol=1e-4
        )

    def test_grid_solver_close_to_quantile(self, fleet, report):
        plant, pools = fleet
        grid = pl.plan_fleet_pools(
            pools, mode="rolling", cadence_weeks=2, start_weeks=8,
            horizon_weeks=6, compare=False, migration=plant,
            convertible=True, solver="grid", num_grid=128,
        )
        assert grid.total_cost == pytest.approx(
            report.total_cost, rel=0.02
        )

    def test_one_shot_carries_conv_fields(self, fleet):
        plant, pools = fleet
        plan = pl.plan_fleet_pools(
            pools, horizon_weeks=6, migration=plant, convertible=True
        )
        assert plan.migration_edges.num_edges == 2
        assert plan.conv_widths.shape == (
            len(plan.conv_clouds), len(plan.conv_options)
        )
        assert plan.conv_cost >= 0.0
        assert plan.conv_ladders.keys[0][2] == "convertible"
        # accounting: conv spend is part of the reported total
        base = sum(float(e.spend.committed.sum()) for e in plan.per_pool)
        od = sum(e.spend.on_demand for e in plan.per_pool)
        assert plan.total_cost == pytest.approx(
            base + od + plan.conv_cost, rel=1e-6
        )


# The golden fleet of the migration=None / convertible=None contract.  The
# disabled spellings must be the default program bit for bit; the facts
# pinned below are the ones no toolchain moves (shapes, None-ness,
# decision weeks), and totals are held to a float64 re-bill or to the
# python-loop replay.
GOLDEN_POOLS = dict(num_pools=4, num_hours=24 * 7 * 24, seed=5)
GOLDEN_ROLLING = dict(cadence_weeks=2, start_weeks=8, horizon_weeks=4)
#: scan vs ``backend="loop"``: the two replays differ only in the float32
#: summation order of the prefix normal equations.
LOOP_RTOL = 2e-4
#: reported one-shot total vs a float64 re-bill of the plan's own stacks.
REBILL_RTOL = 1e-6
GOLDEN_STACK_COST = [78608.2421875, 72014.28125, 75383.375]
GOLDEN_GRID_COST = [78648.7578125, 72030.34375, 75404.921875]


def _rolling_off(pools, off, **kw):
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        return pl.plan_fleet_pools(
            pools, mode="rolling", compare=False, migration=off,
            convertible=off, **GOLDEN_ROLLING, **kw,
        )


class TestMigrationDisabledBitIdentical:
    """Satellite: migration=None / convertible=None are the default
    program on every path — one-shot, rolling, grid and stacked-quantile
    solvers — mirroring the spot=None goldens."""

    @pytest.fixture(scope="class")
    def pools(self):
        return traces.synthetic_pool_set(**GOLDEN_POOLS)

    @pytest.fixture(scope="class")
    def one_shot_default(self, pools):
        return pl.plan_fleet_pools(pools, horizon_weeks=4)

    @pytest.fixture(scope="class")
    def rolling_default(self, pools):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            return pl.plan_fleet_pools(
                pools, mode="rolling", compare=False, **GOLDEN_ROLLING
            )

    @pytest.mark.parametrize("off", [None, False])
    def test_one_shot_golden(self, pools, one_shot_default, off):
        plan = pl.plan_fleet_pools(
            pools, horizon_weeks=4, migration=off, convertible=off
        )
        assert plan.total_cost == one_shot_default.total_cost
        np.testing.assert_array_equal(plan.widths, one_shot_default.widths)
        assert plan.widths.shape == (4, len(pf.options_from_pricing()))
        hours = 4 * HOURS_PER_WEEK
        actual = np.asarray(pools.demand[:, -hours:], np.float64)
        rates = np.asarray(
            [o.rate for o in pf.options_from_pricing()], np.float64
        )
        widths = plan.widths.astype(np.float64)
        rebill = (rates * widths).sum() * hours + (
            pricing.on_demand_premium()
            * np.maximum(actual - widths.sum(1)[:, None], 0.0).sum()
        )
        np.testing.assert_allclose(plan.total_cost, rebill, rtol=REBILL_RTOL)
        assert plan.migration_edges is None
        assert plan.conv_options is None
        assert plan.conv_widths is None
        assert plan.conv_cost == 0.0

    @pytest.mark.parametrize("off", [None, False])
    def test_rolling_golden(self, pools, rolling_default, off):
        rep = _rolling_off(pools, off)
        assert rep.total_cost == rolling_default.total_cost
        np.testing.assert_array_equal(rep.targets, rolling_default.targets)
        np.testing.assert_array_equal(
            rep.increments, rolling_default.increments
        )
        np.testing.assert_array_equal(rep.weeks, np.arange(8, 24))
        np.testing.assert_array_equal(
            rep.decision_mask, np.arange(16) % 2 == 0
        )
        assert rep.conv_options is None
        assert rep.conv_active is None
        assert rep.migration_edges is None

    def test_rolling_golden_matches_loop_replay(self, pools, rolling_default):
        loop = _rolling_off(pools, None, backend="loop")
        np.testing.assert_allclose(
            rolling_default.total_cost, loop.total_cost, rtol=LOOP_RTOL
        )

    def test_rolling_grid_golden(self, pools):
        rep = _rolling_off(pools, None, solver="grid", num_grid=64)
        loop = _rolling_off(
            pools, None, solver="grid", num_grid=64, backend="loop"
        )
        assert rep.targets.shape == (16, 4, len(pf.options_from_pricing()))
        np.testing.assert_array_equal(
            rep.decision_mask, np.arange(16) % 2 == 0
        )
        np.testing.assert_allclose(
            rep.total_cost, loop.total_cost, rtol=LOOP_RTOL
        )
        np.testing.assert_allclose(
            float(rep.increments.sum()), float(loop.increments.sum()),
            rtol=LOOP_RTOL,
        )

    def test_solver_goldens(self):
        rng = np.random.default_rng(17)
        f = jnp.asarray(rng.gamma(2.0, 40.0, (3, 600)).astype(np.float32))
        opts = pf.options_from_pricing()
        al, be, _ = pf.pool_option_lines(opts, ("aws", "azure", "gcp"))
        stack = jax.vmap(
            lambda f_, a_, b_: pf.optimal_portfolio_stack(
                f_, a_, b_, od_rate=2.1
            )
        )(f, al, be)
        np.testing.assert_allclose(
            np.asarray(stack.cost, np.float64), GOLDEN_STACK_COST,
            rtol=1e-6,
        )
        grid = pf.optimal_portfolio_grid(f, al, be, od_rate=2.1, num_grid=64)
        np.testing.assert_allclose(
            np.asarray(grid.cost, np.float64), GOLDEN_GRID_COST, rtol=1e-6
        )


class TestTwoTurnoverAcceptance:
    """Acceptance: on a synthetic 3-year fleet with two family turnovers,
    migration-aware rolling with convertible commitments beats the
    migration-blind rolling plan by >= 5% (the planner sees the turnover
    window, the blind baseline keeps buying on dying families)."""

    @pytest.fixture(scope="class")
    def reports(self):
        two = gn.MigrationConfig(generations=(
            pricing.Generation("aws", "C6i", "C7i", 30, 40.0, 0.25),
            pricing.Generation(
                "gcp", "N2-Standard", "N4-Standard", 85, 36.0, 0.50
            ),
        ))
        pools = traces.synthetic_pool_set(
            num_pools=4, num_hours=24 * 7 * 156, seed=7, migration=two
        )
        kw = dict(
            mode="rolling", cadence_weeks=2, start_weeks=26,
            horizon_weeks=52, compare=False,
        )
        blind = pl.plan_fleet_pools(pools, **kw)
        aware = pl.plan_fleet_pools(
            pools, migration=two, convertible=True, **kw
        )
        return blind, aware

    def test_margin_at_least_5pct(self, reports):
        blind, aware = reports
        margin = 1.0 - aware.total_cost / blind.total_cost
        assert margin >= 0.05, f"margin {margin:.3f} below 5%"

    def test_convertible_capacity_was_bought_and_pinned(self, reports):
        _, aware = reports
        assert float(aware.conv_active[-1].sum()) > 1.0
        assert float(aware.conv_alloc.sum()) > 0.0
        # the convertible band suppressed some standard purchases
        assert aware.conv_committed_cost.sum() > 0.0
