"""One benchmark per paper table/figure.  Each function returns CSV rows
(name, us_per_call, derived) where `derived` is the headline number the
paper's table/figure reports."""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import commitment as cm
from repro.core import demand as dm
from repro.core import forecast as fc
from repro.core import freepool as fp
from repro.core import ladder as ld
from repro.core import planner as pl
from repro.core import portfolio as pt
from repro.core import timeshift as ts
from repro.core.demand import HOURS_PER_WEEK

Row = tuple[str, float, str]


def _time(fn, *args, iters=5, warmup=2) -> float:
    """Wall time per call in microseconds (after jit warmup)."""
    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


def bench_demand_characterization(quick: bool = False, seed: int = 0) -> list[Row]:
    """Paper §2.2 / Figs 2,5,7: dataset statistics of the calibrated trace."""
    trace = dm.synth_demand(
        24 * 365 if quick else 24 * 365 * 3, key=jax.random.PRNGKey(seed + 7)
    )
    us = _time(lambda t: dm.hourly_to_daily(t), trace)
    stats = dm.characterize(np.asarray(trace))
    return [
        ("fig2_lag7_autocorr", us, f"{stats['lag7_daily_autocorr']:.3f}"),
        ("fig2_weekly_peak_trough", us, f"{stats['weekly_ratio']:.2f}x"),
        ("fig2_diurnal_peak_trough", us, f"{stats['diurnal_ratio']:.2f}x"),
        ("fig5_neg_week_fraction", us, f"{stats['neg_week_fraction']:.2f}"),
        ("fig2_3yr_growth", us, f"{stats['total_growth']:.1f}x"),
    ]


def bench_commitment_fig4(quick: bool = False, seed: int = 0) -> list[Row]:
    """Paper Fig 4: 9 commitment scenarios over two weeks, A=2.1, B=1."""
    f = dm.synth_demand(
        24 * 14, dm.DemandConfig(annual_growth=0.0, noise_sigma=0.005),
        key=jax.random.PRNGKey(seed + 1),
    )
    levels, costs, best = cm.scenario_costs(f, 9)
    us = _time(lambda x: cm.scenario_costs(x, 9)[1], f)
    exact = float(cm.optimal_commitment_quantile(f))
    brent = cm.optimal_commitment_brent(np.asarray(f))
    return [
        ("fig4_best_scenario_of_9", us, f"scenario {int(best)+1}"),
        ("fig4_exact_optimum_quantile", us,
         f"c*={exact:.1f} (q=A/(A+B)={2.1/3.1:.3f})"),
        ("fig4_brent_agreement", us,
         "|brent-exact| cost delta "
         f"{abs(float(cm.commitment_cost(f, brent)) - float(cm.commitment_cost(f, exact))):.2f}"),
    ]


def bench_sensitivity_table3(quick: bool = False, seed: int = 0) -> list[Row]:
    """Paper Table 3: cost delta per $1M when the commitment is computed
    from a trend-blind forecast instead of actuals, by trend x update freq."""
    rows: list[Row] = []
    base = dm.synth_demand(
        HOURS_PER_WEEK, dm.DemandConfig(annual_growth=0.0, noise_sigma=0.0)
    )
    t0 = time.perf_counter()
    for update_weeks in (1, 2) if quick else (1, 2, 4, 8):
        for trend in (0.10, 0.50, 1.00):
            hours = update_weeks * HOURS_PER_WEEK
            growth = (1.0 + trend) ** (
                jnp.arange(hours, dtype=jnp.float32) / (24 * 365)
            )
            actual = jnp.tile(base, update_weeks) * growth
            naive = jnp.tile(base, update_weeks)  # trend-blind forecast
            c_actual = cm.optimal_commitment_quantile(actual)
            c_naive = cm.optimal_commitment_quantile(naive)
            cost_actual = float(cm.commitment_cost(actual, c_actual))
            cost_naive = float(cm.commitment_cost(actual, c_naive))
            delta_per_m = (cost_naive - cost_actual) / cost_actual * 1e6
            rows.append((
                f"table3_u{update_weeks}w_trend{int(trend*100)}",
                0.0,
                f"${delta_per_m:.2f} per $1M",
            ))
    us = (time.perf_counter() - t0) / len(rows) * 1e6
    return [(n, us, d) for n, _, d in rows]


def bench_planner_fig8(quick: bool = False, seed: int = 0) -> list[Row]:
    """Paper Fig 8: 1-week vs 2-week forecast horizon commitment, evaluated
    over the 2-week window containing a holiday dip."""
    hist = dm.synth_demand(
        24 * 7 * (8 if quick else 20), key=jax.random.PRNGKey(seed + 3)
    )
    res = pl.plan_commitment(hist, num_horizons=4)
    base = dm.synth_demand(
        HOURS_PER_WEEK * 2, dm.DemandConfig(annual_growth=0.0,
                                            noise_sigma=0.0))
    dip = jnp.concatenate([
        jnp.ones(HOURS_PER_WEEK),
        jnp.full((HOURS_PER_WEEK,), 0.88),  # holiday week: -12% demand
    ])
    yhat = base * dip
    out = pl.compare_horizons(yhat, (1, 2))
    us = _time(lambda h: pl.plan_commitment(h, num_horizons=4).forecast, hist,
               iters=2, warmup=1)
    return [
        ("fig8_c_w1_level", us, f"{out[1]['level']:.1f}"),
        ("fig8_c_w2_level", us, f"{out[2]['level']:.1f}"),
        ("fig8_2wk_cheaper_by", us,
         f"{(out[1]['total_spend'] - out[2]['total_spend']) / out[1]['total_spend'] * 100:.2f}%"),
        ("alg1_cstar_min_over_horizons", us,
         f"{res.commitment:.1f} (binding horizon w={res.argmin_horizon + 1})"),
    ]


def bench_ladder_fig9(quick: bool = False, seed: int = 0) -> list[Row]:
    """Paper Fig 9: flat vs perfectly-laddered commitment over a 4-week
    window with a year-end demand drop (paper: ~1.1% savings)."""
    demand = np.asarray(dm.synth_demand(
        HOURS_PER_WEEK * 4,
        dm.DemandConfig(annual_growth=0.0, noise_sigma=0.0)))
    demand = demand.copy()
    demand[HOURS_PER_WEEK * 2: HOURS_PER_WEEK * 3] *= 0.92  # holiday week
    t0 = time.perf_counter()
    weekly = [
        float(cm.optimal_commitment_quantile(jnp.asarray(
            demand[w * HOURS_PER_WEEK:(w + 1) * HOURS_PER_WEEK])))
        for w in range(4)
    ]
    out = ld.ladder_vs_flat(demand, np.array(weekly))
    us = (time.perf_counter() - t0) * 1e6
    return [
        ("fig9_flat_vs_laddered_savings", us,
         f"{out['savings_frac'] * 100:.2f}% (paper ~1.1%)"),
    ]


def bench_timeshift_sec4(quick: bool = False, seed: int = 0) -> list[Row]:
    """Paper §4: unused-commitment trough supply and shiftable workloads."""
    f = np.asarray(dm.synth_demand(
        24 * 7 * (12 if quick else 52), key=jax.random.PRNGKey(seed + 4)
    ))
    c = float(cm.optimal_commitment_quantile(jnp.asarray(f)))
    stats = ts.shiftable_supply_stats(f, c)
    # schedule a 5%-of-total deferrable workload into the troughs
    total_work = f.sum() * 0.05
    n_jobs = 12 if quick else 52
    jobs = [
        ts.Job(arrival=int(h), work=float(total_work / n_jobs),
               deadline=int(h) + 24 * 7)
        for h in np.linspace(0, len(f) - 24 * 7 - 1, n_jobs)
    ]
    t0 = time.perf_counter()
    out = ts.schedule_jobs(f, c, jobs)
    us = (time.perf_counter() - t0) * 1e6
    saved_frac = out["on_demand_savings"] / max(out["on_demand_cost_naive"],
                                                1e-9)
    return [
        ("sec4_unused_commitment_frac", us,
         f"{stats['unused_frac'] * 100:.1f}% (paper 4.3%)"),
        ("sec4_weekend_trough_share", us,
         f"{stats['weekend_share'] * 100:.0f}%"),
        ("sec4_timeshift_od_cost_saved", us, f"{saved_frac * 100:.0f}%"),
    ]


def bench_freepool_fig12(quick: bool = False, seed: int = 0) -> list[Row]:
    """Paper Fig 12: static vs predicted free pool on held-out demand."""
    hist = dm.synth_demand(24 * 7 * 8, key=jax.random.PRNGKey(seed + 5))
    fut = dm.synth_demand(24 * 7 * 9, key=jax.random.PRNGKey(seed + 5))[-24 * 7:]
    cfg = fp.FreePoolConfig(p_over=1.0, p_under=10.0, lead_time=1)
    us = _time(
        lambda h: fp.predicted_pool(h, 24 * 7, cfg), hist, iters=3, warmup=1
    )
    out = fp.compare_static_vs_predicted(hist, fut, cfg)
    return [
        ("fig12_static_pool_cost", us, f"{out['static_cost']:.0f}"),
        ("fig12_predicted_pool_cost", us, f"{out['predicted_cost']:.0f}"),
        ("fig12_cost_reduction", us,
         f"{(1 - out['predicted_cost'] / out['static_cost']) * 100:.0f}%"),
        ("fig12_under_minutes_ratio", us,
         f"{out['under_minutes_predicted'] / max(out['under_minutes_static'], 1e-9):.2f}"),
    ]


def bench_forecast_quality(quick: bool = False, seed: int = 0) -> list[Row]:
    """§3.3.3: forecaster asymmetric-error metric on held-out data."""
    n = 12 if quick else 30
    full = dm.synth_demand(24 * 7 * n, key=jax.random.PRNGKey(seed + 6))
    hist, fut = full[: 24 * 7 * (n - 4)], full[24 * 7 * (n - 4):]
    model = fc.fit(hist)
    us = _time(lambda h: fc._fit(h, fc.ForecastConfig(),
                                 float(h.shape[0] - 1)), hist,
               iters=3, warmup=1)
    yhat = fc.forecast_horizon(model, hist.shape[0], fut.shape[0])
    wmape = float(fc.weighted_mape(fut, yhat))
    mape = float(jnp.abs((fut - yhat) / fut).mean())
    return [
        ("forecast_holdout_mape_4wk", us, f"{mape * 100:.1f}%"),
        ("forecast_holdout_wmape_asym", us, f"{wmape * 100:.1f}%"),
    ]


def bench_portfolio_table2(quick: bool = False, seed: int = 0) -> list[Row]:
    """Beyond-paper: Table-2 SKU portfolio vs the single averaged commitment
    level, batched over a fleet of pools.  The exact stacked-quantile solver
    is one sort + K gathers per pool; the grid solver is timed on its jnp
    reference path (the Pallas 2-D sweep behind ``use_kernel=True`` is
    benchmarked in kernel_benches and validated in tests)."""
    n_pools, n_weeks = (4, 8) if quick else (16, 52)
    pools = jnp.stack([
        dm.synth_demand(24 * 7 * n_weeks, key=jax.random.PRNGKey(seed + i))
        for i in range(n_pools)
    ])
    opts = pt.options_from_pricing()
    al, be = pt.option_lines(opts, term_weighting=1.0)
    od = 2.1

    exact = jax.jit(
        lambda f: pt.optimal_portfolio_stack(f, al, be, od_rate=od).cost
    )
    us_exact = _time(exact, pools, iters=3, warmup=1)
    plan = pt.optimal_portfolio_stack(pools, al, be, od_rate=od)

    grid_fn = jax.jit(
        lambda f: pt.optimal_portfolio_grid(f, al, be, od_rate=od).cost
    )
    us_grid = _time(grid_fn, pools, iters=3, warmup=1)

    # Real-dollar comparison (both sides billed in-window at actual rates;
    # the term-weighted *planning* objective is not a billing statement, so
    # the savings headline uses the in-window-optimal tw=0 stack):
    al0, be0 = pt.option_lines(opts, term_weighting=0.0)
    plan0 = pt.optimal_portfolio_stack(pools, al0, be0, od_rate=od)
    port = np.asarray([
        pt.portfolio_spend(
            pools[i], np.asarray(plan0.widths)[i], opts, od_rate=od
        ).total
        for i in range(pools.shape[0])
    ])
    c_single = cm.optimal_commitment_quantile(pools, od - 1.0, 1.0)
    base = np.asarray(
        cm.total_spend(pools, c_single, od)  # rate-1.0 commitment + od over
    )
    saving = float((1.0 - port / base).mean())
    n_opts = int((jnp.asarray(plan.widths) > 0).any(0).sum())
    return [
        ("portfolio_exact_16pools_1y", us_exact,
         f"{n_opts} SKUs on envelope"),
        ("portfolio_grid_16pools_1y", us_grid,
         f"mean saving vs single-level {saving * 100:.1f}%"),
    ]


def bench_tournament(quick: bool = False, seed: int = 0) -> list[Row]:
    """Beyond-paper: the policy tournament (competitive ratio vs per-path
    hindsight across the §2 scenario taxonomy).  Times one compiled
    vmapped replay program per policy; the derived columns are the mean
    competitive ratios the acceptance tests pin (hedging bounds on
    steady, rolling's margin on declining)."""
    from repro.core import tournament as tn

    kw = dict(
        policies=("rolling_portfolio", "deterministic_hedge",
                  "randomized_hedge"),
        families=("steady", "declining"),
        num_pools=2 if quick else 3,
        num_weeks=24 if quick else 48,
        num_seeds=2 if quick else 8,
        base_seed=seed,
        start_weeks=12 if quick else 20,
        cadence_weeks=2,
        horizon_weeks=4 if quick else 8,
    )
    t0 = time.perf_counter()
    rep = tn.run_tournament(**kw)
    rep.elapsed_s = time.perf_counter() - t0
    us = rep.elapsed_s * 1e6
    rows: list[Row] = []
    for pol_name, short in (
        ("rolling_portfolio", "rolling"),
        ("deterministic_hedge", "det_hedge"),
        ("randomized_hedge", "rand_hedge"),
    ):
        for fam in rep.families:
            st = rep.family_stats(pol_name, fam)
            rows.append((
                f"tournament_{short}_{fam}", us,
                f"CR mean {st['cr_mean']:.3f} max {st['cr_max']:.3f}",
            ))
    return rows


def bench_fleet_scale(quick: bool = False, seed: int = 0) -> list[Row]:
    """Beyond-paper: the (N scenarios x P pools) batched rolling replay's
    fleet-scale curve.  One batched ``replan_fleet_pools(scenarios=N)``
    program per P, plus the loop-over-scenarios oracle (N unbatched
    replays) at the middle size — the batched scan's speedup over it is
    the headline.  Gate: scenario 0 of every batched run is BIT-IDENTICAL
    to the unbatched replay at the same P (the flattening contract).

    ``--quick`` (the CI bench-smoke job) runs P in {16, 128} on a short
    trace; the full curve — P in {16, 128, 1024}, N=32, a 3-year weekly
    replan — sits behind ``--filter fleet_scale`` without ``--quick``."""
    import dataclasses

    from repro.core import replan as rp
    from repro.data import scenarios as sc
    from repro.data import traces

    if quick:
        p_sizes, n_scen, hours = (16, 128), 4, 24 * 7 * 20
        kw = dict(cadence_weeks=2, start_weeks=6, horizon_weeks=4,
                  compare=False)
    else:
        p_sizes, n_scen, hours = (16, 128, 1024), 32, 24 * 7 * 156
        kw = dict(cadence_weeks=1, start_weeks=26, horizon_weeks=8,
                  compare=False)
    oracle_p = 128
    cfg = sc.ScenarioConfig(n_scenarios=n_scen, family="growth", seed=seed)

    rows: list[Row] = []
    for p in p_sizes:
        pools = traces.synthetic_pool_set(
            num_pools=p, num_hours=hours, seed=seed
        )
        t0 = time.perf_counter()
        rep = rp.replan_fleet_pools(pools, scenarios=cfg, **kw)
        us_batched = (time.perf_counter() - t0) * 1e6
        t0 = time.perf_counter()
        base = rp.replan_fleet_pools(pools, **kw)
        us_single = (time.perf_counter() - t0) * 1e6
        # The flattening contract: scenario 0 IS the realized replay.
        np.testing.assert_array_equal(rep.targets[:, 0], base.targets)
        np.testing.assert_array_equal(
            float(rep.scenario_cost[0]), base.total_cost
        )
        derived = (
            f"P={p} N={n_scen} {hours // HOURS_PER_WEEK}wk, "
            f"{us_batched / us_single:.1f}x one unbatched replay, "
            f"scenario0 bit-identical"
        )
        rows.append((f"fleet_scale_p{p}", us_batched, derived))
        if p == oracle_p:
            # Loop-over-scenarios oracle: N unbatched replays over the
            # perturbed paths — the program the batched scan replaces.
            batch = np.asarray(sc.scenario_batch(pools.demand, cfg))
            t0 = time.perf_counter()
            per_scen = []
            for s in range(n_scen):
                srep = rp.replan_fleet_pools(
                    dataclasses.replace(pools, demand=batch[s]), **kw
                )
                per_scen.append(srep.total_cost)
            us_loop = (time.perf_counter() - t0) * 1e6
            np.testing.assert_allclose(
                np.asarray(per_scen), rep.scenario_cost, rtol=1e-5
            )
            speedup = us_loop / us_batched
            rows.append((
                f"fleet_scale_p{oracle_p}_vs_loop", us_loop,
                f"batched scan {speedup:.1f}x loop-over-{n_scen}-scenarios",
            ))
    return rows


def bench_breach_cadence(quick: bool = False, seed: int = 0) -> list[Row]:
    """Beyond-paper: breach-triggered replan cadence vs the weekly
    baseline on a steady fleet.  ``cadence="breach"`` re-solves only in
    weeks where realized demand exits the previous decision's forecast
    band, so most weeks carry the standing plan — the headline is the
    decision-week reduction at a near-zero realized-cost delta.  Gates:
    the weekly spelling stays the default program, and breach must
    actually skip decisions (strictly fewer decision weeks than weekly).

    ``--quick`` (the CI bench-smoke job) runs the short fleet; the full
    52-week acceptance configuration sits behind ``--filter breach``
    without ``--quick``."""
    from repro.core import replan as rp
    from repro.data import scenarios as sc

    if quick:
        num_weeks, start_weeks = 26, 12
    else:
        num_weeks, start_weeks = 52, 24
    pools = sc.scenario_pool_set(
        "steady", num_pools=4, num_weeks=num_weeks, seed=seed
    )
    kw = dict(cadence_weeks=1, start_weeks=start_weeks, horizon_weeks=4,
              compare=False)

    t0 = time.perf_counter()
    weekly = rp.replan_fleet_pools(pools, **kw)
    us_weekly = (time.perf_counter() - t0) * 1e6
    t0 = time.perf_counter()
    breach = rp.replan_fleet_pools(pools, cadence="breach", **kw)
    us_breach = (time.perf_counter() - t0) * 1e6

    n_weekly = int(np.asarray(weekly.decision_mask).sum())
    n_breach = int(np.asarray(breach.decision_mask).sum())
    assert n_breach < n_weekly, (
        f"breach cadence skipped nothing: {n_breach} vs {n_weekly}"
    )
    rel = abs(breach.total_cost - weekly.total_cost) / weekly.total_cost
    return [
        ("breach_cadence_weekly", us_weekly,
         f"{n_weekly} decision weeks ({num_weeks}wk steady fleet)"),
        ("breach_cadence_breach", us_breach,
         f"{n_breach} decision weeks "
         f"({1 - n_breach / n_weekly:.0%} fewer), "
         f"cost delta {rel:.2%}"),
    ]


ALL_PAPER_BENCHES = [
    bench_demand_characterization,
    bench_commitment_fig4,
    bench_sensitivity_table3,
    bench_planner_fig8,
    bench_ladder_fig9,
    bench_timeshift_sec4,
    bench_freepool_fig12,
    bench_forecast_quality,
    bench_portfolio_table2,
    bench_tournament,
    bench_fleet_scale,
    bench_breach_cadence,
]
