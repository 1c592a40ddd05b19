"""Fleet simulator: turns (training jobs + serving fleets) into chip-demand
traces, then runs the paper's full §3 pipeline against them.

This is where the Shaved Ice technique becomes a first-class framework
feature: the training runtime reports chips-per-job, the serving runtime
reports chips-per-replica x autoscaled replica counts, the simulator rolls
them into an hourly chip-demand series, and the planner (core.planner)
prices commitments for the fleet.
"""

from __future__ import annotations

import dataclasses
from collections import defaultdict

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.core import demand as dm
from repro.core import planner as pl
from repro.core import portfolio as pf
from repro.core import timeshift as ts
from repro.capacity import generations as gn
from repro.capacity import preemption as pe
from repro.capacity import pricing
from repro.capacity.pricing import on_demand_premium
from repro.data import scenarios as sc
from repro.models.model import build

pricing.validate_tables()


@dataclasses.dataclass(frozen=True)
class ServingFleet:
    """A served architecture: replicas autoscale with request demand.

    ``pool`` pins the fleet's chips to one (cloud, region, machine-family)
    pool — the granularity commitments are actually purchased at (§6).
    None falls back to a deterministic slot in the default pool catalog."""

    arch: str
    chips_per_replica: int
    tokens_per_sec_per_replica: float
    base_requests_per_hour: float
    demand_cfg: dm.DemandConfig = dataclasses.field(
        default_factory=lambda: dm.DemandConfig(base_level=1.0)
    )
    pool: dm.PoolKey | None = None


@dataclasses.dataclass(frozen=True)
class TrainingJob:
    """A scheduled training run: a block of chips for a window of hours."""

    arch: str
    chips: int
    start_hour: int
    duration_hours: int
    deferrable: bool = False
    deadline_slack_hours: int = 0
    pool: dm.PoolKey | None = None


def default_pool_catalog() -> list[dm.PoolKey]:
    """12 (cloud, region, machine-family) pools drawn from the Table-2 SKUs
    — the pool granularity the released dataset keys demand by, so fleet
    plans can answer per-cloud/per-region commitment questions."""
    regions = ["region_0", "region_1", "region_2", "region_3"]
    plans = list(pricing.SAVINGS_PLANS)
    catalog = [
        (p.cloud, regions[i % len(regions)], p.family)
        for i, p in enumerate(plans)
    ]
    catalog += [
        (p.cloud, regions[(i + 1) % len(regions)], p.family)
        for i, p in enumerate(plans[:4])
    ]
    return catalog


def default_fleet() -> tuple[list[ServingFleet], list[TrainingJob]]:
    """A fleet spanning the assigned architectures: chips-per-replica scales
    with parameter count (bf16 weights + KV/state under ~12 GB/chip).
    Every fleet/job is pinned to a pool from the default catalog."""
    catalog = default_pool_catalog()
    fleets = []
    for i, arch in enumerate(sorted(configs.ARCHS)):
        n = build(configs.get(arch)).num_params()
        chips = max(1, int(np.ceil(n * 2 / (12 * 1024**3))))
        fleets.append(ServingFleet(
            arch=arch,
            chips_per_replica=chips,
            tokens_per_sec_per_replica=5e4 / chips,
            base_requests_per_hour=50.0 * chips,
            pool=catalog[i % len(catalog)],
        ))
    jobs = [
        TrainingJob("stablelm-1.6b", chips=64, start_hour=24 * 7,
                    duration_hours=24 * 5, pool=catalog[10]),
        TrainingJob("internlm2-20b", chips=256, start_hour=24 * 30,
                    duration_hours=24 * 14, pool=catalog[11]),
        TrainingJob("jamba-v0.1-52b", chips=512, start_hour=24 * 60,
                    duration_hours=24 * 21, pool=catalog[6]),
    ]
    return fleets, jobs


def fleet_pool_demand(
    fleets: list[ServingFleet],
    jobs: list[TrainingJob],
    num_hours: int,
    *,
    seed: int = 0,
    migration: "gn.MigrationConfig | bool | None" = None,
) -> dm.PoolSet:
    """Hourly chip demand of the fleet, attributed per pool.

    Each serving fleet / training job lands in its own (cloud, region,
    machine-family) pool instead of being summed into one series — the
    native shape for the batched planner.  Unpinned members fall back to a
    deterministic catalog slot so attribution is reproducible.

    ``migration`` runs the attributed demand through the hardware-
    generation turnover model (``capacity.generations``): wherever the
    catalog holds both an old family and its successor in one (cloud,
    region), demand volume transfers along the logistic adoption curve and
    the software-efficiency deflator acts on every pool.  ``None``
    (default) keeps attribution bit-identical to the pre-migration path."""
    import jax

    catalog = default_pool_catalog()
    per_pool: dict[dm.PoolKey, np.ndarray] = defaultdict(
        lambda: np.zeros(num_hours, np.float64)
    )
    for i, fl in enumerate(fleets):
        req = np.asarray(dm.synth_demand(
            num_hours, fl.demand_cfg, key=jax.random.PRNGKey(seed + i)
        ))
        req = req / req.mean() * fl.base_requests_per_hour
        # replicas needed to serve the request rate (ceil'd, autoscaled)
        replicas = np.ceil(req / 50.0)
        key = fl.pool if fl.pool is not None else catalog[i % len(catalog)]
        per_pool[tuple(key)] += replicas * fl.chips_per_replica
    for j, job in enumerate(jobs):
        lo = min(job.start_hour, num_hours)
        hi = min(job.start_hour + job.duration_hours, num_hours)
        key = job.pool if job.pool is not None else catalog[j % len(catalog)]
        per_pool[tuple(key)][lo:hi] += job.chips
    pools = dm.PoolSet.from_dict(dict(per_pool))
    mig = gn.resolve_migration(migration)
    if mig is not None:
        pools = gn.migrate_pool_set(pools, mig)
    return pools


def fleet_chip_demand(
    fleets: list[ServingFleet],
    jobs: list[TrainingJob],
    num_hours: int,
    *,
    seed: int = 0,
) -> np.ndarray:
    """Hourly total chip demand of the fleet — the aggregate view, i.e. the
    per-pool demand summed over pools (kept for single-level planning)."""
    return fleet_pool_demand(
        fleets, jobs, num_hours, seed=seed
    ).aggregate().astype(np.float64)


@dataclasses.dataclass
class FleetPlan:
    commitment: float
    on_demand_chip_hours: float
    unused_chip_hours: float
    committed_cost: float
    on_demand_cost: float
    total_cost: float
    all_on_demand_cost: float
    savings_vs_on_demand: float


def plan_fleet(
    demand: np.ndarray,
    *,
    horizon_weeks: int = 8,
    shiftable_frac: float = 0.0,
    portfolio: bool = False,
    options: "list[pf.PurchaseOption] | None" = None,
    term_weighting: float = 0.0,
):
    """Run Algorithm 1 on fleet demand; optionally time-shift the deferrable
    fraction into troughs first (§4) — the full paper pipeline.

    With ``portfolio=True`` the single averaged commitment is replaced by a
    stack of Table-2 purchasing options (returns a ``PortfolioFleetPlan``
    with per-option spend breakdown; see ``plan_fleet_portfolio``;
    ``term_weighting`` > 0 prices term-stranding risk and admits the 1y
    hedge bands onto the stack)."""
    if portfolio:
        return plan_fleet_portfolio(
            demand, horizon_weeks=horizon_weeks,
            shiftable_frac=shiftable_frac, options=options,
            term_weighting=term_weighting,
        )
    hist = jnp.asarray(demand[: -horizon_weeks * 168].astype(np.float32))
    res = pl.plan_commitment(hist, num_horizons=horizon_weeks)
    c = res.commitment

    actual = jnp.asarray(demand[-horizon_weeks * 168:].astype(np.float32))
    if shiftable_frac > 0:
        actual = ts.shift_demand(actual, c, shiftable_frac)

    premium = on_demand_premium()
    over = float(jnp.maximum(actual - c, 0.0).sum())
    under = float(jnp.maximum(c - actual, 0.0).sum())
    hours = actual.shape[0]
    committed_cost = c * hours            # committed rate = 1.0/chip-hour
    od_cost = premium * over
    all_od = premium * float(actual.sum())
    total = committed_cost + od_cost
    return FleetPlan(
        commitment=float(c),
        on_demand_chip_hours=over,
        unused_chip_hours=under,
        committed_cost=committed_cost,
        on_demand_cost=od_cost,
        total_cost=total,
        all_on_demand_cost=all_od,
        savings_vs_on_demand=1.0 - total / all_od,
    )


@dataclasses.dataclass
class PortfolioFleetPlan:
    """Fleet plan built from a stack of Table-2 purchasing options."""

    options: list[pf.PurchaseOption]
    widths: np.ndarray                  # (K,) committed band widths
    total_commitment: float             # stack top
    breakdown: dict[str, float]         # per-option committed spend (nonzero)
    committed_cost: float
    on_demand_cost: float
    total_cost: float
    all_on_demand_cost: float
    savings_vs_on_demand: float
    single_level_cost: float            # the single-level plan, same trace
    savings_vs_single_level: float


def plan_fleet_portfolio(
    demand: np.ndarray,
    *,
    horizon_weeks: int = 8,
    shiftable_frac: float = 0.0,
    options: list[pf.PurchaseOption] | None = None,
    term_weighting: float = 0.0,
) -> PortfolioFleetPlan:
    """§3 pipeline with the Table-2 purchase portfolio instead of one
    averaged commitment level: Algorithm 1 runs once per option term, the
    resulting stack is billed per option at its own committed rate, and the
    result is compared against both all-on-demand and the single-level
    ``plan_fleet`` on the same trace.

    Accounting note: rates are normalized so the mean 3y committed rate is
    1.0 — identical units to ``plan_fleet`` — so ``savings_vs_single_level``
    is an apples-to-apples statement about mixing SKUs (cheaper base-load
    rate + per-term thresholds) rather than a unit artifact."""
    options = options if options is not None else pf.options_from_pricing()
    premium = on_demand_premium()

    hist = jnp.asarray(demand[: -horizon_weeks * 168].astype(np.float32))
    res = pl.plan_portfolio(
        hist, options, num_horizons=horizon_weeks,
        od_rate=premium, term_weighting=term_weighting,
    )
    widths = np.asarray(res.widths)

    actual = jnp.asarray(demand[-horizon_weeks * 168:].astype(np.float32))
    single = plan_fleet(
        demand, horizon_weeks=horizon_weeks, shiftable_frac=shiftable_frac
    )
    if shiftable_frac > 0:
        actual = ts.shift_demand(actual, float(widths.sum()), shiftable_frac)

    spend = pf.portfolio_spend(actual, widths, options, od_rate=premium)
    breakdown = {
        o.name: float(c)
        for o, c in zip(options, spend.committed) if c > 0
    }
    return PortfolioFleetPlan(
        options=options,
        widths=widths,
        total_commitment=float(widths.sum()),
        breakdown=breakdown,
        committed_cost=float(spend.committed.sum()),
        on_demand_cost=spend.on_demand,
        total_cost=spend.total,
        all_on_demand_cost=spend.all_on_demand,
        savings_vs_on_demand=spend.savings_vs_on_demand,
        single_level_cost=single.total_cost,
        savings_vs_single_level=1.0 - spend.total / single.total_cost,
    )


def simulate_and_plan_pools(
    fleets: list[ServingFleet] | None = None,
    jobs: list[TrainingJob] | None = None,
    *,
    num_hours: int = 24 * 7 * 40,
    horizon_weeks: int = 8,
    seed: int = 0,
    demand_migration: "gn.MigrationConfig | bool | None" = None,
    **plan_kw,
) -> tuple[dm.PoolSet, pl.FleetPoolsPlan]:
    """One-call per-pool pipeline: attribute the (default) fleet's demand to
    its (cloud, region, machine-family) pools, then run the batched
    Algorithm-1 portfolio planner over the pool axis.  Returns the PoolSet
    alongside the plan so callers can inspect the traces that produced it.

    ``demand_migration`` is the *generative* turnover switch (demand
    volume actually moves between families); pass ``migration=`` in
    ``plan_kw`` to additionally make the planner migration-aware."""
    if fleets is None or jobs is None:
        d_fleets, d_jobs = default_fleet()
        fleets = d_fleets if fleets is None else fleets
        jobs = d_jobs if jobs is None else jobs
    pools = fleet_pool_demand(
        fleets, jobs, num_hours, seed=seed, migration=demand_migration
    )
    return pools, pl.plan_fleet_pools(
        pools, horizon_weeks=horizon_weeks, **plan_kw
    )


@dataclasses.dataclass
class SpotReplayReport:
    """A spot-enabled plan replayed against sampled revocation paths.

    The planner prices the spot band at an *expected* effective rate; this
    report is the realized counterpart: for ``num_draws`` Monte-Carlo
    revocation paths, demand routed above the spot floor is billed at the
    market spot price while the slice is up, falls back to on-demand while
    it is revoked, and pays the requeue/recompute penalty on every
    revocation of a serving slice.  ``availability`` is demand-weighted —
    1 - (spot demand-hours caught by a revoked slice) / (all demand-hours)
    — the quantity the chance constraint promises stays >= the target."""

    num_draws: int
    availability_target: float
    availability: np.ndarray        # (N, P) realized per draw per pool
    mean_availability: np.ndarray   # (P,) mean over draws
    fleet_availability: float       # demand-weighted, mean over draws
    meets_target: bool              # min over pools of mean availability
    shortfall_chip_hours: float     # mean over draws, fleet total
    planned_cost: float             # the plan's expected-rate bill
    realized_cost: float            # mean over draws
    realized_spot_cost: float       # market-price spot bill, mean
    fallback_on_demand_cost: float  # revoked-hours od fallback, mean
    requeue_cost: float             # recompute penalty, mean


def replay_spot_plan(
    pools: dm.PoolSet,
    report,
    *,
    num_draws: int = 32,
    seed: int = 0,
    scenario: int = 0,
) -> SpotReplayReport:
    """Replay a spot-enabled rolling plan against sampled revocation paths.

    ``report`` is a :class:`repro.core.replan.RollingPlanReport` produced
    with ``spot=...`` on the same ``pools``.  Weekly committed levels and
    spot floors are broadcast back to hours, ``num_draws`` revocation paths
    are sampled from the per-cloud two-state process, and the realized
    three-way bill (committed / on-demand / spot + fallback + requeue) is
    accounted per draw.

    On a scenario-batched report (``scenarios=`` on the plan request)
    ``scenario`` selects which demand future to replay: its floors and
    base costs are sliced off the report's N axis, and for ``scenario >
    0`` the demand path itself is regenerated from the report's
    ``scenario_config`` (scenario batches are pure functions of the
    realized trace + config, so the replayed path is exactly the one the
    scan billed).  Scenario 0 — the realized trace — is the default and
    the only valid index on unbatched reports."""
    if report.spot_floor is None:
        raise ValueError("report has no spot band; re-plan with spot=...")
    cfg, lines = report.spot_config, report.spot_lines
    wk = dm.HOURS_PER_WEEK
    batched = np.asarray(report.spot_floor).ndim == 3    # (S, N, P)
    n_scen = report.n_scenarios if batched else 1
    if not 0 <= scenario < n_scen:
        raise ValueError(
            f"scenario index {scenario} out of range for a report with "
            f"{n_scen} scenario(s)"
        )

    def _pick(a):
        """Scenario view of a per-week report array."""
        a = np.asarray(a)
        return a[:, scenario] if batched else a

    spot_floor = _pick(report.spot_floor)
    s, p = spot_floor.shape
    if batched:
        # The report's spot lines were built for the flattened (N x P)
        # row axis; every leaf is per-row, so one tree-slice recovers
        # this scenario's (P,) block.
        blk = slice(scenario * p, (scenario + 1) * p)
        lines = jax.tree_util.tree_map(lambda a: a[blk], lines)
    t0 = report.start_weeks * wk
    if scenario == 0:
        demand = np.asarray(pools.demand[:, t0: t0 + s * wk], np.float32)
    else:
        # Re-derive the scenario's demand path: scenario_batch is a pure
        # function of (realized trace, config), built on the device by the
        # same module the replay's rows came from, and a one-scenario
        # slice is the same rows as the whole batch's; np.asarray of it
        # is the exact rows the scan saw.
        t_hist = (pools.num_hours // wk) * wk
        batch = sc.scenario_batch(
            pools.demand[:, :t_hist], report.scenario_config,
            scenario, scenario + 1,
        )
        demand = np.asarray(batch[0, :, t0: t0 + s * wk], np.float32)
    floor = np.repeat(spot_floor.T, wk, axis=1)
    spot_dem = np.maximum(demand - floor, 0.0)            # (P, T)

    paths = pe.simulate_revocations(
        lines.params, s * wk, num_draws=num_draws,
        key=jax.random.PRNGKey(seed),
    )
    up = np.asarray(paths.available)                      # (N, P, T)
    price = np.asarray(paths.price)

    served = spot_dem[None] * up
    fallback = spot_dem[None] * (1.0 - up)
    od = on_demand_premium()
    market = np.asarray(lines.market_rate)[None, :, None]
    spot_bill = (market * price * served).sum(-1)         # (N, P)
    fallback_bill = od * fallback.sum(-1)
    requeue_bill = od * np.asarray(
        pe.requeue_cost_hours(paths, spot_dem, cfg.requeue_hours)
    )

    total_dem = np.maximum(demand.sum(-1), 1e-9)          # (P,)
    avail = 1.0 - fallback.sum(-1) / total_dem            # (N, P)
    fleet_avail = float(
        1.0 - fallback.sum((-1, -2)).mean() / total_dem.sum()
    )
    # The committed + convertible + mid-band on-demand bill is path
    # independent — read it off the report rather than re-deriving the
    # replanner's billing here.
    base = float(
        _pick(report.committed_cost).sum()
        + _pick(report.on_demand_cost).sum()
    )
    if report.conv_committed_cost is not None:
        base += float(_pick(report.conv_committed_cost).sum())
    realized = base + float(
        (spot_bill + fallback_bill + requeue_bill).sum(-1).mean()
    )
    planned = (
        float(report.scenario_cost[scenario]) if batched
        else report.total_cost
    )
    mean_avail = avail.mean(0)
    return SpotReplayReport(
        num_draws=num_draws,
        availability_target=cfg.availability_target,
        availability=avail,
        mean_availability=mean_avail,
        fleet_availability=fleet_avail,
        meets_target=bool(mean_avail.min() >= cfg.availability_target),
        shortfall_chip_hours=float(fallback.sum((-1, -2)).mean()),
        planned_cost=planned,
        realized_cost=realized,
        realized_spot_cost=float(spot_bill.sum(-1).mean()),
        fallback_on_demand_cost=float(fallback_bill.sum(-1).mean()),
        requeue_cost=float(requeue_bill.sum(-1).mean()),
    )


def simulate_and_replan_pools(
    fleets: list[ServingFleet] | None = None,
    jobs: list[TrainingJob] | None = None,
    *,
    num_hours: int = 24 * 7 * 60,
    cadence_weeks: int = 1,
    horizon_weeks: int = 8,
    seed: int = 0,
    **replan_kw,
):
    """The rolling counterpart of :func:`simulate_and_plan_pools`: attribute
    the fleet's demand to its pools, then *replay* the weekly re-planning
    loop over the whole simulated window (re-fit, re-solve, buy increments,
    roll tranches off) instead of fitting once against a holdout.  Returns
    ``(PoolSet, repro.core.replan.RollingPlanReport)`` — the report carries
    the one-shot and hindsight baselines for the same window.  Pass
    ``spot=...`` to add the preemptible band, then hand the report to
    :func:`replay_spot_plan` to price it against sampled revocation
    paths."""
    return simulate_and_plan_pools(
        fleets, jobs, num_hours=num_hours, horizon_weeks=horizon_weeks,
        seed=seed, mode="rolling", cadence_weeks=cadence_weeks, **replan_kw,
    )
