"""Algorithm 1: Optimal Commitment For Demand Forecast (paper §3.3.3).

Step 1  Fit the forecaster on the hourly training history; forecast 1 year.
Step 2  For each weekly horizon w = 1..52, take the forecast prefix X̂_w.
Step 3  Compute the minimal-cost commitment level c_w over each prefix.
Step 4  c* = min_w c_w  — commitments can be *increased* later but never
        reduced, so the safe level to buy now is the minimum over horizons
        (buying more than some future optimum strands capacity).

All 52 horizon optimizations run as one vectorized pass: with the exact
quantile solver each c_w is a weighted quantile of a prefix, and with the
golden-section solver the 52 prefixes are masked views of one array, batched
under vmap.
"""

from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Literal

import jax
import jax.numpy as jnp
import numpy as np

from repro.capacity import generations as gn
from repro.capacity import pricing
from repro.core import commitment as cm
from repro.core import demand as dm
from repro.core import forecast as fc
from repro.core import ladder as ld
from repro.core import migration as mg
from repro.core import portfolio as pf
from repro.core import spot as spot_mod
from repro.core.demand import HOURS_PER_WEEK

pricing.validate_tables()


@dataclasses.dataclass
class PlanResult:
    commitment: float                 # c* to purchase now
    per_horizon_levels: jnp.ndarray   # (W,) c_w for each horizon
    argmin_horizon: int               # which horizon set the binding level
    forecast: jnp.ndarray             # (W*168,) hourly forecast used


def plan_commitment(
    history: jnp.ndarray,
    *,
    num_horizons: int = 52,
    a: float = cm.DEFAULT_A,
    b: float = cm.DEFAULT_B,
    cfg: fc.ForecastConfig = fc.ForecastConfig(),
    solver: Literal["quantile", "golden"] = "quantile",
) -> PlanResult:
    """Run Algorithm 1 on an hourly demand history."""
    model = fc.fit(history, cfg)
    t0 = history.shape[-1]
    horizon_hours = num_horizons * HOURS_PER_WEEK
    yhat = fc.forecast_horizon(model, t0, horizon_hours)  # Step 1

    w_hours = (jnp.arange(1, num_horizons + 1)) * HOURS_PER_WEEK  # Step 2

    if solver == "quantile":
        # Exact weighted quantile at q = a/(a+b) over each masked prefix —
        # the K=1 instance of the portfolio prefix solver (one shared sort).
        q = jnp.asarray([a / (a + b)], yhat.dtype)
        levels = _prefix_weighted_quantiles(yhat, w_hours, q)[:, 0]  # Step 3
    else:
        def golden_prefix(w):
            t = jnp.arange(yhat.shape[0])
            # Mask out-of-horizon hours by pinning them to the prefix median:
            # they then contribute a c-independent-gradient-free... not exact.
            # For the golden path we instead clamp to the valid min so masked
            # entries never bind the 'over' hinge and contribute a constant
            # slope to 'under'; exactness is restored by subtracting that
            # slope — in practice we simply evaluate cost only on valid hours
            # via where().
            fvals = jnp.where(t < w, yhat, jnp.nan)
            # golden on nan-masked cost:
            lo, hi = jnp.nanmin(fvals), jnp.nanmax(fvals)

            def cost(c):
                over = jnp.where(t < w, jnp.maximum(yhat - c, 0.0), 0.0)
                under = jnp.where(t < w, jnp.maximum(c - yhat, 0.0), 0.0)
                return a * over.sum() + b * under.sum()

            def body(_, st):
                lo, hi = st
                x1 = lo + (hi - lo) * 0.381966
                x2 = lo + (hi - lo) * 0.618034
                sm = cost(x1) < cost(x2)
                return jnp.where(sm, lo, x1), jnp.where(sm, x2, hi)

            lo, hi = jax.lax.fori_loop(0, 60, body, (lo, hi))
            return 0.5 * (lo + hi)

        levels = jax.vmap(golden_prefix)(w_hours)

    c_star = levels.min()  # Step 4
    return PlanResult(
        commitment=float(c_star),
        per_horizon_levels=levels,
        argmin_horizon=int(jnp.argmin(levels)),
        forecast=yhat,
    )


@dataclasses.dataclass
class PortfolioPlanResult:
    """Algorithm 1 generalized to a commitment portfolio (one run per
    option term).  Arrays are aligned with ``options``."""

    options: list[pf.PurchaseOption]
    widths: jnp.ndarray               # (K,) band width to purchase now
    levels: jnp.ndarray               # (K,) stack tops (envelope-monotone)
    per_horizon_levels: jnp.ndarray   # (W, K) per-horizon prefix thresholds
    fractiles: jnp.ndarray            # (K,) per-option critical fractiles
    forecast: jnp.ndarray             # (W*168,) hourly forecast used


def _prefix_weighted_quantiles(
    yhat: jnp.ndarray, w_hours: jnp.ndarray, qs: jnp.ndarray
) -> jnp.ndarray:
    """Thresholds (W, K): for each horizon prefix yhat[:w] the weighted
    quantile at each fractile q — the vectorized heart of Step 3, one sort
    for all horizons x options (same masked-prefix trick as the single-level
    path, broadcast over the portfolio's critical fractiles)."""
    order = jnp.argsort(yhat)
    sorted_y = yhat[order]
    t = jnp.arange(yhat.shape[0])
    sorted_t = t[order]

    def one_horizon(w):
        valid = (sorted_t < w).astype(yhat.dtype)
        cum = jnp.cumsum(valid)
        frac = cum / jnp.maximum(cum[-1], 1.0)
        idx = jnp.argmax(frac[None, :] >= qs[:, None], axis=-1)  # (K,)
        return sorted_y[idx]

    return jax.vmap(one_horizon)(w_hours)


def _prefix_spot_floors(
    yhat: jnp.ndarray, w_hours: jnp.ndarray, cap: jnp.ndarray
) -> jnp.ndarray:
    """(W,) per-horizon spot floor levels: on each prefix yhat[:w], the
    smallest demand level whose above-floor volume fits the chance-
    constraint cap — sum_t max(yhat_t - floor, 0) <= cap * sum_t yhat_t.
    The volume analogue of the weighted-quantile thresholds (same shared
    sort + masked-prefix trick; the floor snaps up to an observed level so
    the cap is never exceeded).  Vmap over pools for per-pool caps."""
    order = jnp.argsort(yhat)
    sorted_y = yhat[order]
    t = jnp.arange(yhat.shape[0])
    sorted_t = t[order]

    def one_horizon(w):
        valid = (sorted_t < w).astype(yhat.dtype)
        v = sorted_y * valid
        suf = jnp.flip(jnp.cumsum(jnp.flip(v)))          # sum_{j >= i} v_j
        cnt = jnp.flip(jnp.cumsum(jnp.flip(valid)))
        # volume above level sorted_y[i], prefix hours only — nonincreasing
        # in i, so the first index inside the cap is the lowest floor.
        va = (suf - v) - sorted_y * (cnt - valid)
        return sorted_y[jnp.argmax(va <= cap * suf[0])]

    return jax.vmap(one_horizon)(w_hours)


def _monotone_stack(
    per_horizon: jnp.ndarray,
    qs: jnp.ndarray,
    term_weeks: jnp.ndarray,
    num_horizons: int,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Step 4 of Algorithm 1 for one pool's option stack.

    per_horizon (W, K) prefix thresholds, qs (K,) critical fractiles ->
    (widths (K,), levels (K,)).  Takes each option's min over the horizons
    within its own term, then re-monotonizes the stack (running max in
    envelope-depth order) since per-option minima over different horizon
    sets can cross.  Pure array code — vmapped over the P pool axis by
    ``plan_fleet_pools``."""
    weeks = jnp.arange(1, num_horizons + 1)[:, None]              # (W, 1)
    in_term = weeks <= jnp.maximum(term_weeks[None, :], 1)
    big = jnp.float32(jnp.inf)
    mins = jnp.where(in_term, per_horizon, big).min(0)            # (K,)
    on_env = qs > 0

    depth = jnp.argsort(jnp.where(on_env, qs, jnp.inf))
    inv = jnp.argsort(depth)
    mins_d = jnp.where(on_env, mins, 0.0)[depth]
    tops_d = jax.lax.associative_scan(jnp.maximum, mins_d)
    prev_d = jnp.concatenate([jnp.zeros((1,), tops_d.dtype), tops_d[:-1]])
    widths_d = jnp.where(on_env[depth], tops_d - prev_d, 0.0)
    return widths_d[inv], tops_d[inv]


def plan_portfolio(
    history: jnp.ndarray,
    options: list[pf.PurchaseOption] | None = None,
    *,
    num_horizons: int = 52,
    od_rate: float = 2.1,
    term_weighting: float = 0.0,
    cfg: fc.ForecastConfig = fc.ForecastConfig(),
    lines: tuple[jnp.ndarray, jnp.ndarray] | None = None,
) -> PortfolioPlanResult:
    """Algorithm 1 with one horizon sweep per purchasing option.

    Steps 1-2 are shared (one forecast, 52 weekly prefixes).  Step 3
    computes each option's optimal stack threshold on every prefix — a
    weighted quantile at the option's critical fractile (portfolio lower
    envelope).  Step 4 takes the min per option over the horizons *within
    that option's term*: a commitment can never be reduced while its term
    runs, so upcoming demand drops inside the term cap today's safe
    purchase; drops after expiry are irrelevant (the tranche simply is not
    renewed) — short-term options therefore clear fewer horizons and may
    commit more aggressively than long-term ones.  Finally the stack is
    re-monotonized (running max in envelope-depth order) since per-option
    minima over different horizon sets can cross.

    ``lines`` overrides the (alphas, betas) cost lines derived from
    ``options`` — the hook ``plan_fleet_pools`` uses to price one pool's
    unavailable (wrong-cloud) options at the on-demand rate."""
    options = options if options is not None else pf.options_from_pricing()
    alphas, betas = (
        lines if lines is not None
        else pf.option_lines(options, term_weighting=term_weighting)
    )
    qs = pf.handover_fractiles(alphas, betas, od_rate=od_rate)

    model = fc.fit(history, cfg)
    t0 = history.shape[-1]
    horizon_hours = num_horizons * HOURS_PER_WEEK
    yhat = fc.forecast_horizon(model, t0, horizon_hours)          # Step 1
    w_hours = jnp.arange(1, num_horizons + 1) * HOURS_PER_WEEK    # Step 2

    per_horizon = _prefix_weighted_quantiles(yhat, w_hours, qs)   # Step 3

    term_weeks = jnp.asarray([o.term_weeks for o in options])
    widths, levels = _monotone_stack(                             # Step 4
        per_horizon, qs, term_weeks, num_horizons
    )
    return PortfolioPlanResult(
        options=options,
        widths=widths,
        levels=levels,
        per_horizon_levels=per_horizon,
        fractiles=qs,
        forecast=yhat,
    )


@dataclasses.dataclass
class PoolPlanEntry:
    """One pool's slice of a fleet plan: Algorithm-1 stack + evaluation."""

    key: dm.PoolKey
    widths: np.ndarray            # (K,) band widths, options-aligned
    levels: np.ndarray            # (K,) stack tops
    total_commitment: float       # stack top = on-demand threshold
    spend: pf.PortfolioSpend      # real-dollar eval on the held-out window


@dataclasses.dataclass
class FleetPoolsPlan:
    """Per-pool fleet plan: Algorithm 1 batched over the P pool axis.

    ``pooling_premium`` is the diagnostic the paper's per-pool framing
    implies: sum-of-pool-plan cost over the cost of one plan on the pooled
    (aggregate) trace, minus 1.  The aggregate plan pretends capacity in any
    cloud can serve any pool's demand — commitments cannot actually move
    across clouds/SKUs, so the premium is the pooling benefit an aggregate
    planner overstates."""

    keys: tuple[dm.PoolKey, ...]
    options: list[pf.PurchaseOption]
    available: np.ndarray             # (P, K) purchasable mask (cloud match)
    widths: np.ndarray                # (P, K) band widths to purchase now
    levels: np.ndarray                # (P, K) stack tops
    fractiles: np.ndarray             # (P, K) per-pool critical fractiles
    per_horizon_levels: np.ndarray    # (P, W, K) prefix thresholds
    forecasts: np.ndarray             # (P, W*168) hourly forecasts
    ladders: ld.PoolLadderBook        # per-pool tranche stacks
    per_pool: list[PoolPlanEntry]
    committed_cost: float
    on_demand_cost: float
    total_cost: float
    all_on_demand_cost: float
    savings_vs_on_demand: float
    aggregate_cost: float             # one plan on the summed fleet trace
    pooling_premium: float
    # Spot band (None / 0.0 on spot-free plans): the per-pool demand level
    # above which the plan routes demand to preemptible capacity, priced at
    # the risk-adjusted effective rate in ``spot_lines``.
    spot_lines: "spot_mod.SpotLines | None" = None
    spot_floor: np.ndarray | None = None    # (P,) spot band bottoms
    spot_cost: float = 0.0
    # Migration awareness (None on migration-blind plans): the successor
    # edges the share-based forecaster composed per-pool forecasts over.
    migration_edges: "gn.MigrationEdges | None" = None
    # Convertible band (None on convertible-free plans): cloud-level
    # exchangeable tranches sized on the residual demand above the pool
    # stacks and re-pinned onto pools for the evaluation window.
    conv_options: "list[pf.PurchaseOption] | None" = None
    conv_clouds: tuple[str, ...] | None = None
    conv_widths: np.ndarray | None = None   # (C, Kc) widths purchased now
    conv_alloc: np.ndarray | None = None    # (P,) re-pinned allocation
    conv_ladders: ld.PoolLadderBook | None = None
    conv_cost: float = 0.0

    def commitment(
        self,
        cloud: str | None = None,
        region: str | None = None,
        term_weeks: int | None = None,
    ) -> float:
        """Answer "how much 3y GCP commitment in us-central1": total width
        purchased, filtered by pool cloud/region and option term."""
        total = 0.0
        for p, key in enumerate(self.keys):
            if cloud is not None and key[0] != cloud:
                continue
            if region is not None and key[1] != region:
                continue
            for k, opt in enumerate(self.options):
                if term_weeks is not None and opt.term_weeks != term_weeks:
                    continue
                total += float(self.widths[p, k])
        return total


def plan_fleet_pools(
    pools: dm.PoolSet,
    options: list[pf.PurchaseOption] | None = None,
    *,
    horizon_weeks: int = 8,
    od_rate: float | None = None,
    term_weighting: float = 0.0,
    cfg: fc.ForecastConfig = fc.ForecastConfig(),
    mode: Literal["one_shot", "rolling"] = "one_shot",
    spot: "spot_mod.SpotConfig | bool | None" = None,
    migration: "gn.MigrationConfig | bool | None" = None,
    convertible: "list[pf.PurchaseOption] | bool | None" = None,
    policy=None,
    telemetry=None,
    **rolling_kw,
):
    """Algorithm 1 + the portfolio solver over every pool in ONE batched
    pass: the (P, T) demand matrix rides the vmapped forecaster fit, one
    shared sort per pool for all horizons x options, and per-pool purchase
    options masked to each pool's cloud (Table-2 SKUs are per cloud).

    ``mode="one_shot"`` (default, returns :class:`FleetPoolsPlan`): the
    last ``horizon_weeks`` of the trace are held out; plans are fit on the
    prefix and evaluated in real dollars on the holdout, per pool and
    fleet-total, alongside the aggregate-trace plan for the pooling-premium
    diagnostic.  Mirrors ``capacity.simulator.plan_fleet`` semantics at the
    pool level.

    ``mode="rolling"`` (returns :class:`repro.core.replan.RollingPlanReport`)
    replays the paper's actual operating loop instead: week by week, re-fit
    the forecaster on the extended prefix, re-run the solver, and buy only
    incremental tranches while expiring ones roll off — with one-shot and
    hindsight baselines on the same window.  Extra keyword arguments
    (``cadence_weeks``, ``start_weeks``, ``backend``, ``solver``, ...) are
    forwarded to :func:`repro.core.replan.replan_fleet_pools`.

    ``spot`` enables the preemptible third purchasing option (``core.spot``;
    True = default :class:`repro.core.spot.SpotConfig`): each pool gains a
    risk-priced spot band above its commitment stack, chance-constrained so
    expected demand-weighted availability stays >= the configured target.
    ``spot=None`` (default) leaves every code path bit-identical to the
    spot-free planner.

    ``migration`` makes forecasting turnover-aware (``core.migration``):
    pools matched by the ``pricing.GENERATIONS`` successor table are
    forecast as *pair total x logistic family share* instead of raw
    per-pool traces, so a generational migration is not extrapolated as
    permanent organic decay/growth.  ``convertible`` adds the cloud-level
    exchangeable SKUs (``pricing.CONVERTIBLE_PLANS``): a convertible
    stack is sized on the cloud residual demand above the pool-pinned
    stacks and its width re-pinned onto pools over the evaluation window
    (the aggregate pooling-premium baseline stays commitments+spot only —
    pooled capacity is already fungible, which is exactly what a
    convertible buys back).  Both default to None and leave every code
    path bit-identical to the pre-migration planner.

    ``policy`` (rolling mode only) selects the weekly decision rule — a
    :class:`repro.core.policy.Policy`, a registry name such as
    ``"deterministic_hedge"``, or None for the paper's rolling portfolio
    loop.  ``policy=None`` (default) keeps the replay bit-identical to
    the pre-policy planner (golden-tested).

    ``telemetry`` (rolling mode only; True or a
    :class:`repro.obs.config.TelemetryConfig`) attaches the observability
    layer — the per-week x per-pool x per-source cost ledger and kernel
    stats (``repro.obs``).  ``telemetry=None`` (default) keeps the replay
    bit-identical to the telemetry-free planner (golden-tested).

    This is the *legacy* spelling, kept as a thin shim over the unified
    request API: it builds the equivalent :class:`repro.core.api.PlanRequest`
    and calls :func:`repro.core.api.plan`, so both spellings are
    bit-identical by construction.  Loose rolling knobs in ``rolling_kw``
    (``cadence_weeks=``, ``backend=``, ...) emit a ``DeprecationWarning``
    pointing at ``RollingConfig``; new call sites should construct a
    ``PlanRequest`` directly."""
    from repro.core import api

    if mode != "rolling":
        if rolling_kw:
            raise TypeError(
                "unexpected arguments for mode='one_shot': "
                f"{sorted(rolling_kw)}"
            )
        if policy is not None:
            raise TypeError("policy= applies to mode='rolling' only")
        if telemetry is not None:
            raise TypeError("telemetry= applies to mode='rolling' only")
        request = api.PlanRequest(
            pools=pools, options=options, mode="one_shot",
            horizon_weeks=horizon_weeks, od_rate=od_rate,
            term_weighting=term_weighting, forecast=cfg, spot=spot,
            migration=migration, convertible=convertible,
        )
        return api.plan(request)

    scenarios = rolling_kw.pop("scenarios", None)
    rolling_fields = {f.name for f in dataclasses.fields(api.RollingConfig)}
    unknown = set(rolling_kw) - rolling_fields
    if unknown:
        raise TypeError(
            f"unexpected arguments for mode='rolling': {sorted(unknown)}"
        )
    if rolling_kw:
        warnings.warn(
            "passing rolling-replay knobs as loose keyword arguments "
            f"({sorted(rolling_kw)}) is deprecated; build a "
            "repro.core.api.PlanRequest with rolling=RollingConfig(...) "
            "and call repro.core.api.plan()",
            DeprecationWarning,
            stacklevel=2,
        )
    request = api.PlanRequest(
        pools=pools, options=options, mode="rolling",
        horizon_weeks=horizon_weeks, od_rate=od_rate,
        term_weighting=term_weighting, forecast=cfg, spot=spot,
        migration=migration, convertible=convertible, policy=policy,
        scenarios=scenarios, telemetry=telemetry,
        rolling=api.RollingConfig(**rolling_kw),
    )
    return api.plan(request)


def _plan_fleet_pools_one_shot(
    pools: dm.PoolSet,
    options: list[pf.PurchaseOption] | None = None,
    *,
    horizon_weeks: int = 8,
    od_rate: float | None = None,
    term_weighting: float = 0.0,
    cfg: fc.ForecastConfig = fc.ForecastConfig(),
    spot: "spot_mod.SpotConfig | bool | None" = None,
    migration: "gn.MigrationConfig | bool | None" = None,
    convertible: "list[pf.PurchaseOption] | bool | None" = None,
) -> FleetPoolsPlan:
    """The one-shot planning pipeline behind :func:`repro.core.api.plan`
    (see :func:`plan_fleet_pools` for the full narrative docstring)."""
    options = options if options is not None else pf.options_from_pricing()
    od = od_rate if od_rate is not None else pricing.on_demand_premium()
    eval_hours = horizon_weeks * HOURS_PER_WEEK
    if pools.num_hours <= eval_hours:
        raise ValueError(
            f"need > {eval_hours} hours of demand for a {horizon_weeks}-week"
            f" holdout, got {pools.num_hours}"
        )
    hist = jnp.asarray(pools.demand[:, :-eval_hours], jnp.float32)
    actual = pools.demand[:, -eval_hours:]

    # Per-pool cost lines: options off the pool's cloud priced at od_rate
    # (provably zero width) so one dense (P, K) batch feeds vmap.
    al_p, be_p, avail = pf.pool_option_lines(
        options, pools.clouds, term_weighting=term_weighting, od_rate=od
    )
    qs = jax.vmap(
        functools.partial(pf.handover_fractiles, od_rate=od)
    )(al_p, be_p)                                                 # (P, K)

    # Steps 1-2, batched: one vmapped fit + forecast over the P axis
    # (fit_batched applies fit's own short-history yearly-term guard).
    # With migration awareness, the structural fit runs on turnover-
    # invariant pair totals and per-pool forecasts are recomposed from
    # total x logistic share.
    mig_cfg = gn.resolve_migration(migration)
    edges = (
        gn.migration_edges(pools.keys, mig_cfg)
        if mig_cfg is not None else None
    )
    use_mig = edges is not None and edges.num_edges > 0
    t_fut = hist.shape[-1] + jnp.arange(eval_hours)
    if use_mig:
        model = fc.fit_batched(mg.transform_for_fit(hist, edges), cfg)
        yhat_tot = fc.predict_batched(model, t_fut)
        sh_a, sh_b = mg.fit_share(
            hist, edges, t_max=model.t_max,
            prior_weight=mig_cfg.share_prior_weight,
        )
        shares = mg.predict_share(sh_a, sh_b, t_fut, model.t_max)
        yhat = mg.compose_forecast(yhat_tot, shares, edges)
    else:
        model = fc.fit_batched(hist, cfg)
        yhat = fc.predict_batched(model, t_fut)                   # (P, H)
    w_hours = jnp.arange(1, horizon_weeks + 1) * HOURS_PER_WEEK

    # Steps 3-4, vmapped over pools (per-pool fractiles ride along).
    per_horizon = jax.vmap(
        lambda y, q: _prefix_weighted_quantiles(y, w_hours, q)
    )(yhat, qs)                                                   # (P, W, K)

    # Spot band: per-horizon floors (envelope entry <-> chance-constraint
    # volume cap) truncate the committed stack — capacity above the floor
    # is cheaper to serve from risk-priced preemptible supply than to
    # commit to or buy on demand.
    sp_res = spot_mod.resolve_spot(spot, pools.clouds, od_rate=od)
    spot_floor = None
    if sp_res is not None:
        _, s_lines = sp_res
        u_env = jax.vmap(
            lambda a_, b_, r_: spot_mod.spot_entry_fractile(
                a_, b_, r_, od_rate=od
            )
        )(al_p, be_p, s_lines.rate)                               # (P,)
        env_fl = jax.vmap(
            lambda y, q: _prefix_weighted_quantiles(y, w_hours, q[None])[:, 0]
        )(yhat, u_env)                                            # (P, W)
        vol_fl = jax.vmap(_prefix_spot_floors, in_axes=(0, None, 0))(
            yhat, w_hours, s_lines.cap
        )                                                         # (P, W)
        floors = jnp.maximum(env_fl, vol_fl)
        floors = jnp.where(s_lines.cap[:, None] > 0, floors, jnp.inf)
        per_horizon = jnp.minimum(per_horizon, floors[..., None])
        spot_floor = np.asarray(floors[:, -1])    # full-window floor

    term_weeks = jnp.asarray([o.term_weeks for o in options])
    widths, levels = jax.vmap(
        lambda ph, q: _monotone_stack(ph, q, term_weeks, horizon_weeks)
    )(per_horizon, qs)                                            # (P, K)
    widths_np = np.asarray(widths)

    # Convertible stack: cloud-level exchangeable SKUs sized on the
    # residual forecast above the pool-pinned stacks, re-pinned onto the
    # pools for the evaluation window (same machinery as the weekly
    # re-pin in the rolling replay, applied once).
    conv_opts = pf.resolve_convertible(convertible, pools.clouds)
    conv_alloc_np = None
    conv_cost = 0.0
    if conv_opts is not None:
        conv_clouds, member, al_c, be_c, qs_c, conv_terms = (
            pf.convertible_cloud_setup(
                conv_opts, pools.clouds, term_weighting=term_weighting,
                od_rate=od,
            )
        )
        pool_top = jnp.asarray(widths_np.sum(-1))
        # Cloud totals are turnover-invariant; convertible buys the band
        # that is safe at cloud level but above what pools pin themselves
        # (same sizing as the rolling replay's weekly conv pass).
        highest = jax.lax.Precision.HIGHEST
        total_c = jnp.matmul(member, yhat, precision=highest)
        per_h_c = jax.vmap(
            lambda y, q: _prefix_weighted_quantiles(y, w_hours, q)
        )(total_c, qs_c)
        cw, ct = jax.vmap(
            lambda ph, q: _monotone_stack(ph, q, conv_terms, horizon_weeks)
        )(per_h_c, qs_c)                                          # (C, Kc)
        conv_widths = pf.truncate_convertible_stack(
            ct, cw, jnp.matmul(member, pool_top, precision=highest)
        )
        # Need keys on the window's forecast PEAK, mirroring the rolling
        # replay: allocating sunk capacity is free, and a mean-based need
        # would leave the diurnal peaks billing at on-demand.
        excess = jnp.maximum(yhat.max(-1) - pool_top, 0.0)
        conv_alloc = pf.allocate_convertible(
            conv_widths.sum(-1), excess, member
        )
        conv_widths_np = np.asarray(conv_widths)
        conv_alloc_np = np.asarray(conv_alloc)
        conv_rates = np.asarray([o.rate for o in conv_opts])
        conv_cost = float(
            (conv_rates * conv_widths_np).sum() * eval_hours
        )
        conv_ladders = ld.convertible_ladder_book(
            conv_widths_np[:, None, :],
            np.asarray(
                [o.term_weeks * HOURS_PER_WEEK for o in conv_opts]
            ),
            conv_clouds,
        )

    # Per-pool tranche stacks: buy every band now; terms are per-SKU.
    term_hours = np.asarray([o.term_weeks * HOURS_PER_WEEK for o in options])
    ladders = ld.plan_pool_portfolio_purchases(
        widths_np[:, None, :], term_hours, pools.keys
    )

    per_pool = []
    for p, key in enumerate(pools.keys):
        spend = pf.portfolio_spend(
            jnp.asarray(actual[p], jnp.float32), widths_np[p], options,
            od_rate=od,
            spot_rate=(
                float(sp_res[1].rate[p]) if sp_res is not None else None
            ),
            spot_floor=(
                float(spot_floor[p]) if spot_floor is not None else None
            ),
            level_offset=(
                float(conv_alloc_np[p]) if conv_alloc_np is not None
                else 0.0
            ),
        )
        per_pool.append(PoolPlanEntry(
            key=key,
            widths=widths_np[p],
            levels=np.asarray(levels[p]),
            total_commitment=float(widths_np[p].sum()),
            spend=spend,
        ))

    committed = sum(float(e.spend.committed.sum()) for e in per_pool)
    on_demand = sum(e.spend.on_demand for e in per_pool)
    spot_cost = sum(e.spend.spot for e in per_pool)
    total = committed + on_demand + spot_cost + conv_cost
    all_od = sum(e.spend.all_on_demand for e in per_pool)
    savings = 1.0 - total / all_od if all_od > 0 else 0.0

    # The aggregate (single-pool) plan the fleet trace used to collapse to:
    # same pipeline, pooled demand, every option purchasable.
    agg_hist = jnp.asarray(hist.sum(0))
    agg_res = plan_portfolio(
        agg_hist, options, num_horizons=horizon_weeks, od_rate=od,
        term_weighting=term_weighting, cfg=cfg,
    )
    agg_widths = np.asarray(agg_res.widths)
    agg_spot_rate = agg_spot_floor = None
    if sp_res is not None:
        # The premium must isolate the pooling effect, so the aggregate
        # baseline gets the same spot option: the demand-weighted mean of
        # the per-pool lines (pooled capacity has no single cloud), floors
        # from its own forecast, committed stack truncated identically.
        share = np.asarray(hist.sum(-1))
        share = share / max(share.sum(), 1e-9)
        rate_a = jnp.float32((np.asarray(s_lines.rate) * share).sum())
        cap_a = jnp.float32((np.asarray(s_lines.cap) * share).sum())
        al_a, be_a = pf.option_lines(options, term_weighting=term_weighting)
        u_env_a = spot_mod.spot_entry_fractile(
            al_a, be_a, rate_a, od_rate=od
        )
        ayhat = jnp.asarray(agg_res.forecast)
        env_a = _prefix_weighted_quantiles(ayhat, w_hours, u_env_a[None])
        vol_a = _prefix_spot_floors(ayhat, w_hours, cap_a)
        floors_a = jnp.maximum(env_a[:, 0], vol_a)
        if float(cap_a) > 0:
            per_h_a = jnp.minimum(
                jnp.asarray(agg_res.per_horizon_levels), floors_a[:, None]
            )
            agg_w, _ = _monotone_stack(
                per_h_a, agg_res.fractiles, term_weeks, horizon_weeks
            )
            agg_widths = np.asarray(agg_w)
            agg_spot_floor = float(floors_a[-1])
        else:
            agg_spot_floor = np.inf
        agg_spot_rate = float(rate_a)
    agg_spend = pf.portfolio_spend(
        jnp.asarray(actual.sum(0), jnp.float32), agg_widths,
        options, od_rate=od,
        spot_rate=agg_spot_rate, spot_floor=agg_spot_floor,
    )

    return FleetPoolsPlan(
        keys=pools.keys,
        options=options,
        available=avail,
        widths=widths_np,
        levels=np.asarray(levels),
        fractiles=np.asarray(qs),
        per_horizon_levels=np.asarray(per_horizon),
        forecasts=np.asarray(yhat),
        ladders=ladders,
        per_pool=per_pool,
        committed_cost=committed,
        on_demand_cost=on_demand,
        total_cost=total,
        all_on_demand_cost=all_od,
        savings_vs_on_demand=savings,
        aggregate_cost=agg_spend.total,
        # An empty holdout window (every pool retired) has no plan to
        # compare against: report a neutral premium instead of dividing by 0.
        pooling_premium=(
            total / agg_spend.total - 1.0 if agg_spend.total > 0 else 0.0
        ),
        spot_lines=sp_res[1] if sp_res is not None else None,
        spot_floor=spot_floor,
        spot_cost=spot_cost,
        migration_edges=edges if use_mig else None,
        conv_options=conv_opts,
        conv_clouds=(
            tuple(conv_clouds) if conv_opts is not None else None
        ),
        conv_widths=(
            conv_widths_np if conv_opts is not None else None
        ),
        conv_alloc=conv_alloc_np,
        conv_ladders=(
            conv_ladders if conv_opts is not None else None
        ),
        conv_cost=conv_cost,
    )


def compare_horizons(
    yhat: jnp.ndarray,
    horizons_weeks: tuple[int, ...] = (1, 2),
    a: float = cm.DEFAULT_A,
    b: float = cm.DEFAULT_B,
    eval_weeks: int | None = None,
) -> dict:
    """Paper Fig 8: commitment from a w1-week horizon vs w2-week horizon,
    both *applied over* the longer evaluation window.  Costs use the paper's
    Eq (1) metric: the figure's caption compares C(c_w1, X-hat_w2) vs
    C(c_w2, X-hat_w2).  Demonstrates why upcoming demand drops must be
    considered: the longer-horizon level is lower and cheaper.
    """
    eval_weeks = eval_weeks or max(horizons_weeks)
    eval_slice = yhat[: eval_weeks * HOURS_PER_WEEK]
    out = {}
    for w in horizons_weeks:
        prefix = yhat[: w * HOURS_PER_WEEK]
        c_w = float(cm.optimal_commitment_quantile(prefix, a, b))
        spend = float(cm.commitment_cost(eval_slice, c_w, a, b))
        out[w] = {"level": c_w, "total_spend": spend}
    return out
