"""Rolling weekly re-planning over pool portfolios (paper §3.3.3-§3.3.4).

Algorithm 1 is a *rolling* procedure: the paper's planner re-runs the
purchase decision every period as new demand history arrives, buying only
incremental tranches on top of what is already committed (commitments can be
added any week but only ever expire off).  ``planner.plan_fleet_pools`` is
the one-shot instance — fit at t0, buy every (P, K) width up front.  This
module replays the full operating mode over a multi-year (P, T) demand
matrix:

    for each week w (from ``start_weeks``):
        roll off tranches whose term ends at w
        re-fit the batched forecaster on the demand prefix [0, w·168)
        forecast ``horizon_weeks`` ahead; run the stacked-quantile
            portfolio solver (Algorithm 1 steps 2-4) vmapped over pools
        on decision weeks (every ``cadence_weeks``): buy, per pool per
            option, only the increment that lifts the active committed
            width up to the solver's target
        bill the week: every active tranche at its committed rate,
            demand above the stack top at the on-demand rate

The hot path is one ``lax.scan`` over weeks carrying ``(active committed
stack (P, K), tranche roll-off schedule (P, K, W))``: prefix re-fits gather
precomputed cumulative normal equations (``forecast.prefix_fit_state``) so a
3-year x 12-pool replay is a single compiled program instead of ~156
Python-level solves.  The process keeps that program (``_replay_program``):
its static key is the request's settings (``_ReplaySpec``) and every
array the step reads is an argument, so a request of a known shape class
traces and lowers nothing.  ``backend="loop"`` is the naive replay — one
re-accumulated prefix fit and one Python dispatch per week — kept as the
benchmark baseline (``bench_rolling_replan``) and as an independent
implementation the scan path is tested against.

The report compares three operating points on the same evaluation window:

    rolling    — the replay above;
    one-shot   — the same replay with a single decision week (buy the
                 t0 plan, then let tranches expire; what
                 ``plan_fleet_pools`` prices today);
    hindsight  — the optimal *constant* stack computed on the realized
                 demand (``portfolio.optimal_portfolio_stack`` per pool,
                 full knowledge; short-term tranches assumed repurchased
                 back-to-back).

``solver="grid"`` routes each week's per-horizon prefix solves through the
``commitment_sweep`` over/under sweep on 0/1 prefix-mask weights (the
Pallas kernel on TPU via ``use_kernel=True``) instead of the shared-sort
quantile path — the K-option generalization of Algorithm 1's 52 weight
patterns.

``scenarios=`` batches the whole replay over N sampled demand futures
(``data.scenarios.ScenarioConfig``): the (N, P) block is *flattened* into
the scan's pool-row axis — every per-pool op in the harness is already
row-elementwise or vmapped, so N x P rows ride the same compiled program
(cost lines, spot lines and policy pstates tile per scenario; migration
edges re-index into each scenario's row block; the convertible membership
goes block-diagonal so capacity never pools across futures).  Scenario 0
is always the realized trace, ladders are built from it, and
``n_scenarios=1`` is bit-identical to the unbatched replay (golden-
tested).  Rows are sharded over local devices (``launch.mesh``) when more
than one exists, and ``ScenarioConfig.chunk`` splits very large N into
sequential compiled chunks on one host.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Literal, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.capacity import generations as gn
from repro.capacity import pricing
from repro.core import demand as dm
from repro.core import forecast as fc
from repro.core import ladder as ld
from repro.core import migration as mg
from repro.core import policy as pol
from repro.core import portfolio as pf
from repro.core import spot as spot_mod
from repro.core.demand import HOURS_PER_WEEK
from repro.core.planner import (
    _monotone_stack,
    _prefix_spot_floors,
    _prefix_weighted_quantiles,
)
from repro.core.portfolio import allocate_convertible  # noqa: F401  (API)
from repro.data import scenarios as sc
from repro.launch import mesh as mesh_mod
from repro.obs import calibration as obs_calib
from repro.obs import config as obs_config
from repro.obs import kernelstats as obs_kstats
from repro.obs import ledger as obs_ledger
from repro.obs import provenance as obs_prov
from repro.obs import spans as obs_spans

pricing.validate_tables()


@dataclasses.dataclass
class RollingPlanReport:
    """Replay of the rolling re-planning loop plus its two baselines.

    Per-week arrays are aligned with ``weeks`` (absolute week indices into
    the trace, starting at ``start_weeks``); per-pool axes align with
    ``keys``; option axes with ``options``."""

    keys: tuple[dm.PoolKey, ...]
    options: list[pf.PurchaseOption]
    cadence_weeks: int
    start_weeks: int
    horizon_weeks: int
    weeks: np.ndarray                 # (S,) absolute week index
    targets: np.ndarray               # (S, P, K) per-week solver targets
    increments: np.ndarray            # (S, P, K) tranches actually bought
    active: np.ndarray                # (S, P, K) committed stack after buys
    committed_cost: np.ndarray        # (S, P) weekly committed spend
    on_demand_cost: np.ndarray        # (S, P) weekly shortfall spend
    utilization: np.ndarray           # (S, P) used / committed chip-hours
    ladders: ld.PoolLadderBook        # the purchases as a tranche book
    total_cost: float
    all_on_demand_cost: float
    savings_vs_on_demand: float
    # one-shot baseline: buy the week-``start_weeks`` plan, never re-plan
    one_shot_weekly_cost: np.ndarray | None = None    # (S,)
    one_shot_cost: float | None = None
    savings_vs_one_shot: float | None = None
    # hindsight baseline: optimal constant stack on the realized demand
    hindsight_widths: np.ndarray | None = None        # (P, K)
    hindsight_weekly_cost: np.ndarray | None = None   # (S,)
    hindsight_cost: float | None = None
    regret_vs_hindsight: float | None = None
    # Spot band (None on spot-free replays): the fast half of the capacity
    # split — re-decided every week from that week's forecast, no tranche,
    # no term.  ``spot_floor`` is clamped to the committed stack top;
    # demand above it bills at the effective spot rate, between stack top
    # and floor at on-demand.
    spot_config: "spot_mod.SpotConfig | None" = None
    spot_lines: "spot_mod.SpotLines | None" = None
    spot_floor: np.ndarray | None = None              # (S, P) weekly floors
    spot_cost: np.ndarray | None = None               # (S, P) weekly spend
    spot_volume: np.ndarray | None = None             # (S, P) chip-hours
    spot_ladders: ld.PoolLadderBook | None = None     # 1-week audit tranches
    # Migration awareness (None on migration-blind replays): the successor
    # edges the share-based forecaster composed per-pool forecasts over.
    migration_config: "gn.MigrationConfig | None" = None
    migration_edges: "gn.MigrationEdges | None" = None
    # Convertible band (None on convertible-free replays): cloud-level
    # exchangeable tranches, carried per cloud in the scan and re-pinned
    # onto that cloud's pools every week (``conv_alloc``).  Cloud axes
    # align with ``conv_clouds``; option axes with ``conv_options``.
    conv_options: "list[pf.PurchaseOption] | None" = None
    conv_clouds: tuple[str, ...] | None = None
    conv_targets: np.ndarray | None = None            # (S, C, Kc) targets
    conv_increments: np.ndarray | None = None         # (S, C, Kc) buys
    conv_active: np.ndarray | None = None             # (S, C, Kc) stack
    conv_alloc: np.ndarray | None = None              # (S, P) re-pinned
    conv_committed_cost: np.ndarray | None = None     # (S, C) weekly spend
    conv_ladders: ld.PoolLadderBook | None = None     # cloud-level book
    # Which policy drove the weekly decisions (``core.policy``).
    policy_name: str = "rolling_portfolio"
    # Scenario batch (fields None / axis absent on single-path replays):
    # with a ScenarioConfig of n_scenarios > 1 every per-week array above
    # gains an N axis at position 1 — (S, N, P, K) etc., clouds axes
    # (S, N, C, Kc) — ``hindsight_widths`` becomes (N, P, K), the baseline
    # weekly costs (S, N), and the scalar aggregates (``total_cost``,
    # ``*_cost``) are MEANS over scenarios.  Ladders are always built from
    # scenario 0, the realized trace.
    n_scenarios: int = 1
    scenario_family: str | None = None
    scenario_cost: np.ndarray | None = None            # (N,) replay cost
    scenario_one_shot_cost: np.ndarray | None = None   # (N,)
    scenario_hindsight_cost: np.ndarray | None = None  # (N,)
    scenario_cr: np.ndarray | None = None              # (N,) cost/hindsight
    scenario_regret: np.ndarray | None = None          # (N,) cost-hindsight
    # Request provenance (always set by the replay): the resolved on-demand
    # rate and scenario config, so downstream consumers (spot replay,
    # ledger) need no side-channel.
    od_rate: float | None = None
    scenario_config: "sc.ScenarioConfig | None" = None
    # Telemetry (``repro.obs``; all None on telemetry=None replays —
    # the scan emits no extra outputs at all, so those paths stay
    # bit-identical, golden-tested).  The usage arrays are scan outputs;
    # ``ledger`` / ``kernel_stats`` are the materialized obs objects.
    telemetry: "obs_config.TelemetryConfig | None" = None
    committed_by_sku: np.ndarray | None = None         # (S, P, K) spend
    conv_committed_by_sku: np.ndarray | None = None    # (S, C, Kc) spend
    used_hours: np.ndarray | None = None               # (S, P) chip-hours
    od_volume: np.ndarray | None = None                # (S, P) chip-hours
    ledger: "obs_ledger.CostLedger | None" = None
    kernel_stats: "obs_kstats.KernelStats | None" = None
    # Decision cadence.  "weekly" is the harness grid (the default, the
    # pre-cadence program bit for bit); "breach" re-solves only in weeks
    # where realized demand exited the forecast band held since the last
    # decision.  ``decision_mask`` records which evaluated weeks decided —
    # (S,) bool, (S, N) on scenario batches (uniform within a scenario);
    # the breach bands ride along so a host-side oracle can replay the
    # mask exactly.
    cadence: str = "weekly"
    decision_mask: np.ndarray | None = None            # (S,) / (S, N)
    breach_band_lo: np.ndarray | None = None           # (S, P) / (S, N, P)
    breach_band_hi: np.ndarray | None = None
    # Calibration telemetry (``TelemetryConfig(calibration=True)``): the
    # per-week forecast fractile levels the scan emitted and the scored
    # CalibrationCube (hits / coverage / pinball vs realized demand).
    fractile_levels: np.ndarray | None = None      # (S, P, Q) / (S, N, P, Q)
    calibration: "obs_calib.CalibrationCube | None" = None
    # Decision provenance (``provenance=True``): queryable per-week record
    # of buys, roll-offs and binding constraints on scenario 0.
    decision_log: "obs_prov.DecisionLog | None" = None

    @property
    def weekly_cost(self) -> np.ndarray:
        """(S,) fleet-total spend per week ((S, N) when scenario-batched)."""
        total = self.committed_cost + self.on_demand_cost
        if self.spot_cost is not None:
            total = total + self.spot_cost
        total = total.sum(-1)
        if self.conv_committed_cost is not None:
            total = total + self.conv_committed_cost.sum(-1)
        return total

    def summary(self) -> dict:
        out = {
            "weeks_evaluated": int(len(self.weeks)),
            "cadence_weeks": self.cadence_weeks,
            "total_cost": self.total_cost,
            "savings_vs_on_demand": self.savings_vs_on_demand,
        }
        if self.cadence != "weekly":
            out["cadence"] = self.cadence
        if self.decision_mask is not None:
            dm0 = (
                self.decision_mask if self.decision_mask.ndim == 1
                else self.decision_mask[:, 0]
            )
            out["decision_weeks"] = int(dm0.sum())
        if self.spot_cost is not None:
            out["spot_cost"] = float(self.spot_cost.sum())
            out["spot_chip_hours"] = float(self.spot_volume.sum())
        if self.conv_committed_cost is not None:
            out["convertible_cost"] = float(self.conv_committed_cost.sum())
            out["convertible_final_width"] = float(
                self.conv_active[-1].sum()
            )
        if self.one_shot_cost is not None:
            out["one_shot_cost"] = self.one_shot_cost
            out["savings_vs_one_shot"] = self.savings_vs_one_shot
        if self.hindsight_cost is not None:
            out["hindsight_cost"] = self.hindsight_cost
            out["regret_vs_hindsight"] = self.regret_vs_hindsight
        if self.n_scenarios > 1:
            out["n_scenarios"] = self.n_scenarios
            out["scenario_cost_mean"] = float(self.scenario_cost.mean())
            out["scenario_cost_p95"] = float(
                np.quantile(self.scenario_cost, 0.95)
            )
            if self.scenario_cr is not None:
                out["scenario_cr_mean"] = float(self.scenario_cr.mean())
                out["scenario_cr_p95"] = float(
                    np.quantile(self.scenario_cr, 0.95)
                )
                out["scenario_regret_mean"] = float(
                    self.scenario_regret.mean()
                )
                out["scenario_regret_p95"] = float(
                    np.quantile(self.scenario_regret, 0.95)
                )
        return out


def _tile_edges(edges: gn.MigrationEdges, n: int, p: int) -> gn.MigrationEdges:
    """Replicate one fleet's migration edges onto the flattened
    (N scenarios x P pools) row axis: scenario s's copy of edge g joins
    rows ``src[g] + s*p -> dst[g] + s*p`` — scenarios never exchange
    demand."""
    off = (jnp.arange(n, dtype=jnp.int32) * p)[:, None]
    return dataclasses.replace(
        edges,
        src=(edges.src[None, :] + off).reshape(-1),
        dst=(edges.dst[None, :] + off).reshape(-1),
        uplift=jnp.tile(edges.uplift, n),
        inv_gain=jnp.tile(edges.inv_gain, n),
        midpoint_hours=jnp.tile(edges.midpoint_hours, n),
        rate_per_hour=jnp.tile(edges.rate_per_hour, n),
    )


def _merge_scenario_reports(
    parts: list[RollingPlanReport],
) -> RollingPlanReport:
    """Stitch chunked scenario replays (``ScenarioConfig.chunk``) back into
    one report: per-week arrays concatenate along the scenario axis,
    per-scenario distributions along N, and the scalar aggregates are
    recomputed as means over the full scenario set.  Ladders (always built
    from scenario 0) come from the first chunk."""
    first = parts[0]

    def cat(name: str, axis: int):
        vals = [getattr(p, name) for p in parts]
        return None if vals[0] is None else np.concatenate(vals, axis=axis)

    ns = np.asarray([p.n_scenarios for p in parts], np.float64)
    rep = dataclasses.replace(
        first,
        targets=cat("targets", 1),
        increments=cat("increments", 1),
        active=cat("active", 1),
        committed_cost=cat("committed_cost", 1),
        on_demand_cost=cat("on_demand_cost", 1),
        utilization=cat("utilization", 1),
        spot_floor=cat("spot_floor", 1),
        spot_cost=cat("spot_cost", 1),
        spot_volume=cat("spot_volume", 1),
        conv_targets=cat("conv_targets", 1),
        conv_increments=cat("conv_increments", 1),
        conv_active=cat("conv_active", 1),
        conv_alloc=cat("conv_alloc", 1),
        conv_committed_cost=cat("conv_committed_cost", 1),
        committed_by_sku=cat("committed_by_sku", 1),
        conv_committed_by_sku=cat("conv_committed_by_sku", 1),
        used_hours=cat("used_hours", 1),
        od_volume=cat("od_volume", 1),
        breach_band_lo=cat("breach_band_lo", 1),
        breach_band_hi=cat("breach_band_hi", 1),
        fractile_levels=cat("fractile_levels", 1),
        one_shot_weekly_cost=cat("one_shot_weekly_cost", 1),
        hindsight_weekly_cost=cat("hindsight_weekly_cost", 1),
        hindsight_widths=cat("hindsight_widths", 0),
        scenario_cost=cat("scenario_cost", 0),
        scenario_one_shot_cost=cat("scenario_one_shot_cost", 0),
        scenario_hindsight_cost=cat("scenario_hindsight_cost", 0),
        scenario_cr=cat("scenario_cr", 0),
        scenario_regret=cat("scenario_regret", 0),
        n_scenarios=int(ns.sum()),
    )
    if first.decision_mask is not None:
        # Weekly-mode masks are (S,) and identical across chunks; breach
        # masks carry the scenario axis and concatenate along it.
        rep.decision_mask = (
            first.decision_mask if first.decision_mask.ndim == 1
            else np.concatenate([p.decision_mask for p in parts], axis=1)
        )
    if first.calibration is not None:
        cubes = [p.calibration for p in parts]
        rep.calibration = dataclasses.replace(
            cubes[0],
            levels=np.concatenate([c.levels for c in cubes], axis=1),
            hits=np.concatenate([c.hits for c in cubes], axis=1),
            pinball=np.concatenate([c.pinball for c in cubes], axis=1),
            realized_mean=np.concatenate(
                [c.realized_mean for c in cubes], axis=1
            ),
            realized_peak=np.concatenate(
                [c.realized_peak for c in cubes], axis=1
            ),
        )
    rep.total_cost = float(rep.scenario_cost.mean())
    rep.all_on_demand_cost = float(np.average(
        [p.all_on_demand_cost for p in parts], weights=ns
    ))
    rep.savings_vs_on_demand = (
        1.0 - rep.total_cost / rep.all_on_demand_cost
        if rep.all_on_demand_cost > 0 else 0.0
    )
    if rep.scenario_one_shot_cost is not None:
        rep.one_shot_cost = float(rep.scenario_one_shot_cost.mean())
        rep.savings_vs_one_shot = (
            1.0 - rep.total_cost / rep.one_shot_cost
            if rep.one_shot_cost > 0 else 0.0
        )
    if rep.scenario_hindsight_cost is not None:
        rep.hindsight_cost = float(rep.scenario_hindsight_cost.mean())
        rep.regret_vs_hindsight = (
            rep.total_cost / rep.hindsight_cost - 1.0
            if rep.hindsight_cost > 0 else 0.0
        )
    return rep


@dataclasses.dataclass(frozen=True)
class _ReplaySpec:
    """What the replay program's Python structure depends on, and nothing
    else: the jit key of :data:`_replay_program` beside the arguments'
    shapes, dtypes and shardings.  Every field comes from the request's
    settings or its shapes; a value computed from demand is never here,
    since the program would then be traced anew for every plan (and a
    program reused for another plan would answer with the first one's
    data)."""

    policy: pol.Policy
    cadence_weeks: int          # 0: the one-shot baseline's single decision
    mode: str                   # "weekly" or "breach"
    solver: str
    num_grid: int
    use_kernel: bool
    irls_iters: int
    irls_carry: bool
    horizon_weeks: int
    start_weeks: int
    total_weeks: int
    sched_len: int              # roll-off schedule length, weeks
    options: tuple              # the pool-pinned PurchaseOptions
    clouds: tuple               # the cloud of each pool of one scenario
    od: float
    num_scen: int               # scenario blocks on the row axis
    spot: bool
    migration: bool
    convertible: bool
    telemetry: "obs_config.TelemetryConfig | None"
    breach_band: tuple
    breach_tolerance: float


class _ReplayArrays(NamedTuple):
    """Every array the replay's step reads (None where a band is off)."""

    demand: jnp.ndarray                     # (R, T) whole weeks
    state: fc.PrefixFitState
    rates: jnp.ndarray                      # (K,)
    term_weeks: jnp.ndarray                 # (K,) int32
    avail: jnp.ndarray                      # (R, K)
    qs: jnp.ndarray                         # (R, K) handover fractiles
    al: jnp.ndarray                         # (R, K) option cost lines
    be: jnp.ndarray
    w_hours: jnp.ndarray                    # (H,) horizon prefix hours
    u_env: jnp.ndarray | None = None        # (R,) spot entry fractiles
    spot_lines: "spot_mod.SpotLines | None" = None
    share_state: "mg.SharePrefixState | None" = None
    edges: "gn.MigrationEdges | None" = None
    member: jnp.ndarray | None = None       # (C, P) cloud membership
    conv_al: jnp.ndarray | None = None      # (N*C, Kc) convertible lines
    conv_be: jnp.ndarray | None = None
    conv_qs: jnp.ndarray | None = None
    conv_terms: jnp.ndarray | None = None   # (Kc,) int32
    conv_rates: jnp.ndarray | None = None   # (Kc,)


def _pool_to_cloud(v, member, num_scen: int):
    """Aggregate per-pool rows (R, ...) onto the per-scenario cloud rows
    (N*C, ...) — block-diagonal membership without a widened contraction,
    so the cloud-total sum runs over exactly P terms, as unbatched."""
    highest = jax.lax.Precision.HIGHEST
    if num_scen == 1:
        return jnp.matmul(member, v, precision=highest)
    vs = v.reshape(num_scen, member.shape[1], *v.shape[1:])
    out = jnp.einsum("cp,sp...->sc...", member, vs, precision=highest)
    return out.reshape(num_scen * member.shape[0], *v.shape[1:])


def _replay_step(spec: _ReplaySpec, a: _ReplayArrays, zeros, solve_fn):
    """The replay's scan step and its initial carry, built from ``spec``,
    the arrays ``a`` and the carried stacks' initial ``zeros`` alone.
    ``solve_fn`` is the prefix refit: ``fc.solve_prefix`` in the compiled
    scan, ``fc.solve_prefix_direct`` in the Python-loop reference."""
    horizon_weeks = spec.horizon_weeks
    horizon_hours = horizon_weeks * HOURS_PER_WEEK
    num_rows, num_opts = a.demand.shape[0], len(spec.options)
    num_scen, num_pools = spec.num_scen, len(spec.clouds)
    od, tele = spec.od, spec.telemetry
    demand_wk = a.demand.reshape(
        num_rows, spec.total_weeks, HOURS_PER_WEEK
    )
    if spec.convertible:
        num_clouds, num_conv = a.member.shape[0], a.conv_rates.shape[0]
        num_cloud_rows = num_clouds * num_scen

    def grid_prefix_levels(yhat, al, be, num_rows, num_k):
        """Per-horizon stack tops via the over/under sweep on prefix-mask
        weights: horizon prefixes fold into the row axis so the whole
        (R x Wh, H, G) problem is one batched sweep (rows = pools for the
        standard options, clouds for the convertible residual)."""
        f_rep = jnp.repeat(yhat, horizon_weeks, axis=0)    # (R*Wh, H)
        t = jnp.arange(horizon_hours)
        masks = (t[None, :] < a.w_hours[:, None]).astype(yhat.dtype)
        w_rep = jnp.tile(masks, (num_rows, 1))
        plan = pf.optimal_portfolio_grid(
            f_rep,
            jnp.repeat(al, horizon_weeks, axis=0),
            jnp.repeat(be, horizon_weeks, axis=0),
            od_rate=od, num_grid=spec.num_grid, use_kernel=spec.use_kernel,
            weights=w_rep,
        )
        return plan.levels.reshape(num_rows, horizon_weeks, num_k)

    def spot_floors_for(yhat):
        """(P, W) per-horizon spot floors on one week's forecast: the
        envelope entry (below it a commitment prices better than spot) vs
        the chance-constraint volume cap, whichever is higher; +inf where
        the cap is 0 so an uneconomic spot market is never routed to."""
        cap = a.spot_lines.cap
        env_fl = jax.vmap(
            lambda y, q: _prefix_weighted_quantiles(
                y, a.w_hours, q[None]
            )[:, 0]
        )(yhat, a.u_env)
        vol_fl = jax.vmap(_prefix_spot_floors, in_axes=(0, None, 0))(
            yhat, a.w_hours, cap
        )
        floors = jnp.maximum(env_fl, vol_fl)
        return jnp.where(cap[:, None] > 0, floors, jnp.inf)

    def targets_for(yhat):
        """Algorithm 1 steps 2-4 on one week's forecast: per-horizon
        prefix thresholds -> min within each option's term -> monotone
        stack widths (P, K).  With spot, the per-horizon committed levels
        truncate at the spot floors first and the coming week's floor
        (horizon 1 — spot is re-decided weekly, so only the nearest
        horizon binds it) rides along as the fast-capacity decision."""
        if spec.solver == "grid":
            per_h = grid_prefix_levels(yhat, a.al, a.be, num_rows, num_opts)
        else:
            per_h = jax.vmap(
                lambda y, q: _prefix_weighted_quantiles(y, a.w_hours, q)
            )(yhat, a.qs)
        floor = None
        if spec.spot:
            floors = spot_floors_for(yhat)                 # (P, W)
            per_h = jnp.minimum(per_h, floors[..., None])
            floor = floors[:, 0]
        widths, _ = jax.vmap(
            lambda ph, q: _monotone_stack(ph, q, a.term_weeks, horizon_weeks)
        )(per_h, a.qs)
        return widths, floor

    def conv_targets_for(yhat, pool_top):
        """Cloud-level convertible targets on one week's forecast.

        The cloud *total* is the turnover-invariant series (demand moves
        between a cloud's families, it does not leave the cloud), so the
        safe cloud-level stack comes from the same per-horizon prefix
        thresholds -> term minima -> monotone stack machinery run on the
        summed forecast with the convertible cost lines.  Pools pin the
        bottom ``pool_top`` of that demand themselves (standard SKUs are
        cheaper), so the convertible bands are truncated below the summed
        pool targets: convertible buys exactly the band that is safe at
        cloud level but pinnable to no single family — the volume that
        migrates."""
        total_c = _pool_to_cloud(yhat, a.member, num_scen)   # (C, H)
        if spec.solver == "grid":
            per_h = grid_prefix_levels(
                total_c, a.conv_al, a.conv_be, num_cloud_rows, num_conv
            )
        else:
            per_h = jax.vmap(
                lambda y, q: _prefix_weighted_quantiles(y, a.w_hours, q)
            )(total_c, a.conv_qs)
        widths_c, tops_c = jax.vmap(
            lambda ph, q: _monotone_stack(ph, q, a.conv_terms, horizon_weeks)
        )(per_h, a.conv_qs)                                  # (C, Kc) x2
        return pf.truncate_convertible_stack(
            tops_c, widths_c, _pool_to_cloud(pool_top, a.member, num_scen)
        )                                                    # (C, Kc)

    # Migration recomposition as the policy hook: pair totals x rolling
    # logit-share fits become per-pool forecasts (the share state solves
    # on the same week prefix the structural fit did).
    if spec.migration:
        def compose_forecast(yhat, w):
            sa, sb = mg.solve_share_prefix(a.share_state, w)
            t_fut = w * HOURS_PER_WEEK + jnp.arange(horizon_hours)
            sh = mg.predict_share(sa, sb, t_fut, a.share_state.t_max)
            return mg.compose_forecast(yhat, sh, a.edges)
    else:
        compose_forecast = None

    # The full-harness policy context: ``targets_for`` carries the
    # configured solver (quantile or grid sweep) and the spot floors;
    # ``compose_forecast`` the migration recomposition.
    step_policy, mode = spec.policy, spec.mode
    pstate0, decide = step_policy.setup(pol.PolicyContext(
        demand=a.demand, options=list(spec.options),
        clouds=spec.clouds * num_scen, od=od, rates=a.rates,
        term_weeks=a.term_weeks, avail=a.avail, qs=a.qs, w_hours=a.w_hours,
        start_weeks=spec.start_weeks, cadence_weeks=spec.cadence_weeks,
        horizon_weeks=horizon_weeks, total_weeks=spec.total_weeks,
        state=a.state, solve_fn=solve_fn, irls_iters=spec.irls_iters,
        irls_carry=spec.irls_carry, targets_for=targets_for,
        compose_forecast=compose_forecast, cadence_mode=mode,
        breach_band=spec.breach_band,
        breach_tolerance=spec.breach_tolerance, scenario_blocks=num_scen,
    ))
    needs_prev = step_policy.needs_prev_demand or mode == "breach"
    # The trailing realized window anchoring the fractile bands (spread
    # from realized hours, level from the forecast).  Only breach cadence
    # and calibration telemetry pay for the gather.
    needs_trail = mode == "breach" or (tele is not None and tele.calibration)
    sched_len = spec.sched_len

    def step(carry, w):
        if not spec.convertible:
            active, rolloff, pstate = carry
        else:
            active, rolloff, pstate, active_c, rolloff_c = carry
        # 1. tranches whose term ends at week w roll off the stack
        expired = jax.lax.dynamic_index_in_dim(
            rolloff, w, axis=2, keepdims=False
        )
        active = active - expired
        # 2-4. the policy decides this week's target stack (for the
        # default rolling policy: prefix refit -> horizon forecast ->
        # solver targets, op for op the pre-policy scan body).  Buys
        # happen only on decision weeks and only as increments —
        # surpluses persist until their tranches expire.  The spot
        # floor is NOT carried: it is this week's fast-capacity
        # decision, re-derived from scratch on every step.
        d_prev = (
            jax.lax.dynamic_index_in_dim(
                demand_wk, w - 1, axis=1, keepdims=False
            )
            if needs_prev else None
        )
        d_trail = None
        if needs_trail:
            # (R, TRAIL_WEEKS, 168) -> (R, TRAIL_WEEKS*168); the
            # dynamic-slice start clamps, so the first replayed weeks
            # of a short start simply see a shifted-but-valid window.
            d_trail = jax.lax.dynamic_slice_in_dim(
                demand_wk, w - fc.TRAIL_WEEKS, fc.TRAIL_WEEKS, axis=1
            ).reshape(demand_wk.shape[0], -1)
        pstate, dec = decide(
            pstate,
            pol.Observation(
                week=w, active=active, d_prev=d_prev, d_trail=d_trail
            ),
        )
        widths, floor, yhat, is_dec = (
            dec.targets, dec.floor, dec.yhat, dec.is_decision
        )
        # Weekly cadences emit a scalar is_dec and the masks below
        # broadcast it exactly as before; breach mode emits a per-row
        # (R,) vector, lifted to a column at trace time so the weekly
        # compiled program is untouched.
        vec_dec = getattr(is_dec, "ndim", 0) >= 1
        dec_p = is_dec[:, None] if vec_dec else is_dec
        if spec.convertible:
            # Cloud-row view of the mask: breach decisions are
            # uniform within a scenario block, so each scenario's
            # pool-row flag replicates onto its cloud rows.
            dec_c = (
                jnp.repeat(
                    is_dec.reshape(num_scen, num_pools)[:, 0],
                    num_clouds,
                )[:, None]
                if vec_dec else is_dec
            )
        if not spec.convertible:
            inc = jnp.maximum(widths - active, 0.0)
            inc = jnp.where(
                dec_p & (inc > ld.PURCHASE_EPS), inc, 0.0
            )
            active = active + inc
        else:
            # Convertible pass, decided BEFORE the standard buys: roll
            # off, size the cloud-level band (cloud-total stack
            # truncated below the pool targets), buy increments into
            # the cloud-level carry, then re-pin the live width onto
            # the pools with the largest gaps between forecast and
            # their pinned stacks.  Live convertible capacity then
            # *suppresses* new standard purchases pro rata — a
            # tranche that migrated from a dying family serves the
            # successor instead of the successor re-buying pinned
            # capacity under it (the unstranding this SKU class
            # exists for).
            expired_c = jax.lax.dynamic_index_in_dim(
                rolloff_c, w, axis=2, keepdims=False
            )
            active_c = active_c - expired_c
            # Truncate below the HIGHER of this week's targets and the
            # carried stack: surplus standard tranches (targets fell,
            # tranches persist to term) already cover their band — a
            # convertible bought there would bill the same demand
            # twice.
            pool_top = jnp.maximum(widths.sum(-1), active.sum(-1))
            with jax.named_scope("solve"):
                widths_c = conv_targets_for(yhat, pool_top)
            inc_c = jnp.maximum(widths_c - active_c, 0.0)
            inc_c = jnp.where(
                dec_c & (inc_c > ld.PURCHASE_EPS), inc_c, 0.0
            )
            active_c = active_c + inc_c
            expiry_c = jax.nn.one_hot(
                w + a.conv_terms, sched_len, dtype=rolloff_c.dtype
            )
            rolloff_c = rolloff_c + (
                inc_c[:, :, None] * expiry_c[None, :, :]
            )
            # Allocation need keys on the coming week's forecast PEAK:
            # allocating sunk capacity is free, and a mean-based need
            # would leave the diurnal peaks billing at on-demand.
            week1 = yhat[:, :HOURS_PER_WEEK].max(-1)
            need = jnp.maximum(week1 - active.sum(-1), 0.0)
            if num_scen == 1:
                alloc = allocate_convertible(
                    active_c.sum(-1), need, a.member
                )
            else:
                # Per-scenario-block allocation with the base (C, P)
                # membership — same program per block as unbatched.
                alloc = jax.vmap(
                    lambda wv, nv: allocate_convertible(wv, nv, a.member)
                )(
                    active_c.sum(-1).reshape(num_scen, num_clouds),
                    need.reshape(num_scen, num_pools),
                ).reshape(num_rows)
            desired = jnp.maximum(widths - active, 0.0)
            lift = desired.sum(-1)                     # (P,)
            scale = jnp.where(
                lift > ld.PURCHASE_EPS,
                jnp.maximum(lift - alloc, 0.0)
                / jnp.maximum(lift, 1e-9),
                0.0,
            )
            inc = desired * scale[:, None]
            inc = jnp.where(
                dec_p & (inc > ld.PURCHASE_EPS), inc, 0.0
            )
            active = active + inc
        expiry = jax.nn.one_hot(
            w + a.term_weeks, sched_len, dtype=rolloff.dtype
        )                                              # (K, sched)
        rolloff = rolloff + inc[:, :, None] * expiry[None, :, :]
        # 5. bill the week: committed rates regardless of use,
        # shortfall above the stack top at the on-demand rate — or,
        # with a spot band, on-demand only up to the floor and the
        # effective spot rate above it.  A convertible allocation
        # lifts each pool's effective level for the week (the tranche
        # itself bills at cloud level whether or not it is pinned).
        d = jax.lax.dynamic_index_in_dim(
            demand_wk, w, axis=1, keepdims=False
        )                                              # (P, 168)
        level = active.sum(-1)
        committed = (a.rates * active).sum(-1) * HOURS_PER_WEEK
        if spec.convertible:
            level = level + alloc
        used = jnp.minimum(d, level[:, None]).sum(-1)
        util = jnp.where(
            level > 0, used / (level * HOURS_PER_WEEK), 0.0
        )
        if not spec.spot:
            over = jnp.maximum(d - level[:, None], 0.0).sum(-1)
            out = {
                "target": widths, "inc": inc, "active": active,
                "committed": committed, "od": od * over, "util": util,
                "is_dec": is_dec,
            }
        else:
            fl = jnp.maximum(floor, level)
            over = jnp.maximum(
                jnp.minimum(d, fl[:, None]) - level[:, None], 0.0
            ).sum(-1)
            spot_over = jnp.maximum(d - fl[:, None], 0.0)
            out = {
                "target": widths, "inc": inc, "active": active,
                "committed": committed, "od": od * over, "util": util,
                "is_dec": is_dec,
                "floor": fl,
                "spot_vol": spot_over.sum(-1),
                "spot": a.spot_lines.rate * spot_over.sum(-1),
                "spot_peak": spot_over.max(-1),
            }
        if tele is not None and tele.ledger:
            # Ledger-only outputs, emitted ONLY when telemetry is on:
            # per-SKU committed spend plus the usage split the ledger
            # turns into idle hours and on-demand volume.  With
            # telemetry=None these keys do not exist and the compiled
            # program is the exact pre-telemetry one (golden-tested).
            out["committed_k"] = a.rates * active * HOURS_PER_WEEK
            out["used"] = used
            out["od_vol"] = over
        if tele is not None and tele.calibration:
            # Calibration-only output: the anchored fractile levels
            # of this week's forecast over the week being billed,
            # scored host-side against that week's realized demand.
            out["calib_levels"] = fc.anchored_fractile_levels(
                d_trail, tele.fractiles
            )
        if tele is not None and tele.provenance:
            # Provenance-only outputs: the roll-offs this week and
            # the spot-cap binding flag (the stack top hit the spot
            # floor, so the floor — not the envelope — sized it).
            out["prov_expired"] = expired
            if spec.spot:
                out["prov_spot_bound"] = (
                    widths.sum(-1) >= floor - 1e-3
                )
        if dec.extras is not None:
            # Policy-authored per-week extras (breach mode emits the
            # active band as band_lo/band_hi); None on the default
            # paths, so weekly programs gain nothing.
            out.update(dec.extras)
        if not spec.convertible:
            return (active, rolloff, pstate), out
        out.update({
            "conv_target": widths_c, "conv_inc": inc_c,
            "conv_active": active_c, "conv_alloc": alloc,
            "conv_committed": (
                (a.conv_rates * active_c).sum(-1) * HOURS_PER_WEEK
            ),
        })
        if tele is not None and tele.ledger:
            out["conv_committed_k"] = (
                a.conv_rates * active_c * HOURS_PER_WEEK
            )
        if tele is not None and tele.provenance:
            out["prov_conv_expired"] = expired_c
            # Convertible suppression: this pool wanted a standard
            # buy (lift) and live convertible capacity was allocated
            # over it, scaling the purchase down.
            out["prov_conv_sup"] = (
                (alloc > ld.PURCHASE_EPS) & (lift > ld.PURCHASE_EPS)
            )
        return (active, rolloff, pstate, active_c, rolloff_c), out
    return step, (*zeros[:2], pstate0, *zeros[2:])


#: How many times :func:`scan` has been traced in this process.
_replay_traces = 0


def scan(spec: _ReplaySpec, arrays: _ReplayArrays, zeros, ws):
    """The compiled replay: the policy's set-up and the scan over the
    weeks ``ws``, from the carried stacks' initial ``zeros``.  Its body
    runs only while JAX traces it, so it counts the traces.  Its name
    gives the compiled module the name ``jit_scan`` that the eager scan
    had, which the benchmark's device-time reader matches."""
    global _replay_traces
    _replay_traces += 1
    step, carry0 = _replay_step(spec, arrays, zeros, fc.solve_prefix)
    return jax.lax.scan(step, carry0, ws)[1]


#: The replay program the process keeps: traced and lowered once for each
#: spec and argument signature (shapes, dtypes, shardings), then reused.
_replay_program = jax.jit(scan, static_argnames=("spec",))


def _replay_scan(stage: str, spec: _ReplaySpec, arrays: _ReplayArrays,
                 zeros, ws):
    """The scan outputs of the compiled replay.  A stage ``stage`` opened
    once the call returns carries ``scan_lowerings``: how many times the
    call traced and lowered the program (0 where it was kept)."""
    before = _replay_traces
    ys = _replay_program(spec, arrays, zeros, ws)
    with obs_spans.stage(stage, scan_lowerings=_replay_traces - before):
        pass
    return ys


def _validate(total_weeks: int, start_weeks: int, cadence_weeks: int):
    if cadence_weeks < 1:
        raise ValueError(f"cadence_weeks must be >= 1, got {cadence_weeks}")
    if not 1 <= start_weeks < total_weeks:
        raise ValueError(
            f"start_weeks={start_weeks} must leave history and an "
            f"evaluation window inside {total_weeks} whole trace weeks"
        )


def replan_fleet_pools(
    pools: dm.PoolSet,
    options: list[pf.PurchaseOption] | None = None,
    *,
    cadence_weeks: int = 1,
    start_weeks: int | None = None,
    horizon_weeks: int = 8,
    od_rate: float | None = None,
    term_weighting: float = 0.0,
    cfg: fc.ForecastConfig = fc.ForecastConfig(),
    solver: Literal["quantile", "grid"] = "quantile",
    num_grid: int = 128,
    use_kernel: bool = False,
    irls_iters: int = 0,
    backend: Literal["scan", "loop"] = "scan",
    compare: bool = True,
    spot: "spot_mod.SpotConfig | bool | None" = None,
    migration: "gn.MigrationConfig | bool | None" = None,
    convertible: "list[pf.PurchaseOption] | bool | None" = None,
    policy: "pol.Policy | str | None" = None,
    scenarios: "sc.ScenarioConfig | int | None" = None,
    irls_carry: bool = False,
    telemetry: "obs_config.TelemetryConfig | bool | None" = None,
    cadence: Literal["weekly", "breach"] = "weekly",
    breach_band: tuple = (0.05, 0.95),
    breach_tolerance: float = 4.0,
    _scen_slice: tuple[int, int] | None = None,
) -> RollingPlanReport:
    """Replay the rolling re-planning loop over ``pools``.

    The first ``start_weeks`` weeks are pure history (default: a quarter of
    the trace, at least ``horizon_weeks``); every week after that is
    forecast, (on cadence weeks) re-planned, and billed.  ``irls_iters``
    adds asymmetric-error IRLS passes to each weekly refit — exact but a
    full masked design pass per week, so the default keeps the pure
    prefix-sum fit (the one-shot planner's IRLS matters most when a fit
    must survive unrevised for months; a weekly refit corrects drift
    faster than the reweighting does).  With ``compare`` the one-shot and
    hindsight baselines are replayed on the same window.

    ``spot`` adds the preemptible band (``core.spot``): committed tranches
    are the *slow* capacity the scan carries (bought incrementally, rolled
    off at term), while the spot floor is *fast* — re-derived every week
    from that week's forecast with no carry at all, since spot holds no
    term.  Weekly billing then splits three ways: committed rates below
    the stack top, on-demand between stack top and floor, the risk-priced
    effective spot rate above the floor.  The one-shot baseline replays
    with the same spot band; hindsight stays commitments-only.  With
    ``spot=None`` (default) the scan program is unchanged bit for bit.

    ``migration`` makes the weekly forecasts *turnover-aware*
    (``core.migration``): wherever the successor table matches an
    (old family, successor) pool pair, the structural forecaster fits the
    pair total in old-equivalent units (turnover-invariant) and a rolling
    logit-share fit carries the S-curve, so a migrating family's decay is
    forecast as share transfer instead of permanent organic decline — the
    failure mode that keeps migration-blind replans buying tranches on a
    dying family.  One extra prefix-sum state (five moments per edge per
    week) rides the same scan.

    ``convertible`` adds the cloud-level exchangeable SKUs
    (``pricing.CONVERTIBLE_PLANS``): each week, after the pool-pinned
    targets are decided, the *residual* cloud-level demand — forecast
    above the pool stacks, summed per cloud — is solved against the
    convertible cost lines, increments are bought into a cloud-level
    tranche stack the scan carries next to the pool-level one, and the
    live convertible width is re-pinned onto the cloud's pools
    proportionally to each pool's forecast excess
    (:func:`allocate_convertible`).  A migrating family's demand can
    therefore ride one convertible tranche across the family boundary
    instead of stranding a pinned tranche and re-buying on the successor.
    With ``migration=None`` and ``convertible=None`` (defaults) every
    code path is bit-identical to the pre-migration planner.

    ``policy`` selects the weekly decision rule (``core.policy``): a
    :class:`repro.core.policy.Policy` instance, a registry name, or None
    for the paper's :class:`~repro.core.policy.RollingPortfolioPolicy` —
    the pre-refactor scan body op for op, so ``policy=None`` replays are
    bit-identical to the pre-policy planner (golden-tested).  The spot,
    migration and convertible bands all key on the weekly forecast, so
    they require a forecasting policy; the hedging policies are
    forecast-free and run commitments-only.  The ``compare`` baselines
    always replay the standard one-shot and hindsight references,
    whichever policy drives the main replay.

    ``scenarios`` batches the replay over N demand futures derived from
    the realized trace (``data.scenarios.ScenarioConfig``; an int means
    that many "realized" copies).  The (N, P) block is flattened into the
    scan's row axis, so one compiled program replays every scenario;
    reports grow per-scenario cost/CR/regret distributions and an N axis
    on the per-week arrays (see :class:`RollingPlanReport`).
    ``irls_carry`` makes ``irls_iters > 0`` cheap inside the replay by
    carrying the asymmetric-weight moments in the scan state (frozen-
    weights incremental IRLS) instead of full masked passes per week.

    ``telemetry`` (``repro.obs``; None/False default, True, or a
    :class:`~repro.obs.config.TelemetryConfig`) turns on the cost-
    attribution layer: the scan additionally emits per-SKU committed
    spend and usage hours — still trace-pure, still deterministic — and
    the report gains a :class:`~repro.obs.ledger.CostLedger` whose weekly
    row-sums reconcile with ``weekly_cost`` plus, for the grid solver,
    the :class:`~repro.obs.kernelstats.KernelStats` of the sweep shape.
    With ``telemetry=None`` no extra scan outputs exist, so every replay
    compiles the exact pre-telemetry program (golden-tested).

    ``TelemetryConfig(calibration=True)`` additionally emits each week's
    forecast fractile levels (``tele.fractiles``) from the scan and
    scores them against realized demand as a
    :class:`~repro.obs.calibration.CalibrationCube` — per (week x pool x
    fractile) hit indicators, empirical coverage vs nominal, interval
    widths and pinball loss, with per-scenario-family distributions when
    scenario-batched.  ``provenance=True`` emits per-week decision
    records (buys per SKU, roll-offs, binding constraint: envelope vs
    spot cap vs convertible suppression) materialized as a
    :class:`~repro.obs.provenance.DecisionLog`.  Both require a
    forecasting policy (calibration scores the forecast) and, like the
    ledger, add ZERO scan outputs when off.

    ``cadence="breach"`` (with ``cadence_weeks=1``) replaces the weekly
    decision grid with band-breach triggering: the policy re-solves only
    in weeks where last week's realized demand spent more than
    ``breach_tolerance x`` the nominal miss mass of its hours outside
    the ``breach_band`` fractile pair of the forecast made at the last
    decision (plus the mandatory start week).  The mask is computed
    in-scan through the policy ``Decision.is_decision`` carry; the
    default ``cadence="weekly"`` path stays bit-identical.
    """
    with obs_spans.stage("replan"):
        options = options if options is not None else pf.options_from_pricing()
        od = od_rate if od_rate is not None else pricing.on_demand_premium()
        total_weeks = pools.num_hours // HOURS_PER_WEEK
        if start_weeks is None:
            start_weeks = min(max(horizon_weeks, total_weeks // 4),
                              max(total_weeks - 1, 1))
        _validate(total_weeks, start_weeks, cadence_weeks)
        if cadence not in ("weekly", "breach"):
            raise ValueError(
                f"unknown cadence {cadence!r}; known: ('weekly', 'breach')"
            )
        if cadence == "breach" and cadence_weeks != 1:
            raise ValueError(
                "cadence='breach' evaluates every week and masks decisions "
                f"itself; use cadence_weeks=1, got {cadence_weeks}"
            )
        tele = obs_config.resolve_telemetry(telemetry)

        scen = sc.resolve_scenarios(scenarios)
        if (
            scen is not None and _scen_slice is None
            and scen.chunk is not None and scen.chunk < scen.n_scenarios
        ):
            # Memory relief on one host: sequential compiled chunks over
            # scenario sub-batches, merged back into one report.
            parts = [
                replan_fleet_pools(
                    pools, options, cadence_weeks=cadence_weeks,
                    start_weeks=start_weeks, horizon_weeks=horizon_weeks,
                    od_rate=od, term_weighting=term_weighting, cfg=cfg,
                    solver=solver, num_grid=num_grid, use_kernel=use_kernel,
                    irls_iters=irls_iters, backend=backend, compare=compare,
                    spot=spot, migration=migration, convertible=convertible,
                    policy=policy, scenarios=scen, irls_carry=irls_carry,
                    telemetry=tele, cadence=cadence, breach_band=breach_band,
                    breach_tolerance=breach_tolerance,
                    _scen_slice=(lo, min(lo + scen.chunk, scen.n_scenarios)),
                )
                for lo in range(0, scen.n_scenarios, scen.chunk)
            ]
            return _merge_scenario_reports(parts)

        num_pools, num_opts = pools.num_pools, len(options)
        horizon_hours = horizon_weeks * HOURS_PER_WEEK
        t_hist = total_weeks * HOURS_PER_WEEK
        if scen is None:
            num_scen = 1
            row_clouds = pools.clouds
        else:
            lo, hi = (
                _scen_slice if _scen_slice is not None
                else (0, scen.n_scenarios)
            )
            num_scen = hi - lo
            # The generator's draws run on the host; the futures are built
            # on the device from them and the realized rows.
            with obs_spans.stage(
                "replan/scenarios", device_rows=num_scen * num_pools
            ):
                batch = sc.scenario_batch(
                    pools.demand[:, :t_hist], scen, lo, hi
                )
            row_clouds = pools.clouds * num_scen
        # Bytes placed on the device: the realized rows, and on scenario
        # replays the generator's draws beside them.
        placed = (
            num_pools * t_hist * 4 if scen is None
            else sc.placed_bytes(scen, num_pools, t_hist, lo, hi)
        )
        with obs_spans.stage("replan/place_rows", h2d_bytes=placed):
            if scen is None:
                demand = jnp.asarray(pools.demand[:, :t_hist], jnp.float32)
            else:
                # Flatten (N, P) -> N*P rows: every per-pool op in the harness
                # is row-elementwise or vmapped, so the scenario axis rides the
                # pool axis through one compiled scan.  Scenario 0 (the
                # realized trace) occupies the first P rows; rows shard over
                # local devices when more than one exists (no-op,
                # bit-identical, on one device).  The (N, P, T) batch is
                # dropped here: the scan's rows are the only copy left.
                demand = mesh_mod.shard_rows(
                    batch.reshape(num_scen * num_pools, t_hist)
                )
                del batch
        num_rows = demand.shape[0]
        # The scenario axis materializes on report arrays only for a true
        # batch (chunked sub-replays always carry it so chunks concatenate).
        scen_axis = scen is not None and (
            num_scen > 1 or _scen_slice is not None
        )

        # Eager set-up dispatches ahead of the scan: option lines, handover
        # fractiles, the band set-ups and the forecaster's prefix state.
        with obs_spans.stage("replan/prepare"):
            al_p, be_p, avail_p = pf.pool_option_lines(
                options, row_clouds, term_weighting=term_weighting, od_rate=od
            )
            qs = jax.vmap(
                functools.partial(pf.handover_fractiles, od_rate=od)
            )(al_p, be_p)                                              # (P, K)
            sp_res = spot_mod.resolve_spot(spot, row_clouds, od_rate=od)
            if sp_res is not None:
                s_cfg, s_lines = sp_res
                u_env = jax.vmap(
                    lambda a_, b_, r_: spot_mod.spot_entry_fractile(
                        a_, b_, r_, od_rate=od
                    )
                )(al_p, be_p, s_lines.rate)                            # (P,)
            rates = jnp.asarray([o.rate for o in options], jnp.float32)
            term_weeks = jnp.asarray(
                [o.term_weeks for o in options], jnp.int32
            )

            # Migration awareness: the structural forecaster fits pair
            # totals (the old-family rows replaced by old + (1+uplift) x
            # successor), a share prefix state rides along, and each
            # week's per-pool forecasts are recomposed from total x share
            # inside the step.
            mig_cfg = gn.resolve_migration(migration)
            edges = (
                gn.migration_edges(pools.keys, mig_cfg)
                if mig_cfg is not None else None
            )
            if edges is not None and num_scen > 1:
                edges = _tile_edges(edges, num_scen, num_pools)
            use_mig = edges is not None and edges.num_edges > 0
            fit_demand = (
                mg.transform_for_fit(demand, edges) if use_mig else demand
            )

            # Convertible band: cloud-level SKUs next to the pool-pinned
            # options.
            conv_opts = pf.resolve_convertible(convertible, pools.clouds)
            if conv_opts is not None:
                conv_clouds, member, al_c, be_c, qs_c, conv_terms = (
                    pf.convertible_cloud_setup(
                        conv_opts, pools.clouds, term_weighting=term_weighting,
                        od_rate=od,
                    )
                )
                num_clouds, num_conv = len(conv_clouds), len(conv_opts)
                if num_scen > 1:
                    # Each scenario owns a private copy of the cloud axis —
                    # convertible capacity must not pool across futures
                    # that never co-occur.  The per-cloud lines tile; the
                    # membership matrix stays (C, P) and is applied per
                    # scenario block (see ``_pool_to_cloud``) so the
                    # cloud-total contraction runs over exactly P terms —
                    # the same float reduction order as the unbatched
                    # replay, keeping scenario 0 bit-identical.
                    al_c = jnp.tile(al_c, (num_scen, 1))
                    be_c = jnp.tile(be_c, (num_scen, 1))
                    qs_c = jnp.tile(qs_c, (num_scen, 1))
                num_cloud_rows = num_clouds * num_scen

                conv_rates = jnp.asarray(
                    [o.rate for o in conv_opts], jnp.float32
                )
                max_term = max(int(term_weeks.max()), int(conv_terms.max()))
            else:
                max_term = int(term_weeks.max())
            sched_len = total_weeks + max_term + 1
            w_hours = jnp.arange(1, horizon_weeks + 1) * HOURS_PER_WEEK

            pcy = pol.get_policy(policy)
            if not pcy.forecasting:
                bands = [
                    name for name, on in [
                        ("spot", sp_res is not None), ("migration", use_mig),
                        ("convertible", conv_opts is not None),
                    ] if on
                ]
                if bands:
                    raise ValueError(
                        f"policy {pcy.name!r} does not forecast, but "
                        f"{'/'.join(bands)} bands key on the weekly forecast; "
                        "use a forecasting policy or disable the bands"
                    )
                if tele is not None and tele.calibration:
                    raise ValueError(
                        f"policy {pcy.name!r} does not forecast, but "
                        "TelemetryConfig(calibration=True) scores the weekly "
                        "forecast fractiles; use a forecasting policy"
                    )
                if cadence == "breach":
                    raise ValueError(
                        f"policy {pcy.name!r} does not forecast, but "
                        "cadence='breach' triggers on the forecast band; "
                        "use a forecasting policy"
                    )

            # The forecaster's state: the design, the prefix Gram, the
            # right-hand sides and, with migration, the share moments.
            prefix_bytes = fc.prefix_state_bytes(
                num_rows, t_hist, cfg, horizon_hours=horizon_hours,
                min_prefix_hours=start_weeks * HOURS_PER_WEEK,
            ) + (mg.share_state_bytes(edges.num_edges, total_weeks)
                 if use_mig else 0)
            with obs_spans.stage(
                "replan/prepare/forecast", prefix_bytes=prefix_bytes
            ):
                state = fc.prefix_fit_state(
                    fit_demand, cfg, horizon_hours=horizon_hours,
                    min_prefix_hours=start_weeks * HOURS_PER_WEEK,
                )
                share_state = (
                    mg.share_prefix_state(
                        demand, edges, t_max=state.t_max,
                        prior_weight=mig_cfg.share_prior_weight,
                    )
                    if use_mig else None
                )

        spec = _ReplaySpec(
            policy=pcy, cadence_weeks=cadence_weeks, mode=cadence,
            solver=solver, num_grid=num_grid, use_kernel=use_kernel,
            irls_iters=irls_iters, irls_carry=irls_carry,
            horizon_weeks=horizon_weeks, start_weeks=start_weeks,
            total_weeks=total_weeks, sched_len=sched_len,
            options=tuple(options), clouds=tuple(pools.clouds), od=float(od),
            num_scen=num_scen, spot=sp_res is not None, migration=use_mig,
            convertible=conv_opts is not None, telemetry=tele,
            breach_band=tuple(float(q) for q in breach_band),
            breach_tolerance=float(breach_tolerance),
        )
        arrays = _ReplayArrays(
            demand=demand, state=state, rates=rates, term_weeks=term_weeks,
            avail=avail_p, qs=qs, al=al_p, be=be_p, w_hours=w_hours,
        )
        if sp_res is not None:
            arrays = arrays._replace(u_env=u_env, spot_lines=s_lines)
        if use_mig:
            arrays = arrays._replace(share_state=share_state, edges=edges)
        if conv_opts is not None:
            arrays = arrays._replace(
                member=member, conv_al=al_c, conv_be=be_c, conv_qs=qs_c,
                conv_terms=conv_terms, conv_rates=conv_rates,
            )

        def zeros():
            """The carried stacks' initial zeros.  The per-row carries
            start where the rows live, not whole on the default device
            (the roll-off schedule is 656 MB at R=32768)."""
            out = (
                jnp.zeros((num_rows, num_opts), jnp.float32,
                          device=demand.sharding),
                jnp.zeros((num_rows, num_opts, sched_len), jnp.float32,
                          device=demand.sharding),
            )
            if conv_opts is None:
                return out
            return out + (
                jnp.zeros((num_cloud_rows, num_conv), jnp.float32),
                jnp.zeros((num_cloud_rows, num_conv, sched_len), jnp.float32),
            )

        with obs_spans.stage("replan/scan", phase="compile"):
            if backend == "scan":
                ys = _replay_scan(
                    "replan/scan/lower", spec, arrays, zeros(),
                    jnp.arange(start_weeks, total_weeks),
                )
            else:
                # Naive python-level replay: one full prefix
                # re-accumulation and one host dispatch per week (what the
                # compiled scan replaces).
                step, carry = _replay_step(
                    spec, arrays, zeros(), fc.solve_prefix_direct
                )
                outs = []
                for w in range(start_weeks, total_weeks):
                    carry, out = step(carry, jnp.int32(w))
                    outs.append(out)
                ys = {
                    key: jnp.stack([o[key] for o in outs]) for key in outs[0]
                }
        # The pull waits for the device, then copies every scan output.
        with obs_spans.stage(
            "replan/pull", phase="execute",
            d2h_bytes=sum(int(v.nbytes) for v in ys.values()),
        ):
            ys = {k_: np.asarray(v) for k_, v in ys.items()}
        # Host post-processing: tranche books, per-scenario totals, the report
        # and its telemetry layers, and (compare=True) the baselines.
        with obs_spans.stage("replan/post"):
            weeks = np.arange(start_weeks, total_weeks)
            with obs_spans.stage("replan/post/books"):
                # The purchases as a tranche book: per-week targets (0 outside
                # decision weeks, so the ladder planner's "never below active"
                # rule buys exactly the scan's increments) threaded through the
                # portfolio ladder.  With a convertible band the solver targets
                # are NOT what was bought (live convertible capacity suppresses
                # standard purchases), so the book replays the scan's realized
                # post-purchase stack instead.
                targets_full = np.zeros(
                    (num_pools, total_weeks, num_opts), np.float32
                )
                # the policy's decision weeks
                dec_raw = ys.pop("is_dec").astype(bool)
                # Weekly cadences emit one scalar flag per week; breach
                # mode emits a per-row (R,) vector, uniform within each
                # scenario block.  Books and baselines key on scenario 0 —
                # the realized trace, i.e. the first P rows of the flattened
                # batch (the whole batch on single-path runs).
                dec = dec_raw[:, 0] if dec_raw.ndim == 2 else dec_raw
                book_targets = (
                    ys["target"] if conv_opts is None else ys["active"]
                )[:, :num_pools]
                targets_full[:, weeks[dec]] = np.swapaxes(
                    book_targets[dec], 0, 1
                )
                term_hours = np.asarray(
                    [o.term_weeks * HOURS_PER_WEEK for o in options]
                )
                ladders = ld.plan_pool_portfolio_purchases(
                    targets_full, term_hours, pools.keys
                )
                if sp_res is not None:
                    # The fast half of the split as a tranche book: spot is a
                    # ladder whose every tranche lasts exactly one period
                    # (re-decided, never carried), sized at the week's peak
                    # spot usage (scenario 0).
                    spot_ladders = ld.spot_ladder_book(
                        ys["spot_peak"][:, :num_pools], pools.keys,
                        start_week=start_weeks,
                    )
                if conv_opts is not None:
                    # The cloud-level tranche book: same increment-only
                    # semantics as the pool book, so its live widths must
                    # reconcile with the scan's carried cloud-level stack every
                    # week (tested).  Scenario 0 rows.
                    conv_full = np.zeros(
                        (len(conv_clouds), total_weeks, len(conv_opts)),
                        np.float32,
                    )
                    conv_full[:, weeks[dec]] = np.swapaxes(
                        ys["conv_target"][:, :num_clouds][dec], 0, 1
                    )
                    conv_ladders = ld.convertible_ladder_book(
                        conv_full,
                        np.asarray(
                            [o.term_weeks * HOURS_PER_WEEK for o in conv_opts]
                        ),
                        conv_clouds,
                    )
            with obs_spans.stage("replan/post/totals"):
                total = float(ys["committed"].sum() + ys["od"].sum())
                if sp_res is not None:
                    total += float(ys["spot"].sum())
                if conv_opts is not None:
                    total += float(ys["conv_committed"].sum())
                eval_demand = demand[:, start_weeks * HOURS_PER_WEEK:]
                all_od = od * float(eval_demand.sum())
                scen_cost = None
                if scen is not None:
                    # Per-scenario replay cost, sliced row-block by
                    # row-block in the same summation order as the
                    # single-path totals — so the N=1 realized batch
                    # reproduces them bit for bit — and the scalar
                    # aggregates become means over scenarios.
                    def _srows(a, s, rows=num_pools):
                        return a[:, s * rows:(s + 1) * rows]

                    def _scen_total(s):
                        cs = float(
                            _srows(ys["committed"], s).sum()
                            + _srows(ys["od"], s).sum()
                        )
                        if sp_res is not None:
                            cs += float(_srows(ys["spot"], s).sum())
                        if conv_opts is not None:
                            cs += float(_srows(
                                ys["conv_committed"], s, num_clouds
                            ).sum())
                        return cs

                    scen_cost = np.asarray(
                        [_scen_total(s) for s in range(num_scen)]
                    )
                    scen_all_od = np.asarray([
                        od * float(eval_demand[
                            s * num_pools:(s + 1) * num_pools
                        ].sum())
                        for s in range(num_scen)
                    ])
                    total = float(scen_cost.mean())
                    all_od = float(scen_all_od.mean())
            with obs_spans.stage("replan/post/report"):
                def _rep(a, rows=num_pools):
                    """Report view of a per-week (S, R, ...) array: insert the
                    N axis on true scenario batches, pass through otherwise."""
                    if not scen_axis:
                        return a
                    return a.reshape(a.shape[0], num_scen, rows, *a.shape[2:])

                report = RollingPlanReport(
                    keys=pools.keys,
                    options=options,
                    cadence_weeks=cadence_weeks,
                    start_weeks=start_weeks,
                    horizon_weeks=horizon_weeks,
                    weeks=weeks,
                    targets=_rep(ys["target"]),
                    increments=_rep(ys["inc"]),
                    active=_rep(ys["active"]),
                    committed_cost=_rep(ys["committed"]),
                    on_demand_cost=_rep(ys["od"]),
                    utilization=_rep(ys["util"]),
                    ladders=ladders,
                    total_cost=total,
                    all_on_demand_cost=all_od,
                    savings_vs_on_demand=(
                        1.0 - total / all_od if all_od > 0 else 0.0
                    ),
                    policy_name=pcy.name,
                    n_scenarios=num_scen,
                    scenario_family=scen.family if scen is not None else None,
                    scenario_cost=scen_cost,
                    od_rate=float(od),
                    scenario_config=scen,
                )
                report.cadence = cadence
                if dec_raw.ndim == 1:
                    report.decision_mask = dec_raw                   # (S,)
                elif scen_axis:
                    # Breach masks are uniform within a scenario block, so one
                    # flag per (week, scenario) is the whole story.
                    report.decision_mask = dec_raw.reshape(
                        len(weeks), num_scen, num_pools
                    )[:, :, 0]                                       # (S, N)
                else:
                    report.decision_mask = dec                       # (S,)
                if "band_lo" in ys:
                    report.breach_band_lo = _rep(ys["band_lo"])
                    report.breach_band_hi = _rep(ys["band_hi"])
                if sp_res is not None:
                    report.spot_config = s_cfg
                    report.spot_lines = s_lines
                    report.spot_floor = _rep(ys["floor"])
                    report.spot_cost = _rep(ys["spot"])
                    report.spot_volume = _rep(ys["spot_vol"])
                    report.spot_ladders = spot_ladders
                if use_mig:
                    report.migration_config = mig_cfg
                    report.migration_edges = edges
                if conv_opts is not None:
                    report.conv_options = conv_opts
                    report.conv_clouds = tuple(conv_clouds)
                    report.conv_targets = _rep(ys["conv_target"], num_clouds)
                    report.conv_increments = _rep(ys["conv_inc"], num_clouds)
                    report.conv_active = _rep(ys["conv_active"], num_clouds)
                    report.conv_alloc = _rep(ys["conv_alloc"])
                    report.conv_committed_cost = _rep(
                        ys["conv_committed"], num_clouds
                    )
                    report.conv_ladders = conv_ladders
                if tele is not None:
                    report.telemetry = tele
                    if tele.kernel_stats and solver == "grid":
                        # The batched sweep shape the grid solver launches
                        # each decision week: horizon prefixes fold into the
                        # row axis (see ``grid_prefix_levels``).
                        report.kernel_stats = obs_kstats.sweep_kernel_stats(
                            num_rows * horizon_weeks, num_grid, horizon_hours,
                        )
                    if tele.ledger:
                        report.committed_by_sku = _rep(ys["committed_k"])
                        report.used_hours = _rep(ys["used"])
                        report.od_volume = _rep(ys["od_vol"])
                        if conv_opts is not None:
                            report.conv_committed_by_sku = _rep(
                                ys["conv_committed_k"], num_clouds
                            )
                        report.ledger = obs_ledger.ledger_from_report(report)
                    if tele.calibration:
                        # Score the scan-emitted fractile levels against
                        # the demand the scan actually billed — every
                        # scenario out of one scan.
                        report.fractile_levels = _rep(ys["calib_levels"])
                        realized = np.swapaxes(
                            np.asarray(demand).reshape(
                                num_rows, total_weeks, HOURS_PER_WEEK
                            )[:, start_weeks:, :], 0, 1
                        )                                    # (S, R, 168)
                        report.calibration = obs_calib.calibration_from_arrays(
                            weeks, ["/".join(k) for k in pools.keys],
                            tele.fractiles,
                            ys["calib_levels"], realized,
                            n_scenarios=num_scen,
                            meta={
                                "policy": pcy.name,
                                "cadence": cadence,
                                "scenario_family": (
                                    scen.family if scen is not None else None
                                ),
                            },
                        )
                    if tele.provenance:
                        # Queryable decision records on scenario 0,
                        # matching the tranche books and the ledger.
                        prov_kw = {}
                        if sp_res is not None:
                            prov_kw["spot_bound"] = (
                                ys["prov_spot_bound"][:, :num_pools]
                            )
                        if conv_opts is not None:
                            prov_kw.update(
                                conv_suppressed=(
                                    ys["prov_conv_sup"][:, :num_pools]
                                ),
                                conv_clouds=conv_clouds,
                                conv_skus=[o.name for o in conv_opts],
                                conv_term_weeks=[
                                    o.term_weeks for o in conv_opts
                                ],
                                conv_increments=ys["conv_inc"][:, :num_clouds],
                                conv_rolloffs=(
                                    ys["prov_conv_expired"][:, :num_clouds]
                                ),
                                conv_active=ys["conv_active"][:, :num_clouds],
                            )
                        report.decision_log = obs_prov.decision_log_from_arrays(
                            weeks, ["/".join(k) for k in pools.keys],
                            [o.name for o in options],
                            [o.term_weeks for o in options],
                            is_decision=dec,
                            targets=ys["target"][:, :num_pools],
                            increments=ys["inc"][:, :num_pools],
                            rolloffs=ys["prov_expired"][:, :num_pools],
                            active=ys["active"][:, :num_pools],
                            purchase_eps=float(ld.PURCHASE_EPS),
                            meta={"policy": pcy.name, "cadence": cadence},
                            **prov_kw,
                        )
            if not compare:
                return report
            # The hindsight baseline pulls the evaluated demand whole.
            with obs_spans.stage(
                "replan/post/baselines", d2h_bytes=int(eval_demand.nbytes)
            ):
                # One-shot baseline: identical replay, single decision week
                # (with the same spot/convertible bands when enabled — the
                # baselines differ in commitment cadence, not in which
                # purchasing options exist).  Always driven by the standard
                # rolling policy so a custom ``policy=`` is still scored
                # against the paper's reference points.
                one = _replay_scan(
                    "replan/post/baselines/lower",
                    dataclasses.replace(
                        spec, policy=pol.RollingPortfolioPolicy(),
                        cadence_weeks=0, mode="weekly",
                    ),
                    arrays, zeros(), jnp.arange(start_weeks, total_weeks),
                )
                one_weekly = _rep(
                    np.asarray(one["committed"] + one["od"])
                ).sum(-1)
                if sp_res is not None:
                    one_weekly = one_weekly + _rep(
                        np.asarray(one["spot"])
                    ).sum(-1)
                if conv_opts is not None:
                    one_weekly = one_weekly + _rep(
                        np.asarray(one["conv_committed"]), num_clouds
                    ).sum(-1)
                report.one_shot_weekly_cost = one_weekly
                if scen is not None:
                    scen_one = (
                        one_weekly.sum(0) if scen_axis
                        else np.asarray([one_weekly.sum()])
                    )
                    report.scenario_one_shot_cost = scen_one
                    report.one_shot_cost = float(scen_one.mean())
                else:
                    report.one_shot_cost = float(one_weekly.sum())
                report.savings_vs_one_shot = (
                    1.0 - total / report.one_shot_cost
                    if report.one_shot_cost > 0 else 0.0
                )

                # Hindsight baseline: the optimal constant stack on realized
                # demand (billing lines, i.e. term_weighting=0: every active
                # tranche bills its rate; expiring short tranches are
                # repurchased back-to-back).
                al0, be0, _ = pf.pool_option_lines(
                    options, row_clouds, term_weighting=0.0, od_rate=od
                )
                hs = jax.vmap(
                    lambda f_, a_, b_: pf.optimal_portfolio_stack(
                        f_, a_, b_, od_rate=od
                    )
                )(eval_demand, al0, be0)
                hs_widths = np.asarray(hs.widths)
                hs_level = hs_widths.sum(-1)
                ed_wk = np.asarray(eval_demand).reshape(
                    num_rows, len(weeks), HOURS_PER_WEEK
                )
                hs_over = np.maximum(
                    ed_wk - hs_level[:, None, None], 0.0
                ).sum(-1)
                hs_committed = (
                    (np.asarray(rates) * hs_widths).sum(-1) * HOURS_PER_WEEK
                )
                hs_weekly = hs_committed[:, None] + od * hs_over      # (R, S)
                report.hindsight_widths = hs_widths
                report.hindsight_weekly_cost = hs_weekly.sum(0)
                report.hindsight_cost = float(hs_weekly.sum())
                if scen is not None:
                    scen_hind = np.asarray([
                        float(hs_weekly[
                            s * num_pools:(s + 1) * num_pools
                        ].sum())
                        for s in range(num_scen)
                    ])
                    report.scenario_hindsight_cost = scen_hind
                    report.hindsight_cost = float(scen_hind.mean())
                    report.scenario_cr = scen_cost / scen_hind
                    report.scenario_regret = scen_cost - scen_hind
                    if scen_axis:
                        report.hindsight_widths = hs_widths.reshape(
                            num_scen, num_pools, num_opts
                        )
                        report.hindsight_weekly_cost = hs_weekly.reshape(
                            num_scen, num_pools, len(weeks)
                        ).sum(1).T                                    # (S, N)
                report.regret_vs_hindsight = (
                    total / report.hindsight_cost - 1.0
                    if report.hindsight_cost > 0 else 0.0
                )
                return report
