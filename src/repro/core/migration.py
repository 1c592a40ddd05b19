"""Demand-driver decomposition + share-based forecasting (paper §2.3).

The **inference** side of the generation-turnover subsystem (the generative
side is ``repro.capacity.generations``): given a realized fleet, fit the
three drivers the paper says compose demand —

    per-pool VM demand = fleet user growth x family adoption share
                         x software efficiency

— and forecast *family share x pair total* instead of raw per-pool traces.
A per-pool structural fit sees a mid-migration family as organic decay (or
explosive growth on the successor side) and extrapolates it linearly in
log-space; the S-curve then accelerates past the fit on one side and
flattens under it on the other.  The share-based forecaster removes the
turnover driver before fitting: the *pair total in old-equivalent units*
(old + (1 + uplift) x successor) is turnover-invariant, so the structural
forecaster fits a stable series, and the turnover itself is carried by a
2-parameter logistic share fit — weighted least squares on the logit, which
is exactly linear in time for a logistic adoption curve.

Everything is prefix-sum friendly so the rolling replanner
(``repro.core.replan``) re-fits both pieces every week inside its
``lax.scan``: the pair-total rows ride the existing
``forecast.prefix_fit_state`` normal equations, and the share fit keeps
five cumulative weekly sums per edge (a 2x2 solve per week —
:class:`SharePrefixState` / :func:`solve_share_prefix`).

:func:`decompose_drivers` is the offline report: fitted logistic
midpoints/spans per edge, the hardware-corrected fleet trend, and — when an
independent user-volume series is supplied (the paper measured query volume
and the Snowflake Performance Index separately; volume and efficiency are
multiplicatively confounded in VM counts alone) — the software-efficiency
drift."""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.capacity import generations as gn
from repro.core import demand as dm
from repro.core import forecast as fc
from repro.core.demand import HOURS_PER_WEEK

# Observed shares are clipped into [SHARE_EPS, 1 - SHARE_EPS] before the
# logit: a successor pool with literally zero demand is "not launched yet",
# not infinitely unlaunched.
SHARE_EPS = 1e-5
_RIDGE = 1e-6


def share_observations(
    demand: jnp.ndarray, edges: gn.MigrationEdges
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(z, w) each (G, T): per-edge logit of the successor's share of the
    pair total in old-equivalent units, and its logistic-regression weight
    s(1 - s) — near-zero weight where the share pins to a clipped extreme,
    so pre-launch hours barely move the fit."""
    d = jnp.asarray(demand, jnp.float32)
    old = d[edges.src]                                  # (G, T)
    new_adj = d[edges.dst] * (1.0 + edges.uplift[:, None])
    total = old + new_adj
    s = jnp.where(total > 0, new_adj / jnp.maximum(total, 1e-12), 0.0)
    s = jnp.clip(s, SHARE_EPS, 1.0 - SHARE_EPS)
    z = jnp.log(s) - jnp.log1p(-s)
    return z, s * (1.0 - s)


def _wls_line(sw, swt, swt2, swz, swtz):
    """Weighted least-squares line z ~ a + b t from the five moment sums
    (broadcasts over any leading axes)."""
    denom = sw * swt2 - swt * swt + _RIDGE
    b = (sw * swtz - swt * swz) / denom
    a = (swz - b * swt) / jnp.maximum(sw, 1e-9)
    return a, b


def _prior_moments(
    edges: gn.MigrationEdges, t_max: float, weight: float
) -> jnp.ndarray:
    """(G, 5) pseudo-observation moments encoding the successor table's
    announced S-curve as a prior on the logit-share line: two points of
    total weight ``weight`` at normalized times 0 and 1 on the table's
    line z(t) = rate (t - midpoint).  Pre-launch — when every real share
    observation sits at a clipped extreme with weight ~ 0 — the prior IS
    the fit; once adoption is underway the data weights (thousands of
    hours) swamp it."""
    b0 = edges.rate_per_hour * t_max
    a0 = -edges.rate_per_hour * edges.midpoint_hours
    half = weight / 2.0
    return jnp.stack(
        [
            jnp.full_like(a0, weight),           # sum w
            jnp.full_like(a0, half),             # sum w t   (t in {0, 1})
            jnp.full_like(a0, half),             # sum w t^2
            half * (2.0 * a0 + b0),              # sum w z
            half * (a0 + b0),                    # sum w t z
        ],
        axis=-1,
    )


def fit_share(
    demand: jnp.ndarray,
    edges: gn.MigrationEdges,
    *,
    t_max: float,
    prior_weight: float = 0.0,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(a, b) each (G,): full-window logit-share line fits, time normalized
    by ``t_max`` (same convention as the forecaster's trend columns so the
    two extrapolate on one clock).  predicted share = sigmoid(a + b t/t_max).

    ``prior_weight`` blends in the table's announced adoption curve (see
    :func:`_prior_moments`); 0 fits the data alone."""
    z, w = share_observations(demand, edges)
    t = jnp.arange(z.shape[-1], dtype=jnp.float32) / t_max
    sums = [
        w.sum(-1),
        (w * t).sum(-1),
        (w * t * t).sum(-1),
        (w * z).sum(-1),
        (w * t * z).sum(-1),
    ]
    if prior_weight > 0:
        prior = _prior_moments(edges, t_max, prior_weight)
        sums = [s + prior[:, i] for i, s in enumerate(sums)]
    return _wls_line(*sums)


def predict_share(
    a: jnp.ndarray, b: jnp.ndarray, t_hours: jnp.ndarray, t_max: float
) -> jnp.ndarray:
    """(G, H) logistic share forecast at absolute hours ``t_hours``."""
    ts = jnp.asarray(t_hours, jnp.float32) / t_max
    return jax.nn.sigmoid(a[:, None] + b[:, None] * ts[None, :])


def transform_for_fit(
    demand: jnp.ndarray, edges: gn.MigrationEdges
) -> jnp.ndarray:
    """Replace each edge's old-family row by the pair total in
    old-equivalent units — the turnover-invariant series the structural
    forecaster should fit.  Successor rows are left as-is (their fits are
    overwritten by the share composition and never read)."""
    d = jnp.asarray(demand, jnp.float32)
    total = d[edges.src] + d[edges.dst] * (1.0 + edges.uplift[:, None])
    return d.at[edges.src].set(total)


def compose_forecast(
    yhat_total: jnp.ndarray,
    shares: jnp.ndarray,
    edges: gn.MigrationEdges,
) -> jnp.ndarray:
    """Recombine pair-total forecasts (P, H) with share forecasts (G, H)
    into per-pool forecasts: the old family keeps (1 - s) of the pair
    total, the successor serves s of it at 1/(1 + uplift) VMs per
    old-equivalent unit."""
    tot = yhat_total[edges.src]                          # (G, H)
    y = yhat_total.at[edges.src].set((1.0 - shares) * tot)
    return y.at[edges.dst].set(
        shares * tot * edges.inv_gain[:, None]
    )


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SharePrefixState:
    """Cumulative weekly moment sums for rolling logit-share re-fits.

    ``cum[g, w]`` holds [sum w, sum w t, sum w t^2, sum w z, sum w t z]
    over the first w+1 whole weeks of edge g's share observations (time
    normalized by ``t_max``), so the week-w share fit inside the replay
    scan is one gather + a closed-form 2x2 solve — the share-fit analogue
    of ``forecast.PrefixFitState``."""

    cum: jnp.ndarray       # (G, W, 5)
    t_max: jnp.ndarray     # scalar, forecast-state time normalization


def share_prefix_state(
    demand: jnp.ndarray,
    edges: gn.MigrationEdges,
    *,
    t_max: float,
    period_hours: int = HOURS_PER_WEEK,
    prior_weight: float = 0.0,
) -> SharePrefixState:
    """Build the rolling share-fit state for a (P, T) fleet (T truncated to
    whole periods, matching ``forecast.prefix_fit_state``).  The prior
    moments, if any, ride inside every prefix (the announced-launch prior
    binds hardest exactly when the prefix holds no adoption signal)."""
    return SharePrefixState(
        cum=_share_cum(
            jnp.asarray(demand, jnp.float32), edges, t_max=float(t_max),
            period_hours=period_hours, prior_weight=float(prior_weight),
        ),
        t_max=jnp.float32(t_max),
    )


@functools.partial(
    jax.jit, static_argnames=("t_max", "period_hours", "prior_weight")
)
def _share_cum(demand, edges, t_max, period_hours, prior_weight):
    """(G, W, 5) cumulative weekly moments of :func:`share_prefix_state`,
    in one compiled call: no (G, T) intermediate outlives it, so the
    device memory it takes does not hang on how far the host has run
    ahead of the device."""
    z, w = share_observations(demand, edges)
    g = z.shape[0]
    num_weeks = z.shape[-1] // period_hours
    t_hist = num_weeks * period_hours
    t = jnp.arange(t_hist, dtype=jnp.float32) / t_max
    z, w = z[:, :t_hist], w[:, :t_hist]
    moments = jnp.stack(
        [w, w * t, w * t * t, w * z, w * t * z], axis=-1
    )                                                    # (G, T, 5)
    weekly = moments.reshape(g, num_weeks, period_hours, 5).sum(2)
    cum = jnp.cumsum(weekly, axis=1)
    if prior_weight > 0:
        cum = cum + _prior_moments(edges, t_max, prior_weight)[:, None, :]
    return cum


def share_state_bytes(num_edges: int, num_weeks: int) -> int:
    """Bytes of the :class:`SharePrefixState` of ``num_edges`` edges over
    ``num_weeks`` whole periods: the float32 moments and ``t_max``."""
    return 4 * (num_edges * num_weeks * 5 + 1)


def solve_share_prefix(
    state: SharePrefixState, week
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(a, b) each (G,) fit on the prefix of ``week`` whole periods —
    scan-safe (``week`` may be traced, >= 1)."""
    c = jax.lax.dynamic_index_in_dim(
        state.cum, week - 1, axis=1, keepdims=False
    )                                                    # (G, 5)
    return _wls_line(c[:, 0], c[:, 1], c[:, 2], c[:, 3], c[:, 4])


@dataclasses.dataclass
class EdgeFit:
    """One fitted turnover edge, reported in table units."""

    cloud: str
    region: str
    old_family: str
    new_family: str
    uplift: float
    midpoint_weeks: float    # fitted 50%-adoption epoch
    span_weeks: float        # fitted 10%->90% width
    final_share: float       # fitted share at the end of the window


@dataclasses.dataclass
class DriverDecomposition:
    """The three fitted demand drivers of a realized fleet.

    ``edge_fits`` carry the per-family logistic turnover; ``fleet_model``
    is the structural fit of the hardware-corrected fleet total (user
    growth x software efficiency — the turnover driver removed);
    ``efficiency_per_year`` separates the software driver out of that
    product when an independent user-volume series was supplied, else
    None.  ``hardware_index`` is the realized VM-count multiplier of
    turnover: raw fleet total over old-equivalent total (< 1 once
    adoption of a faster family is underway)."""

    keys: tuple[dm.PoolKey, ...]
    edges: gn.MigrationEdges
    share_a: np.ndarray            # (G,) logit intercepts (t / t_max clock)
    share_b: np.ndarray            # (G,) logit slopes
    t_max: float
    edge_fits: list[EdgeFit]
    fleet_model: fc.ForecastModel
    hardware_index: np.ndarray     # (T,)
    efficiency_per_year: float | None
    growth_per_year: float | None  # user-volume trend when supplied

    def predicted_shares(self, t_hours: jnp.ndarray) -> np.ndarray:
        return np.asarray(predict_share(
            jnp.asarray(self.share_a), jnp.asarray(self.share_b),
            t_hours, self.t_max,
        ))


def _log_slope_per_year(series: np.ndarray) -> float:
    """OLS slope of log(series) per year of hours."""
    y = np.log(np.maximum(np.asarray(series, np.float64), 1e-12))
    t = np.arange(y.shape[-1], dtype=np.float64) / gn.HOURS_PER_YEAR
    t = t - t.mean()
    return float((t * (y - y.mean())).sum() / np.maximum((t * t).sum(), 1e-12))


def decompose_drivers(
    pools: dm.PoolSet,
    *,
    migration: "gn.MigrationConfig | bool | None" = True,
    user_volume: np.ndarray | None = None,
    cfg: fc.ForecastConfig = fc.ForecastConfig(),
) -> DriverDecomposition:
    """Fit the three-driver decomposition to a realized fleet.

    ``migration`` supplies the successor *structure* (which family pairs
    can turn over, and their published perf uplifts); the adoption epochs
    themselves are fitted from the data, never read from the table.
    ``user_volume`` (T,) is the independent demand-driver measurement
    (query volume in old-equivalent VM units); with it the software-
    efficiency drift is identified as the log-slope of corrected-VM-total
    over user volume, without it user growth and efficiency stay folded
    into ``fleet_model``'s trend (they are multiplicatively confounded in
    VM counts alone — the paper separates them with the SPI)."""
    mig = gn.resolve_migration(migration)
    if mig is None:
        # False/None mean "migration off" everywhere else in this
        # subsystem; silently substituting the default successor table
        # here would invert that contract.
        raise ValueError(
            "decompose_drivers needs a successor structure; pass "
            "migration=True (pricing.GENERATIONS) or a MigrationConfig"
        )
    edges = gn.migration_edges(pools.keys, mig)
    demand = jnp.asarray(pools.demand, jnp.float32)
    t_hist = pools.num_hours
    t_max = float(max(t_hist - 1, 1))

    a, b = fit_share(demand, edges, t_max=t_max)
    a_np, b_np = np.asarray(a, np.float64), np.asarray(b, np.float64)
    src_np = np.asarray(edges.src)
    dst_np = np.asarray(edges.dst)
    up_np = np.asarray(edges.uplift, np.float64)
    edge_fits = []
    for g in range(edges.num_edges):
        rate_hr = b_np[g] / t_max                  # logit slope per hour
        wk = HOURS_PER_WEEK
        mid = -a_np[g] / rate_hr / wk if abs(rate_hr) > 1e-12 else np.inf
        span = (
            gn._LOGISTIC_1090 / rate_hr / wk
            if abs(rate_hr) > 1e-12 else np.inf
        )
        key_old, key_new = pools.keys[src_np[g]], pools.keys[dst_np[g]]
        edge_fits.append(EdgeFit(
            cloud=key_old[0], region=key_old[1],
            old_family=key_old[2], new_family=key_new[2],
            uplift=float(up_np[g]),
            midpoint_weeks=float(mid),
            span_weeks=float(span),
            final_share=float(
                jax.nn.sigmoid(a_np[g] + b_np[g] * (t_hist - 1) / t_max)
            ),
        ))

    # Hardware-corrected fleet total: successors counted at (1 + uplift)
    # VMs of old-equivalent work — the turnover driver divided out.
    perf = np.ones(pools.num_pools, np.float64)
    perf[dst_np] = 1.0 + up_np
    corrected = (np.asarray(pools.demand, np.float64) * perf[:, None]).sum(0)
    raw_total = pools.demand.sum(0)
    fleet_model = fc.fit(jnp.asarray(corrected, jnp.float32), cfg)
    hardware_index = raw_total / np.maximum(corrected, 1e-12)

    efficiency = growth = None
    if user_volume is not None:
        user_volume = np.asarray(user_volume, np.float64)
        if user_volume.shape[-1] != t_hist:
            raise ValueError(
                f"user_volume length {user_volume.shape[-1]} != "
                f"{t_hist} fleet hours"
            )
        # corrected / user = (1 + r)^(-t/yr): slope recovers the drift.
        slope = _log_slope_per_year(
            corrected / np.maximum(user_volume, 1e-12)
        )
        efficiency = float(np.expm1(-slope))
        growth = float(np.expm1(_log_slope_per_year(user_volume)))

    return DriverDecomposition(
        keys=pools.keys,
        edges=edges,
        share_a=np.asarray(a),
        share_b=np.asarray(b),
        t_max=t_max,
        edge_fits=edge_fits,
        fleet_model=fleet_model,
        hardware_index=np.asarray(hardware_index, np.float32),
        efficiency_per_year=efficiency,
        growth_per_year=growth,
    )
