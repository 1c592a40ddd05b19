"""JAX structural time-series forecaster (paper §3.3.3, Prophet replacement).

The paper fits Prophet [Taylor & Letham 2018] with a *weighted* error metric
whose asymmetry matches the cost asymmetry (under-forecast pays 2.1x
on-demand; over-forecast pays 1x unused commitment).  We replace Prophet with
a JAX-native decomposable model over hourly data

    y_t = trend(t) * seasonality(t) * holiday(t) * (1 + eps)

fit in log-space as a linear model:

    log y = beta . [1, t, relu(t - cp_1..K),            # piecewise trend
                    fourier_daily, fourier_weekly, fourier_yearly,
                    holiday_dummy]

solved by ridge-regularized weighted least squares (normal equations), with
IRLS reweighting to realize the asymmetric error metric: residuals where the
model under-forecasts get weight ``asym`` (=A/B=2.1), over-forecasts weight 1.
The whole fit is jit-able and vmappable over thousands of pools.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.core.demand import DAYS_PER_YEAR, HOURS_PER_DAY, HOURS_PER_WEEK

HOURS_PER_YEAR = HOURS_PER_DAY * DAYS_PER_YEAR

#: Every float32 contraction here runs at full float32 precision.  A TPU
#: rounds matmul inputs to bfloat16 at default precision, which puts a
#: ~2% error on an exponentiated log-demand forecast and perturbs the
#: badly conditioned normal equations (cond ~5e4 at a 3-year prefix);
#: the CPU ignores the setting, so its programs are unchanged.
_HI = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class ForecastConfig:
    daily_order: int = 4        # Fourier harmonics per period
    weekly_order: int = 6
    yearly_order: int = 8
    num_changepoints: int = 8   # evenly spaced piecewise-linear trend knots
    ridge: float = 1e-3
    asym_weight: float = 2.1    # paper footnote 2: under-forecast costs 2.1x
    irls_iters: int = 4
    holiday_start_day: int = 357  # Dec 24 (day-of-year, 0-based)
    holiday_len_days: int = 9


def _fourier(t: jnp.ndarray, period: float, order: int) -> jnp.ndarray:
    """(T, 2*order) Fourier design block."""
    k = jnp.arange(1, order + 1, dtype=jnp.float32)
    ang = 2.0 * jnp.pi * t[:, None] * k[None, :] / period
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def design_matrix(
    t_hours: jnp.ndarray, cfg: ForecastConfig, t_max: float
) -> jnp.ndarray:
    """Feature matrix X (T, D).  ``t_max`` fixes changepoint locations so the
    same basis extends consistently into the future."""
    t = t_hours.astype(jnp.float32)
    ts = t / t_max  # normalized time for trend columns
    cols = [jnp.ones_like(ts)[:, None], ts[:, None]]
    if cfg.num_changepoints:
        cps = jnp.linspace(0.1, 0.9, cfg.num_changepoints)
        cols.append(jnp.maximum(ts[:, None] - cps[None, :], 0.0))
    cols.append(_fourier(t, HOURS_PER_DAY, cfg.daily_order))
    cols.append(_fourier(t, HOURS_PER_WEEK, cfg.weekly_order))
    cols.append(_fourier(t, HOURS_PER_YEAR, cfg.yearly_order))
    day_of_year = jnp.mod(t // HOURS_PER_DAY, DAYS_PER_YEAR)
    holiday = (
        (day_of_year >= cfg.holiday_start_day)
        & (day_of_year < cfg.holiday_start_day + cfg.holiday_len_days)
    ).astype(jnp.float32)
    cols.append(holiday[:, None])
    return jnp.concatenate(cols, axis=-1)


@dataclasses.dataclass
class ForecastModel:
    beta: jnp.ndarray  # (D,)
    t_max: float
    cfg: ForecastConfig


def _solve_wls(x, y, w, ridge):
    xw = x * w[:, None]
    gram = jnp.matmul(xw.T, x, precision=_HI) + ridge * jnp.eye(
        x.shape[1], dtype=x.dtype
    )
    rhs = jnp.matmul(xw.T, y, precision=_HI)
    return jnp.linalg.solve(gram, rhs)


@functools.partial(jax.jit, static_argnames=("cfg",))
def _fit(y: jnp.ndarray, cfg: ForecastConfig, t_max: float):
    t = jnp.arange(y.shape[-1], dtype=jnp.float32)
    x = design_matrix(t, cfg, t_max)
    logy = jnp.log(jnp.maximum(y, 1e-6))

    beta = _solve_wls(x, logy, jnp.ones_like(logy), cfg.ridge)

    def irls_step(beta, _):
        resid = logy - jnp.matmul(x, beta, precision=_HI)
        # Under-forecast (actual above prediction) weighted ``asym`` heavier.
        w = jnp.where(resid > 0, cfg.asym_weight, 1.0)
        return _solve_wls(x, logy, w, cfg.ridge), None

    beta, _ = jax.lax.scan(irls_step, beta, None, length=cfg.irls_iters)
    return beta


def fit(y: jnp.ndarray, cfg: ForecastConfig = ForecastConfig()) -> ForecastModel:
    """Fit on an hourly history ``y`` (T,). Returns a ForecastModel.

    Yearly Fourier terms are disabled automatically when the history is
    shorter than ~1.2 years: with less than one full cycle observed they are
    unidentifiable and extrapolate wildly (the same guard Prophet applies).
    """
    if y.shape[-1] < 1.2 * HOURS_PER_YEAR and cfg.yearly_order:
        cfg = dataclasses.replace(cfg, yearly_order=0)
    t_max = float(max(y.shape[-1] - 1, 1))
    beta = _fit(y, cfg, t_max)
    return ForecastModel(beta=beta, t_max=t_max, cfg=cfg)


def predict(model: ForecastModel, t_hours: jnp.ndarray) -> jnp.ndarray:
    """Predict demand at absolute hour indices ``t_hours`` (may be future)."""
    x = design_matrix(t_hours.astype(jnp.float32), model.cfg, model.t_max)
    return jnp.exp(jnp.matmul(x, model.beta, precision=_HI))


def forecast_horizon(
    model: ForecastModel, t_start: int, num_hours: int
) -> jnp.ndarray:
    """Forecast ``num_hours`` starting at absolute hour ``t_start`` (Step 1 of
    Algorithm 1 uses num_hours = 52*7*24)."""
    t = t_start + jnp.arange(num_hours)
    return predict(model, t)


def weighted_mape(
    y_true: jnp.ndarray, y_pred: jnp.ndarray, asym: float = 2.1
) -> jnp.ndarray:
    """The paper's asymmetric error metric (footnote 2): under-forecast errors
    (y_true > y_pred, i.e. we'd pay on-demand) cost ``asym`` x more."""
    err = (y_true - y_pred) / jnp.maximum(y_true, 1e-9)
    w = jnp.where(err > 0, asym, 1.0)
    return (w * jnp.abs(err)).mean(-1)


def _ridge_solve(gram: jnp.ndarray, rhs: jnp.ndarray, ridge: float):
    """Solve (gram + ridge I) beta = rhs for one shared gram and a batch of
    right-hand sides rhs (P, D) -> (P, D)."""
    g = gram + ridge * jnp.eye(gram.shape[-1], dtype=gram.dtype)
    return jnp.linalg.solve(g, rhs.T).T


@dataclasses.dataclass(frozen=True)
class PrefixFitState:
    """Precomputed normal-equation state for *rolling* prefix re-fits.

    The rolling planner re-fits the forecaster every week on the extended
    demand prefix.  Re-running :func:`fit_batched` per week costs a full
    O(T D^2) design pass per refit; but with one FIXED design matrix (time
    normalization ``t_max`` and changepoint locations pinned to the full
    trace so every week solves in the same basis), the week-w normal
    equations are *prefix sums* of per-week blocks:

        gram_prefix[w] = sum_{t < (w+1) 168} x_t x_t^T     (pool-shared)
        rhs_prefix[p, w] = sum_{t < (w+1) 168} x_t log y_{p,t}

    so a refit inside ``lax.scan`` is one (D, D) gather + ridge solve —
    O(D^3) per week instead of O(T D^2) — which is what makes a multi-year
    replay one compiled program (see ``repro.core.replan``).

    Unweighted (the IRLS asymmetry reweights per-residual and therefore
    needs a full masked pass; :func:`irls_refine` provides it as an optional
    exact refinement on top of the prefix solve).
    """

    x: jnp.ndarray            # (T + H, D) design over history + horizon
    gram_prefix: jnp.ndarray  # (W, D, D) cumulative X^T X per week prefix
    rhs_prefix: jnp.ndarray   # (P, W, D) cumulative X^T log y per prefix
    logy: jnp.ndarray         # (P, T) log-space targets
    cfg: ForecastConfig
    t_max: float
    num_hist_hours: int
    period_hours: int

    @property
    def num_weeks(self) -> int:
        return self.gram_prefix.shape[0]


def prefix_fit_state(
    ys: jnp.ndarray,
    cfg: ForecastConfig = ForecastConfig(),
    *,
    horizon_hours: int,
    period_hours: int = HOURS_PER_WEEK,
    min_prefix_hours: int | None = None,
) -> PrefixFitState:
    """Build the rolling-refit state for a (P, T) pool batch.

    ``min_prefix_hours`` is the shortest prefix any refit will see: the
    short-history guard on the yearly Fourier terms keys on it (the one-shot
    ``fit`` keys the same guard on its single history length).  T is
    truncated to whole periods."""
    ys = jnp.asarray(ys, jnp.float32)
    num_weeks = ys.shape[-1] // period_hours
    t_hist = num_weeks * period_hours
    ys = ys[..., :t_hist]
    guard_hours = t_hist if min_prefix_hours is None else min_prefix_hours
    if guard_hours < 1.2 * HOURS_PER_YEAR and cfg.yearly_order:
        cfg = dataclasses.replace(cfg, yearly_order=0)
    t_max = float(max(t_hist - 1, 1))
    t_all = jnp.arange(t_hist + horizon_hours, dtype=jnp.float32)
    x = design_matrix(t_all, cfg, t_max)
    xh = x[:t_hist]
    d = xh.shape[-1]
    xw = xh.reshape(num_weeks, period_hours, d)
    gram_prefix = jnp.cumsum(
        jnp.einsum("wtd,wte->wde", xw, xw, precision=_HI), axis=0
    )
    logy = jnp.log(jnp.maximum(ys, 1e-6))
    lw = logy.reshape(ys.shape[0], num_weeks, period_hours)
    rhs_prefix = jnp.cumsum(
        jnp.einsum("wtd,pwt->pwd", xw, lw, precision=_HI), axis=1
    )
    return PrefixFitState(
        x=x, gram_prefix=gram_prefix, rhs_prefix=rhs_prefix, logy=logy,
        cfg=cfg, t_max=t_max, num_hist_hours=t_hist,
        period_hours=period_hours,
    )


def solve_prefix(state: PrefixFitState, week) -> jnp.ndarray:
    """beta (P, D) fit on the prefix of ``week`` whole periods — one gather
    into the cumulative normal equations + a ridge solve.  ``week`` may be a
    traced integer (scan-safe); must be >= 1."""
    g = jax.lax.dynamic_index_in_dim(
        state.gram_prefix, week - 1, axis=0, keepdims=False
    )
    r = jax.lax.dynamic_index_in_dim(
        state.rhs_prefix, week - 1, axis=1, keepdims=False
    )
    return _ridge_solve(g, r, state.cfg.ridge)


def solve_prefix_direct(state: PrefixFitState, week) -> jnp.ndarray:
    """The same prefix fit computed the naive way: mask the full design and
    re-accumulate the normal equations from scratch, O(T D^2) per call.
    This is the python-loop replay baseline the scan path is benched
    against; it differs from :func:`solve_prefix` only in float summation
    order."""
    xh = state.x[: state.num_hist_hours]
    t = jnp.arange(state.num_hist_hours)
    mask = (t < week * state.period_hours).astype(xh.dtype)
    xm = xh * mask[:, None]
    g = jnp.matmul(xm.T, xh, precision=_HI)
    r = jnp.einsum("td,pt->pd", xm, state.logy, precision=_HI)
    return _ridge_solve(g, r, state.cfg.ridge)


def irls_refine(
    state: PrefixFitState, beta: jnp.ndarray, week, iters: int
) -> jnp.ndarray:
    """Optional asymmetric-error refinement of a prefix fit: ``iters`` IRLS
    passes over the masked prefix (under-forecast residuals weighted
    ``cfg.asym_weight``).  Each pass is a full O(P T D^2) masked
    accumulation — exact but W-times more expensive inside a replay, hence
    opt-in (``iters=0`` keeps the pure prefix-sum path)."""
    if iters == 0:
        return beta
    xh = state.x[: state.num_hist_hours]
    t = jnp.arange(state.num_hist_hours)
    mask = (t < week * state.period_hours).astype(xh.dtype)
    eye = state.cfg.ridge * jnp.eye(xh.shape[-1], dtype=xh.dtype)
    for _ in range(iters):
        resid = state.logy - jnp.matmul(beta, xh.T, precision=_HI)  # (P, T)
        w = jnp.where(resid > 0, state.cfg.asym_weight, 1.0) * mask
        g = jnp.einsum(
            "pt,td,te->pde", w, xh, xh, precision=_HI
        )                                                    # (P, D, D)
        r = jnp.einsum("pt,td->pd", w * state.logy, xh, precision=_HI)
        beta = jax.vmap(lambda gi, ri: jnp.linalg.solve(gi + eye, ri))(g, r)
    return beta


def solve_prefix_adjusted(
    state: PrefixFitState, week, gram_adj: jnp.ndarray, rhs_adj: jnp.ndarray
) -> jnp.ndarray:
    """Prefix fit with carried IRLS weight-adjustment moments.

    The asymmetric weights ``w = 1 + (asym-1)[resid > 0]`` split the
    weighted normal equations into the unweighted prefix sums (already in
    ``state``) plus an adjustment accumulated only over under-forecast
    hours: ``gram_adj (P, D, D)``, ``rhs_adj (P, D)``.  Solving

        (gram_prefix[w] + gram_adj + ridge I) beta = rhs_prefix[w] + rhs_adj

    reproduces a weighted fit without any O(T D^2) pass — the carried-
    moments half of the incremental IRLS scheme (see
    :func:`irls_carry_init` / :func:`irls_carry_extend`)."""
    g = jax.lax.dynamic_index_in_dim(
        state.gram_prefix, week - 1, axis=0, keepdims=False
    )
    r = jax.lax.dynamic_index_in_dim(
        state.rhs_prefix, week - 1, axis=1, keepdims=False
    )
    eye = state.cfg.ridge * jnp.eye(g.shape[-1], dtype=g.dtype)
    return jax.vmap(
        lambda ga, ri: jnp.linalg.solve(g + ga + eye, ri)
    )(gram_adj, r + rhs_adj)


def irls_carry_init(
    state: PrefixFitState, week: int, iters: int
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Exact IRLS adjustment moments on the ``week``-period start prefix.

    Runs the full masked IRLS (matching :func:`irls_refine`) once at trace
    time and returns the final iteration's weight-adjustment moments
    ``(gram_adj (P, D, D), rhs_adj (P, D))``.  A replay seeds its scan
    carry with these, then keeps them current with
    :func:`irls_carry_extend` — O(period D^2) per replayed week instead of
    ``iters`` full O(T D^2) passes."""
    beta = solve_prefix(state, week)
    xh = state.x[: state.num_hist_hours]
    t = jnp.arange(state.num_hist_hours)
    mask = (t < week * state.period_hours).astype(xh.dtype)
    num_p, d = state.logy.shape[0], xh.shape[-1]
    g_adj = jnp.zeros((num_p, d, d), xh.dtype)
    r_adj = jnp.zeros((num_p, d), xh.dtype)
    for _ in range(max(iters, 0)):
        resid = state.logy - jnp.matmul(beta, xh.T, precision=_HI)  # (P, T)
        wadj = (state.cfg.asym_weight - 1.0) * (resid > 0) * mask
        g_adj = jnp.einsum("pt,td,te->pde", wadj, xh, xh, precision=_HI)
        r_adj = jnp.einsum(
            "pt,td->pd", wadj * state.logy, xh, precision=_HI
        )
        beta = solve_prefix_adjusted(state, week, g_adj, r_adj)
    return g_adj, r_adj


def irls_carry_extend(
    state: PrefixFitState,
    beta: jnp.ndarray,
    gram_adj: jnp.ndarray,
    rhs_adj: jnp.ndarray,
    week,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Extend carried IRLS moments with period ``week``'s demand block.

    Classifies only the newest period's residuals under the *current*
    ``beta`` and adds their asymmetric-weight contribution, so the moments
    cover the ``week+1``-period prefix for the next refit.  Older periods
    keep the classification they had when appended (frozen-weights IRLS) —
    the approximation that buys O(period D^2)/week; the closeness test
    pins it against the exact :func:`irls_refine` path.  Scan-safe
    (``week`` may be traced)."""
    ph = state.period_hours
    xb = jax.lax.dynamic_slice_in_dim(
        state.x, week * ph, ph, axis=0
    )                                                        # (ph, D)
    lb = jax.lax.dynamic_slice_in_dim(
        state.logy, week * ph, ph, axis=1
    )                                                        # (P, ph)
    resid = lb - jnp.matmul(beta, xb.T, precision=_HI)
    wadj = (state.cfg.asym_weight - 1.0) * (resid > 0)
    dg = jnp.einsum("pt,td,te->pde", wadj, xb, xb, precision=_HI)
    dr = jnp.einsum("pt,td->pd", wadj * lb, xb, precision=_HI)
    return gram_adj + dg, rhs_adj + dr


def predict_from_beta(
    state: PrefixFitState, beta: jnp.ndarray, t_start, num_hours: int
) -> jnp.ndarray:
    """(P, num_hours) forecast from prefix-fit betas starting at absolute
    hour ``t_start`` (traced-safe dynamic slice into the shared design)."""
    xf = jax.lax.dynamic_slice_in_dim(state.x, t_start, num_hours, axis=0)
    return jnp.exp(jnp.matmul(beta, xf.T, precision=_HI))


def weekly_fractile_levels(
    yhat: jnp.ndarray,
    fractiles,
    hours: int = HOURS_PER_WEEK,
) -> jnp.ndarray:
    """(..., Q) fractile levels of the first ``hours`` of a forecast.

    The pure-model band: quantiles of the smooth structural fit's own
    hourly distribution.  The calibration telemetry and the breach
    cadence both use :func:`anchored_fractile_levels` instead (same
    shape, realized-spread anchored) because the smooth fit alone
    under-disperses; this variant remains for model-only diagnostics."""
    q = jnp.asarray(fractiles, yhat.dtype)
    levels = jnp.quantile(yhat[..., :hours], q, axis=-1)
    return jnp.moveaxis(levels, 0, -1)


#: Trailing realized weeks pooled into the anchored band's empirical
#: spread.  Four weeks keeps steady-family coverage within ~1pp of
#: nominal while still tracking level moves within a month.
TRAIL_WEEKS = 4


def anchored_fractile_levels(d_trail: jnp.ndarray, fractiles) -> jnp.ndarray:
    """(..., Q) forecast fractile levels for the coming week, anchored on
    the realized hourly distribution of the trailing window.

    Empirical quantiles of ``d_trail`` ((..., TRAIL_WEEKS*168) hours) —
    the persistence-quantile forecast of next week's hourly distribution.
    The smooth structural fit is deliberately NOT blended in: ridge + the
    finite Fourier order shrink its seasonal amplitude and it carries no
    residual noise, so :func:`weekly_fractile_levels` of the fit alone
    under-covers the tails by ~20pp, and shifting this band by the fit's
    predicted mean move only injects fit noise (measured: coverage drift
    1pp -> 8pp on the steady family).  Anchoring keeps coverage within
    ~1pp of nominal on predictable families while regime shifts — which
    a trailing window cannot see coming — still degrade it, exactly the
    signal the calibration telemetry and the breach cadence key on."""
    q = jnp.asarray(fractiles, d_trail.dtype)
    base = jnp.quantile(d_trail, q, axis=-1)
    return jnp.moveaxis(base, 0, -1)


# Batched fits across pools: vmap over the leading axis of ``ys``.
def fit_batched(ys: jnp.ndarray, cfg: ForecastConfig = ForecastConfig()):
    """``fit`` vmapped over a (P, T) pool batch — same short-history guard
    on the yearly Fourier terms as the single-series path."""
    if ys.shape[-1] < 1.2 * HOURS_PER_YEAR and cfg.yearly_order:
        cfg = dataclasses.replace(cfg, yearly_order=0)
    t_max = float(max(ys.shape[-1] - 1, 1))
    betas = jax.vmap(lambda y: _fit(y, cfg, t_max))(ys)
    return ForecastModel(beta=betas, t_max=t_max, cfg=cfg)


def predict_batched(model: ForecastModel, t_hours: jnp.ndarray) -> jnp.ndarray:
    x = design_matrix(t_hours.astype(jnp.float32), model.cfg, model.t_max)
    return jnp.exp(jnp.matmul(model.beta, x.T, precision=_HI))
