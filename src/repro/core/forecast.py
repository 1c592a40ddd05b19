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
import math

import jax
import jax.numpy as jnp
import jax.scipy.linalg as jsl

from repro.core.demand import DAYS_PER_YEAR, HOURS_PER_DAY, HOURS_PER_WEEK

HOURS_PER_YEAR = HOURS_PER_DAY * DAYS_PER_YEAR

#: Every float32 contraction here runs at full float32 precision.  A TPU
#: rounds matmul inputs to bfloat16 at default precision, which puts a
#: ~2% error on an exponentiated log-demand forecast and perturbs the
#: badly conditioned normal equations: on the paper's 156-week estate
#: with the yearly terms off (D = 31), cond(G + ridge I) is 1.3e6 at an
#: 8-week prefix, 2.0e7 at 100 weeks, 2.5e7 at 141 and 5.6e4 at 156
#: (8.6e3-3.6e4 once Jacobi-scaled, see :func:`_spd_solve`); the CPU
#: ignores the setting, so its programs are unchanged.
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


def _fourier(t: jnp.ndarray, period: int, order: int) -> jnp.ndarray:
    """(T, 2*order) Fourier design block of integer hours ``t``.  The phase
    t k is reduced modulo the period in int32 (exact: t k stays far below
    2^31), centred on zero, and only then scaled to an angle in float32,
    so every entry is within a few float32 ulps of the exact design.  A
    float32 angle of 2 pi t k / period itself reaches 5.8e4 rad over a
    three-year history and carries an error of up to 2.7e-3."""
    k = jnp.arange(1, order + 1, dtype=jnp.int32)
    m = (t[:, None] * k[None, :]) % period
    m = jnp.where(2 * m >= period, m - period, m)
    ang = m.astype(jnp.float32) * (2.0 * math.pi / period)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def design_matrix(
    t_hours: jnp.ndarray, cfg: ForecastConfig, t_max: float
) -> jnp.ndarray:
    """Feature matrix X (T, D) at whole hours ``t_hours``.  ``t_max`` fixes
    changepoint locations so the same basis extends consistently into the
    future."""
    t = jnp.asarray(t_hours).astype(jnp.int32)
    ts = t.astype(jnp.float32) / t_max  # normalized time for trend columns
    cols = [jnp.ones_like(ts)[:, None], ts[:, None]]
    if cfg.num_changepoints:
        cps = jnp.linspace(0.1, 0.9, cfg.num_changepoints)
        cols.append(jnp.maximum(ts[:, None] - cps[None, :], 0.0))
    cols.append(_fourier(t, HOURS_PER_DAY, cfg.daily_order))
    cols.append(_fourier(t, HOURS_PER_WEEK, cfg.weekly_order))
    cols.append(_fourier(t, HOURS_PER_YEAR, cfg.yearly_order))
    day_of_year = (t // HOURS_PER_DAY) % DAYS_PER_YEAR
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


def _spd_solve(g: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Solve g x = b for a symmetric positive definite g (D, D) and b
    (D, ...).  The system is Jacobi-scaled first, (S g S) z = S b with
    S = diag(g)^(-1/2) and x = S z, which takes the ridge normal
    equations from cond ~2.5e7 to ~3.6e4 (see ``_HI``) without moving the
    solution, and then solved by Cholesky at HIGHEST precision: one
    factorization shared by every column of b."""
    s = jax.lax.rsqrt(jnp.diagonal(g))
    sb = s.reshape(s.shape + (1,) * (b.ndim - 1))
    with jax.default_matmul_precision("highest"):
        c = jsl.cho_factor(g * s[:, None] * s[None, :])
        return sb * jsl.cho_solve(c, sb * b)


def _solve_wls(x, y, w, ridge):
    xw = x * w[:, None]
    gram = jnp.matmul(xw.T, x, precision=_HI) + ridge * jnp.eye(
        x.shape[1], dtype=x.dtype
    )
    rhs = jnp.matmul(xw.T, y, precision=_HI)
    return _spd_solve(gram, rhs)


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
    right-hand sides rhs (P, D) -> (P, D): one factorization per call,
    the rows one batch of right-hand sides."""
    g = gram + ridge * jnp.eye(gram.shape[-1], dtype=gram.dtype)
    return _spd_solve(g, rhs.T).T


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["x", "gram_prefix", "rhs_prefix", "logy"],
    meta_fields=["cfg", "t_max", "num_hist_hours", "period_hours"],
)
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


def _prefix_config(
    cfg: ForecastConfig, t_hist: int, min_prefix_hours: int | None
) -> ForecastConfig:
    """``cfg`` with the yearly terms off where the shortest prefix a refit
    sees (``min_prefix_hours``, else the whole ``t_hist``) is under 1.2
    years."""
    guard_hours = t_hist if min_prefix_hours is None else min_prefix_hours
    if guard_hours < 1.2 * HOURS_PER_YEAR and cfg.yearly_order:
        return dataclasses.replace(cfg, yearly_order=0)
    return cfg


def num_features(cfg: ForecastConfig) -> int:
    """Columns D of :func:`design_matrix` under ``cfg``."""
    fourier = cfg.daily_order + cfg.weekly_order + cfg.yearly_order
    return 3 + cfg.num_changepoints + 2 * fourier


def prefix_state_bytes(
    num_rows: int,
    num_hours: int,
    cfg: ForecastConfig = ForecastConfig(),
    *,
    horizon_hours: int,
    period_hours: int = HOURS_PER_WEEK,
    min_prefix_hours: int | None = None,
) -> int:
    """Bytes of the design, the prefix Gram and the right-hand sides that
    :func:`prefix_fit_state` builds for ``num_rows`` rows of ``num_hours``
    hours (float32), from the shapes alone."""
    num_weeks = num_hours // period_hours
    t_hist = num_weeks * period_hours
    d = num_features(_prefix_config(cfg, t_hist, min_prefix_hours))
    return 4 * d * (t_hist + horizon_hours + num_weeks * (d + num_rows))


def _pairwise_sum(a: jnp.ndarray, axis: int) -> jnp.ndarray:
    """Sum over ``axis`` by halving it: each entry takes ~log2(n) float32
    roundings, where a running sum takes ~n."""
    a = jnp.moveaxis(a, axis, 0)
    while a.shape[0] > 1:
        if a.shape[0] % 2:
            a = jnp.concatenate([a, jnp.zeros_like(a[:1])])
        a = a.reshape(2, a.shape[0] // 2, *a.shape[1:])
        a = a[0] + a[1]
    return a[0]


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "num_weeks", "period_hours", "horizon_hours",
                     "t_max"),
)
def _prefix_arrays(ys, cfg, num_weeks, period_hours, horizon_hours, t_max):
    """The design over history and horizon, the prefix Grams, the prefix
    right-hand sides and the log targets of :func:`prefix_fit_state`, in
    one compiled call."""
    t_hist = num_weeks * period_hours
    ys = ys[..., :t_hist]
    x = design_matrix(jnp.arange(t_hist + horizon_hours), cfg, t_max)
    xw = x[:t_hist].reshape(num_weeks, period_hours, x.shape[-1])
    # The pool-shared weekly Gram blocks sum their hours pairwise, one week
    # at a time: a float32 dot over the 168 hours put 2e-4 of a pool's mean
    # on the forecast in the week after a changepoint.
    gram_prefix = jnp.cumsum(jax.lax.map(
        lambda xb: _pairwise_sum(xb[:, :, None] * xb[:, None, :], axis=0), xw
    ), axis=0)
    logy = jnp.log(jnp.maximum(ys, 1e-6))
    lw = logy.reshape(ys.shape[0], num_weeks, period_hours)
    rhs_prefix = jnp.cumsum(
        jnp.einsum("wtd,pwt->pwd", xw, lw, precision=_HI), axis=1
    )
    return x, gram_prefix, rhs_prefix, logy


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
    cfg = _prefix_config(cfg, t_hist, min_prefix_hours)
    t_max = float(max(t_hist - 1, 1))
    x, gram_prefix, rhs_prefix, logy = _prefix_arrays(
        ys, cfg, num_weeks, period_hours, horizon_hours, t_max
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
        beta = jax.vmap(lambda gi, ri: _spd_solve(gi + eye, ri))(g, r)
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
        lambda ga, ri: _spd_solve(g + ga + eye, ri)
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
