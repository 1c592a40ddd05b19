"""The forecaster against float64 numpy written here: the design, the
weekly prefix refit and forecast of a three-year replay, and the scaled
solve under them.

The paper's estate replays 117 weekly refits over a 156-week hourly
history.  There the ridge normal equations reach cond 2.5e7 (the week
after the last trend changepoint enters the prefix), so the float32
program has to reduce its Fourier angles exactly and solve a scaled
system to stay near float64."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.capacity import generations as gn
from repro.core import forecast as fc
from repro.core import migration as mg
from repro.data import traces

WK = 168
WEEKS, HORIZON, START = 156, 8, 39


def exact_design(t, cfg: fc.ForecastConfig, t_max: float) -> np.ndarray:
    """(T, D) float64 design with the Fourier phases t k reduced modulo
    the period in integers."""
    t = np.asarray(t, np.int64)
    ts = t / t_max
    cols = [np.ones_like(ts)[:, None], ts[:, None]]
    if cfg.num_changepoints:
        cps = np.linspace(0.1, 0.9, cfg.num_changepoints)
        cols.append(np.maximum(ts[:, None] - cps[None, :], 0.0))
    for period, order in ((24, cfg.daily_order), (WK, cfg.weekly_order),
                          (8760, cfg.yearly_order)):
        k = np.arange(1, order + 1)
        ang = 2.0 * np.pi * ((t[:, None] * k[None, :]) % period) / period
        cols += [np.sin(ang), np.cos(ang)]
    doy = (t // 24) % 365
    hol = (doy >= cfg.holiday_start_day) & (
        doy < cfg.holiday_start_day + cfg.holiday_len_days)
    cols.append(hol.astype(np.float64)[:, None])
    return np.concatenate(cols, axis=1)


@pytest.mark.parametrize("yearly_order", [8, 0])
def test_design_matches_the_exact_design(yearly_order):
    """Over 156 weeks of history and an 8-week horizon every entry is
    within a few float32 ulps of the exact design (1e-6; a float32 angle
    2 pi t k / period, unreduced, is off by up to 2.7e-3 there)."""
    cfg = fc.ForecastConfig(yearly_order=yearly_order)
    t = np.arange((WEEKS + HORIZON) * WK)
    t_max = float(WEEKS * WK - 1)
    got = np.asarray(fc.design_matrix(jnp.asarray(t), cfg, t_max),
                     np.float64)
    want = exact_design(t, cfg, t_max)
    assert got.shape == want.shape == (len(t), fc.num_features(cfg))
    assert np.abs(got - want).max() <= 1e-6


@pytest.fixture(scope="module", params=[0, 4, 7])
def estate(request):
    """The rows a migration-aware replay of a seeded 12-pool turnover
    estate fits: pair totals in old-family units, float32."""
    pools = traces.synthetic_pool_set(num_pools=12, num_hours=WEEKS * WK,
                                      seed=request.param, migration=True)
    edges = gn.migration_edges(pools.keys)
    return np.asarray(mg.transform_for_fit(
        jnp.asarray(pools.demand, jnp.float32), edges))


def test_prefix_refit_and_forecast_at_every_week(estate):
    """Every week of the replay, the weeks after each trend changepoint
    (52, 70, 88, 141, ...) included: the float32 prefix refit's 8-week
    forecast is within 2e-4 of each row's mean forecast of the float64
    ridge solve on the exact design.  What is left is float32's: the
    prefix Grams and right-hand sides rounded to float32 (~6e-8 of each
    entry) and the float32 Cholesky factor, magnified by the
    Jacobi-scaled condition, which grows to 5.4e4 by week 155.  The three
    seeds read 1.45e-4-1.51e-4, at weeks 150-154; a float32 dot over each
    week's hours in place of the pairwise sum read 2.1e-4-2.6e-4 (week
    52), and an unreduced design with an unscaled LU 1.4e-3-1.6e-3
    (week 87)."""
    cfg = fc.ForecastConfig()
    hh = HORIZON * WK
    state = fc.prefix_fit_state(estate, cfg, horizon_hours=hh,
                                min_prefix_hours=START * WK)
    weeks = np.arange(START, WEEKS)

    @jax.jit
    def forecasts(ws):
        return jax.vmap(lambda w: fc.predict_from_beta(
            state, fc.solve_prefix(state, w), w * WK, hh))(ws)

    got = np.asarray(forecasts(jnp.asarray(weeks)), np.float64)

    x = exact_design(np.arange(WEEKS * WK + hh), state.cfg, state.t_max)
    d = x.shape[1]
    xw = x[:WEEKS * WK].reshape(WEEKS, WK, d)
    gram = np.cumsum(np.einsum("wtd,wte->wde", xw, xw), axis=0)
    logy = np.log(np.maximum(estate.astype(np.float64), 1e-6))
    rhs = np.cumsum(np.einsum("wtd,pwt->wpd", xw,
                              logy.reshape(-1, WEEKS, WK)), axis=0)
    gaps = []
    for i, w in enumerate(weeks):
        g = gram[w - 1] + cfg.ridge * np.eye(d)
        beta = np.linalg.solve(g, rhs[w - 1].T).T
        want = np.exp(beta @ x[w * WK:w * WK + hh].T)
        gaps.append((np.abs(got[i] - want)
                     / want.mean(1, keepdims=True)).max())
    assert 141 in weeks
    assert max(gaps) <= 2e-4, (int(weeks[int(np.argmax(gaps))]), max(gaps))


def test_scaled_solve_on_a_badly_conditioned_gram():
    """A seeded weighted Gram of the estate's design over a 141-week
    prefix (IRLS-style weights of 1 or 2.1 per hour), condition ~2.5e7:
    the float32 ridge solve's 8-week forecast, in log space, is within
    1e-4 of float64 ``np.linalg.solve``'s.  Jacobi-scaled Cholesky leaves
    ~5e-6 there; an unscaled float32 LU leaves 1e-2-4e-2."""
    rng = np.random.default_rng(20250315)
    cfg = fc.ForecastConfig(yearly_order=0)
    n = 141 * WK
    x = exact_design(np.arange(n + HORIZON * WK), cfg, WEEKS * WK - 1.0)
    wts = np.where(rng.random(n) < 0.5, 2.1, 1.0)
    gram = (x[:n] * wts[:, None]).T @ x[:n]
    beta = rng.standard_normal((4, x.shape[1]))
    y = beta @ x[:n].T + 0.1 * rng.standard_normal((4, n))
    rhs = (y * wts) @ x[:n]
    g = gram + cfg.ridge * np.eye(x.shape[1])
    assert 1e7 <= np.linalg.cond(g) <= 1e8
    want = np.linalg.solve(g, rhs.T).T
    got = np.asarray(fc._ridge_solve(jnp.asarray(gram, jnp.float32),
                                     jnp.asarray(rhs, jnp.float32),
                                     cfg.ridge), np.float64)
    assert np.abs((got - want) @ x[n:].T).max() <= 1e-4
