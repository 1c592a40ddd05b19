"""Plain reference of the rolling planner: Algorithm 1 replayed week by
week in numpy, float64 throughout.

It follows the semantics the program states for ``api.plan(PlanRequest(
mode="rolling"))`` and shares no code with it:

* forecaster: ridge least squares of log demand on a fixed design
  (intercept, trend, 8 trend changepoints, daily/weekly/yearly Fourier
  terms, holiday dummy; yearly terms off when the first decision sees less
  than 1.2 years), refit every week on the whole-week prefix, forecast
  ``horizon_weeks`` ahead.  Fourier angles are reduced modulo the period
  in integers, so the design is exact;
* migration: the old family's row fits the pair total in old-equivalent
  units; a logit-share line per edge (weighted by s(1 - s), with the
  announced S-curve as a prior of weight ``share_prior_weight``) splits
  the forecast back into the pair;
* solver: per horizon prefix, each SKU's threshold is the order statistic
  at its hand-over fractile of the lower envelope of the cost lines; the
  minimum over the horizons within each SKU's term, re-monotonised in
  envelope order, gives the stack widths;
* spot: the per-horizon floor is the higher of the envelope entry
  fractile and the chance-constraint volume cap; committed levels are
  truncated at it;
* convertible: the cloud-total forecast is solved against the convertible
  lines, truncated below the pools' own level, bought into a cloud-level
  stack and re-pinned onto pools in proportion to their forecast peak
  excess (three passes); live convertible width scales down standard buys;
* hedge: a band commits once its accrued spend reaches its price; where
  the program's commit count in a row and week differs from the rule's
  only by bands within ``TIE`` of their price, the replay takes the
  program's count (given the program's buys, ``follow``);
* the replay buys increments on decision weeks, rolls tranches off at
  term, and bills committed rates, on-demand above the level (and spot
  above the floor);
* baselines: the one-shot replay decides only at the start week; the
  hindsight stack is the exact lower-envelope stack on the realized
  evaluation demand, billed weekly.

``Numerics("bfloat16")`` and ``Numerics("high")`` are the controls: the
same code one precision step below what a configuration states.  A
comparison that cannot tell the control from the program is too loose.
"""

from __future__ import annotations

import math

import numpy as np

from lib import deployment as dep

HOURS_PER_DAY = 24
HOURS_PER_WEEK = 168
HOURS_PER_YEAR = 24 * 365
DAYS_PER_YEAR = 365
FRACTILE_GRID = 4096
PURCHASE_EPS = 1e-9
SHARE_EPS = 1e-5
SHARE_RIDGE = 1e-6
# A hedge band whose accrued spend lies within this share of its price is
# a tie: float32 sums (the program states float32) may put it on either
# side, so the replay follows the program's choice there (``Hedge.decide``).
# Set between the widest margin at which the program's float32 decisions
# differ from float64 and the bfloat16 control's typical one (PERF.md).
TIE = 1e-4
LOGISTIC_1090 = 2.0 * math.log(9.0)


class Numerics:
    """The reference's arithmetic: ``float64``; or a control one step
    below a configuration's stated precision: ``high`` (float32, every
    contraction in three bfloat16 passes, for float32 at HIGHEST) or
    ``bfloat16`` (every array rounded to bfloat16, for plain float32)."""

    def __init__(self, kind: str = "float64"):
        import ml_dtypes

        self.kind = kind
        self.control = kind == "high"
        self.dt = {"float64": np.float64, "high": np.float32,
                   "bfloat16": ml_dtypes.bfloat16}[kind]

    def arr(self, a):
        return np.asarray(a, self.dt)

    def mm(self, a, b):
        """``a @ b`` (broadcasting over leading axes)."""
        a, b = self.arr(a), self.arr(b)
        if not self.control:
            return (a @ b).astype(self.dt)
        import ml_dtypes

        bf = ml_dtypes.bfloat16

        def split(x):
            hi = x.astype(bf).astype(np.float32)
            return hi, (x - hi).astype(bf).astype(np.float32)

        a1, a2 = split(a)
        b1, b2 = split(b)
        return a1 @ b1 + (a1 @ b2 + a2 @ b1)


# ---------------------------------------------------------------- lines


def _fractile_index(alphas, betas, od):
    """(K,) grid index of the fractile where each line hands over to the
    next occupant of the lower envelope of [on-demand, lines] on u = j /
    (FRACTILE_GRID - 1); -1 for a line that never wins (a strict ``<``
    keeps the earlier line on a tie)."""
    j = np.arange(FRACTILE_GRID)
    u = j / (FRACTILE_GRID - 1)
    best_cost = od * (1.0 - u)
    best = np.full(FRACTILE_GRID, -1)
    for k in range(len(alphas)):
        cost = alphas[k] * (1.0 - u) + betas[k] * u
        better = cost < best_cost
        best = np.where(better, k, best)
        best_cost = np.where(better, cost, best_cost)
    return np.asarray([j[best == k].max() if (best == k).any() else -1
                       for k in range(len(alphas))])


def _spot_entry_index(alphas, betas, spot_rate, od):
    """Grid index of the lowest fractile at which the spot line is the
    first minimum of [on-demand, lines, spot]; the top of the grid when it
    never is."""
    u = np.arange(FRACTILE_GRID) / (FRACTILE_GRID - 1)
    lines = np.concatenate([
        (od * (1.0 - u))[:, None],
        alphas[None, :] * (1.0 - u)[:, None] + betas[None, :] * u[:, None],
        (spot_rate * (1.0 - u))[:, None],
    ], axis=1)
    wins = np.argmin(lines, axis=1) == lines.shape[1] - 1
    return int(np.flatnonzero(wins)[0]) if wins.any() else FRACTILE_GRID - 1


def _rank(j, n):
    """1-based rank of the order statistic at fractile j/(grid-1) of n
    values: the smallest c with c/n >= j/(grid-1); 0 for j <= 0."""
    return np.where(j > 0, -(-(np.maximum(j, 0) * n) // (FRACTILE_GRID - 1)),
                    0)


def _lines(opts, clouds, od):
    """(R, K) alphas (= betas: every active tranche bills its rate) with
    SKUs of another cloud priced at on-demand."""
    rates = np.asarray([o.rate for o in opts])
    avail = np.asarray([[o.cloud == c for o in opts] for c in clouds])
    return np.where(avail, rates[None, :], od)


# ------------------------------------------------------------ forecaster


def design(t, fcfg, t_max):
    """(len(t), D) design; Fourier angles reduced modulo the period."""
    t = np.asarray(t, np.int64)
    ts = t / t_max
    cols = [np.ones_like(ts)[:, None], ts[:, None]]
    if fcfg["num_changepoints"]:
        cps = np.linspace(0.1, 0.9, fcfg["num_changepoints"])
        cols.append(np.maximum(ts[:, None] - cps[None, :], 0.0))
    for period, order in ((HOURS_PER_DAY, fcfg["daily_order"]),
                          (HOURS_PER_WEEK, fcfg["weekly_order"]),
                          (HOURS_PER_YEAR, fcfg["yearly_order"])):
        k = np.arange(1, order + 1)
        ang = 2.0 * np.pi * ((t[:, None] * k[None, :]) % period) / period
        cols += [np.sin(ang), np.cos(ang)]
    doy = (t // HOURS_PER_DAY) % DAYS_PER_YEAR
    start = fcfg["holiday_start_day"]
    cols.append(((doy >= start) & (doy < start + fcfg["holiday_len_days"]))
                .astype(np.float64)[:, None])
    return np.concatenate(cols, axis=1)


class Forecaster:
    """Weekly prefix refits of one (R, T) batch."""

    def __init__(self, fit_rows, fcfg, start, horizon_hours, num: Numerics):
        self.num = num
        r, t_hist = fit_rows.shape
        weeks = t_hist // HOURS_PER_WEEK
        fcfg = dict(fcfg)
        if start * HOURS_PER_WEEK < 1.2 * HOURS_PER_YEAR:
            fcfg["yearly_order"] = 0
        self.t_max = float(max(t_hist - 1, 1))
        self.ridge = fcfg["ridge"]
        self.x = num.arr(design(np.arange(t_hist + horizon_hours), fcfg,
                                self.t_max))
        d = self.x.shape[1]
        xw = self.x[:t_hist].reshape(weeks, HOURS_PER_WEEK, d)
        self.gram = np.cumsum(num.mm(np.swapaxes(xw, 1, 2), xw), axis=0)
        logy = np.log(np.maximum(num.arr(fit_rows), 1e-6))
        lw = np.swapaxes(logy.reshape(r, weeks, HOURS_PER_WEEK), 0, 1)
        self.rhs = np.cumsum(num.mm(lw, xw), axis=0)           # (W, R, D)
        self.h = horizon_hours

    def forecast(self, w):
        g = self.gram[w - 1] + self.ridge * np.eye(self.gram.shape[-1])
        beta = np.linalg.solve(g.astype(self.num.dt),
                               self.rhs[w - 1].T).T             # (R, D)
        xf = self.x[w * HOURS_PER_WEEK:w * HOURS_PER_WEEK + self.h]
        return np.exp(self.num.mm(beta, xf.T))


def migration_edges(keys, generations):
    """[(src, dst, uplift, midpoint hours, rate per hour)] of the successor
    table matched onto the pool keys, per region."""
    index = {tuple(k): i for i, k in enumerate(keys)}
    out = []
    for g in generations:
        for region in sorted({k[1] for k in index if k[0] == g["cloud"]}):
            old = index.get((g["cloud"], region, g["old_family"]))
            new = index.get((g["cloud"], region, g["new_family"]))
            if old is None or new is None:
                continue
            out.append((old, new, g["perf_uplift"],
                        (g["launch_week"] + 0.5 * g["span_weeks"])
                        * HOURS_PER_WEEK,
                        LOGISTIC_1090 / (g["span_weeks"] * HOURS_PER_WEEK)))
    return out


class ShareFit:
    """Rolling logit-share lines of the turnover edges."""

    def __init__(self, rows, edges, t_max, prior_weight, num: Numerics):
        self.num = num
        self.src = np.asarray([e[0] for e in edges])
        self.dst = np.asarray([e[1] for e in edges])
        up = num.arr([e[2] for e in edges])
        mid = num.arr([e[3] for e in edges])
        rate = num.arr([e[4] for e in edges])
        self.inv_gain = 1.0 / (1.0 + up)
        self.t_max = t_max
        d = num.arr(rows)
        old, new_adj = d[self.src], d[self.dst] * (1.0 + up[:, None])
        total = old + new_adj
        s = np.where(total > 0, new_adj / np.maximum(total, 1e-12), 0.0)
        s = np.clip(s, SHARE_EPS, 1.0 - SHARE_EPS)
        z = np.log(s) - np.log1p(-s)
        wgt = s * (1.0 - s)
        t = num.arr(np.arange(d.shape[1]) / t_max)
        mom = np.stack([wgt, wgt * t, wgt * t * t, wgt * z, wgt * t * z], -1)
        g = len(edges)
        weekly = mom.reshape(g, -1, HOURS_PER_WEEK, 5).sum(2)
        self.cum = np.cumsum(weekly, axis=1)
        if prior_weight > 0:
            b0, a0, half = rate * t_max, -rate * mid, prior_weight / 2.0
            prior = np.stack([np.full_like(a0, prior_weight),
                              np.full_like(a0, half), np.full_like(a0, half),
                              half * (2.0 * a0 + b0), half * (a0 + b0)], -1)
            self.cum = self.cum + prior[:, None, :]

    def compose(self, yhat, w):
        c = self.cum[:, w - 1]
        sw, swt, swt2, swz, swtz = (c[:, i] for i in range(5))
        b = (sw * swtz - swt * swz) / (sw * swt2 - swt * swt + SHARE_RIDGE)
        a = (swz - b * swt) / np.maximum(sw, 1e-9)
        t = (w * HOURS_PER_WEEK + np.arange(yhat.shape[1])) / self.t_max
        sh = 1.0 / (1.0 + np.exp(-(a[:, None] + b[:, None] * t[None, :])))
        y = yhat.copy()
        tot = yhat[self.src]
        y[self.src] = (1.0 - sh) * tot
        y[self.dst] = sh * tot * self.inv_gain[:, None]
        return y


# ---------------------------------------------------------------- solver


def order_stats(yhat, n, ranks):
    """(R,) per-row order statistics of the first n hours at 1-based
    ``ranks``; rank 0 (fractile 0) is the minimum over the whole
    forecast, as the first sorted value is."""
    out = np.empty(yhat.shape[0], yhat.dtype)
    zero = ranks == 0
    out[zero] = yhat[zero].min(1)
    for kth in np.unique(ranks[~zero]):
        rows = np.flatnonzero(ranks == kth)
        out[rows] = np.partition(yhat[rows, :n], kth - 1, axis=1)[:, kth - 1]
    return out


def prefix_order_stats(yhat, fr_idx, horizon_weeks):
    """(R, H, K) per-horizon thresholds: for each prefix of 168 (h + 1)
    hours the order statistic at each SKU's hand-over fractile."""
    out = np.zeros((yhat.shape[0], horizon_weeks, fr_idx.shape[1]),
                   yhat.dtype)
    for h in range(horizon_weeks):
        n = (h + 1) * HOURS_PER_WEEK
        for k in range(fr_idx.shape[1]):
            out[:, h, k] = order_stats(yhat, n, _rank(fr_idx[:, k], n))
    return out


def monotone_stack(per_h, fr_idx, terms, horizon_weeks):
    """(widths, tops), each (R, K): per-SKU minimum over the horizons in
    its term, running max in envelope (fractile) order."""
    weeks = np.arange(1, horizon_weeks + 1)[:, None]
    in_term = weeks <= np.maximum(terms[None, :], 1)
    mins = np.where(in_term[None], per_h, np.inf).min(1)          # (R, K)
    on_env = fr_idx > 0
    depth = np.argsort(np.where(on_env, fr_idx, np.iinfo(np.int64).max),
                       axis=1, kind="stable")
    inv = np.argsort(depth, axis=1, kind="stable")
    mins_d = np.take_along_axis(np.where(on_env, mins, 0.0), depth, 1)
    tops_d = np.maximum.accumulate(mins_d, axis=1)
    prev_d = np.concatenate([np.zeros_like(tops_d[:, :1]), tops_d[:, :-1]], 1)
    widths_d = np.where(np.take_along_axis(on_env, depth, 1),
                        tops_d - prev_d, 0.0)
    return (np.take_along_axis(widths_d, inv, 1),
            np.take_along_axis(tops_d, inv, 1))


def spot_floors(yhat, env_idx, cap, horizon_weeks):
    """(R, H) per-horizon spot floors: max(envelope-entry order statistic,
    lowest level whose above-level prefix volume fits cap x prefix
    volume); +inf where the cap is 0."""
    order = np.argsort(yhat, axis=1, kind="stable")
    sy = np.take_along_axis(yhat, order, 1)
    out = np.zeros((yhat.shape[0], horizon_weeks), yhat.dtype)
    rows = np.arange(yhat.shape[0])
    for h in range(horizon_weeks):
        n = (h + 1) * HOURS_PER_WEEK
        env = order_stats(yhat, n, _rank(env_idx, n))
        valid = (order < n).astype(yhat.dtype)
        v = sy * valid
        suf = np.flip(np.cumsum(np.flip(v, 1), 1), 1)
        cnt = np.flip(np.cumsum(np.flip(valid, 1), 1), 1)
        va = (suf - v) - sy * (cnt - valid)
        vol = sy[rows, np.argmax(va <= cap[:, None] * suf[:, :1], axis=1)]
        out[:, h] = np.maximum(env, vol)
    return np.where(cap[:, None] > 0, out, np.inf)


def hindsight_widths(f, alphas, od):
    """(R, K) exact lower-envelope stack on demand f (R, T) for per-row
    lines alphas (R, K) (= betas)."""
    r, t = f.shape
    k_n = alphas.shape[1]
    sf = np.sort(f, axis=1)
    out = np.zeros((r, k_n), f.dtype)
    j = np.arange(t, dtype=np.float64)
    for lines in np.unique(alphas, axis=0):
        rows = np.flatnonzero((alphas == lines[None, :]).all(1))
        best_cost = od * (t - j)
        best = np.zeros(t, np.int64)
        for k in range(k_n):
            cost = lines[k] * (t - j) + lines[k] * j
            better = cost < best_cost
            best = np.where(better, k + 1, best)
            best_cost = np.where(better, cost, best_cost)
        for k in range(k_n):
            band = np.flatnonzero(best == k + 1)
            if band.size == 0:
                continue
            lo, hi = band.min(), band.max()
            top = sf[rows, hi]
            bottom = sf[rows, lo - 1] if lo > 0 else 0.0
            out[rows, k] = top - bottom
    return out


# ---------------------------------------------------------------- hedge


class Hedge:
    """Break-even ski rental per capacity band (Ambati et al.): the range
    [0, 1.5 x history peak) of each row is cut into 32 bands; a band above
    the committed top accrues the on-demand spend it would have absorbed
    last week and is committed into the row's cheapest SKU once that
    reaches its price, rate x min(term, window) x 168 x band width."""

    GRID, TOP = 32, 1.5

    def __init__(self, d, avail, rates, terms, start, weeks, od, num):
        hist = d[:, :start * HOURS_PER_WEEK]
        top = np.maximum(hist.max(1), 1e-6) * self.TOP
        self.dg = top / self.GRID
        self.levels = self.dg[:, None] * num.arr(np.arange(self.GRID))[None]
        self.kstar = np.argmin(np.where(avail, rates[None, :], np.inf), 1)
        eff_term = np.minimum(terms[self.kstar], weeks - start)
        self.price = (rates[self.kstar] * eff_term * HOURS_PER_WEEK
                      * self.dg)
        self.od = od
        self.num = num
        self.k_n = len(rates)
        pre = hist[:, :max(start - 1, 0) * HOURS_PER_WEEK]
        self.accrued0 = (self.spend(pre) if pre.shape[1]
                         else np.zeros_like(self.levels))

    def spend(self, d, block=128):
        """(R, GRID) on-demand spend each band absorbed over ``d`` (R, T),
        in blocks of rows."""
        out = np.empty((d.shape[0], self.GRID), self.levels.dtype)
        for a in range(0, d.shape[0], block):
            occ = d[a:a + block, None, :] - self.levels[a:a + block, :, None]
            np.maximum(occ, 0.0, out=occ)
            np.minimum(occ, self.dg[a:a + block, None, None], out=occ)
            out[a:a + block] = self.od * occ.sum(-1)
        return out

    def decide(self, accrued, active, d_prev, want=None, ties=None):
        """One week's commits.  ``want`` (R,), where given, is the number
        of bands the program committed in each row this week: a row that
        differs from the rule only in bands whose accrued spend lies
        within ``TIE`` of their price takes the program's count (the
        bands nearest the price go first), and each such band's margin
        is appended to ``ties``."""
        top = active.sum(1)
        covered = self.levels + self.dg[:, None] <= top[:, None] + 1e-6
        accrued = np.where(covered, accrued, accrued + self.spend(d_prev))
        commit = ~covered & (accrued >= self.price[:, None])
        if want is not None:
            self._follow(commit, ~covered, accrued / self.price[:, None],
                         want, ties)
        accrued = np.where(commit, 0.0, accrued)
        width = self.dg * commit.sum(1)
        targets = np.zeros((len(top), self.k_n), accrued.dtype)
        targets[np.arange(len(top)), self.kstar] = top + width
        return accrued, targets

    @staticmethod
    def _follow(commit, open_, ratio, want, ties):
        """Turn ``commit`` (R, GRID) in place to the program's count per
        row where the bands that differ are ties (see :meth:`decide`)."""
        for r in np.nonzero(commit.sum(1) != want)[0]:
            extra = int(want[r]) - int(commit[r].sum())
            if extra > 0:
                cand = np.nonzero(open_[r] & ~commit[r]
                                  & (ratio[r] >= 1.0 - TIE))[0]
                pick = cand[np.argsort(-ratio[r, cand])][:extra]
            else:
                cand = np.nonzero(commit[r] & (ratio[r] < 1.0 + TIE))[0]
                pick = cand[np.argsort(ratio[r, cand])][:-extra]
            if len(pick) < abs(extra):
                continue
            commit[r, pick] = extra > 0
            if ties is not None:
                ties.extend(np.abs(ratio[r, pick] - 1.0).tolist())


# ---------------------------------------------------------------- replay


def scenario_rows(demand, scen, lo, hi):
    """(n P, T) growth futures ``lo .. hi - 1``: scenario 0 realized, then
    per (s, p) a drift g ~ U(range) from the generator seeded (1000003
    family_index, seed, s, p), demand x exp(g t / 8736), rounded to
    float32."""
    if scen["family"] != "growth":
        raise NotImplementedError(f"scenario family {scen['family']!r}")
    out = []
    t = np.arange(demand.shape[1])
    lo_g, hi_g = scen["growth_range"]
    for s in range(lo, hi):
        if s == 0:
            out.append(demand)
            continue
        g = np.asarray([
            np.random.default_rng((1_000_003 * scen["family_index"],
                                   scen["seed"], s, p)).uniform(lo_g, hi_g)
            for p in range(demand.shape[0])])
        ramp = np.exp(g[:, None] * t[None, :] / (52.0 * HOURS_PER_WEEK))
        out.append((demand * ramp).astype(np.float32))
    return np.concatenate(out, axis=0)


def plan(cfg: dict, req: dict, keys, demand, num: Numerics | None = None,
         block: tuple[int, int] | None = None, follow=None):
    """Replay ``req`` on ``demand`` (P, T), over the scenarios ``block``
    (lo, hi) only where given (scenarios never share a row, so blocks
    replay apart; :func:`merge` joins them).  Returns the arrays the
    comparison reads: ``targets``/``increments`` (S, R, K), ``conv_inc``
    (S, N C, Kc) or None, ``rolling``/``one_shot``/``hindsight`` (N,)
    totals, ``weekly0`` (S,) scenario-0 weekly spend, and ``row_scale``
    (R,) / ``cloud_scale`` (N C,) mean evaluation demand.  ``follow`` (W,
    R), the program's weekly buys per row of the block summed over SKUs,
    settles the hedge's ties; ``ties`` holds the margins it followed."""
    num = num or Numerics()
    pricing = cfg["pricing"]
    for knob, plain in (("solver", "quantile"), ("cadence", "weekly"),
                        ("irls_iters", 0)):
        if req.get(knob, plain) != plain:
            raise NotImplementedError(
                f"the reference replays {knob}={plain!r} only, the request "
                f"has {req[knob]!r}")
    policy = req.get("policy") or "rolling_portfolio"
    if policy not in ("rolling_portfolio", "deterministic_hedge"):
        raise NotImplementedError(f"policy {policy!r}")
    hedge = policy == "deterministic_hedge"
    od = dep.od_rate(pricing)
    opts = dep.options(pricing)
    k_n = len(opts)
    rates = num.arr([o.rate for o in opts])
    terms = np.asarray([o.term_weeks for o in opts])
    hw = cfg["horizon_weeks"]
    hh = hw * HOURS_PER_WEEK
    p_n = demand.shape[0]
    weeks = demand.shape[1] // HOURS_PER_WEEK
    t_hist = weeks * HOURS_PER_WEEK
    start = req.get("start_weeks") or min(max(hw, weeks // 4),
                                          max(weeks - 1, 1))
    base = np.asarray(demand[:, :t_hist], np.float32)
    scen = req.get("scenarios")
    lo, hi = block or (0, scen["n"] if scen else 1)
    n_s = hi - lo
    rows32 = scenario_rows(base, scen, lo, hi) if scen else base
    d = num.arr(rows32)
    r_n = d.shape[0]
    clouds = [k[0] for k in keys] * n_s
    alphas = _lines(opts, clouds, od)
    fr_by_cloud = {c: _fractile_index(*(_lines(opts, [c], od)[0],) * 2, od)
                   for c in set(clouds)}
    fr = np.stack([fr_by_cloud[c] for c in clouds])              # (R, K)

    edges = []
    if req.get("migration"):
        base_edges = migration_edges(keys, pricing["generations"])
        edges = [(s_ * p_n + e[0], s_ * p_n + e[1]) + e[2:]
                 for s_ in range(n_s) for e in base_edges]
    fit = d.copy() if edges else d
    if edges:
        src = np.asarray([e[0] for e in edges])
        dst = np.asarray([e[1] for e in edges])
        up = num.arr([e[2] for e in edges])
        fit[src] = d[src] + d[dst] * (1.0 + up[:, None])
    if hedge:
        hg = Hedge(d, alphas < od, num.arr([o.rate for o in opts]), terms,
                   start, weeks, od, num)
    fcst = None if hedge else Forecaster(fit, cfg["forecast"], start, hh,
                                         num)
    share = (ShareFit(d, edges, fcst.t_max, cfg["share_prior_weight"], num)
             if edges and not hedge else None)

    spot = req.get("spot")
    if spot:
        lines_sp = {c: dep.spot_line(cfg, c, od) for c in set(clouds)}
        s_rate = num.arr([lines_sp[c][0] for c in clouds])
        s_cap = num.arr([lines_sp[c][1] for c in clouds])
        env_by_cloud = {
            c: _spot_entry_index(_lines(opts, [c], od)[0],
                                 _lines(opts, [c], od)[0], lines_sp[c][0], od)
            for c in set(clouds)}
        env_idx = np.asarray([env_by_cloud[c] for c in clouds])

    conv = req.get("convertible")
    if conv:
        copts = dep.convertible_options(pricing, [k[0] for k in keys])
        cl = sorted(set(k[0] for k in keys))
        member = num.arr([[1.0 if c == k[0] else 0.0 for k in keys]
                          for c in cl])                          # (C, P)
        c_n, kc_n = len(cl), len(copts)
        c_rates = num.arr([o.rate for o in copts])
        c_terms = np.asarray([o.term_weeks for o in copts])
        c_alpha = _lines(copts, cl, od)
        c_fr = np.tile(np.stack([_fractile_index(a, a, od) for a in c_alpha]),
                       (n_s, 1))

        def to_cloud(v):
            vs = v.reshape(n_s, p_n, *v.shape[1:])
            out = np.stack([num.mm(member, vs[s_].reshape(p_n, -1))
                            for s_ in range(n_s)])
            return out.reshape(n_s * c_n, *v.shape[1:])

        def allocate(width, need):
            alloc = np.zeros_like(need)
            rem = width
            for _ in range(3):
                cloud_need = to_cloud(need[:, None])[:, 0]
                per = rem / np.maximum(cloud_need, 1e-9)
                give = np.concatenate([
                    num.mm(member.T, per[s_ * c_n:(s_ + 1) * c_n, None])[:, 0]
                    for s_ in range(n_s)]) * need
                give = np.minimum(give, need)
                alloc, need = alloc + give, need - give
                rem = rem - to_cloud(give[:, None])[:, 0]
            return alloc

    sched = weeks + int(max(terms.max(), c_terms.max() if conv else 0)) + 1
    cadence = req.get("cadence_weeks", 1)
    replays = {"rolling": lambda w: (w - start) % cadence == 0,
               "one_shot": lambda w: w == start}
    if not req.get("compare", True):
        replays.pop("one_shot")
    state, ties = {}, []
    for name in replays:
        state[name] = {
            "accrued": hg.accrued0.copy() if hedge else None,
            "active": np.zeros((r_n, k_n), num.dt),
            "rolloff": np.zeros((r_n, k_n, sched), num.dt),
            "cost": np.zeros((weeks - start, r_n), num.dt),
        }
        if conv:
            state[name].update(
                active_c=np.zeros((n_s * c_n, kc_n), num.dt),
                rolloff_c=np.zeros((n_s * c_n, kc_n, sched), num.dt),
                cost_c=np.zeros((weeks - start, n_s * c_n), num.dt))
    out_t = np.zeros((weeks - start, r_n, k_n), num.dt)
    out_inc = np.zeros_like(out_t)
    out_cinc = np.zeros((weeks - start, n_s * c_n, kc_n), num.dt) if conv \
        else None
    dk = d.reshape(r_n, weeks, HOURS_PER_WEEK)
    for i, w in enumerate(range(start, weeks)):
        yhat = floor = widths = None
        if not hedge:
            yhat = fcst.forecast(w)
            if share is not None:
                yhat = share.compose(yhat, w)
            per_h = prefix_order_stats(yhat, fr, hw)
            if spot:
                floors = spot_floors(yhat, env_idx, s_cap, hw)
                per_h = np.minimum(per_h, floors[..., None])
                floor = floors[:, 0]
            widths, _ = monotone_stack(per_h, fr, terms, hw)
            out_t[i] = widths
        if conv:
            tot_c = to_cloud(yhat)
            ph_c = prefix_order_stats(tot_c, c_fr, hw)
            wc0, tops_c = monotone_stack(ph_c, c_fr, c_terms, hw)
            week1 = yhat[:, :HOURS_PER_WEEK].max(1)
        dw = dk[:, w]
        for name, is_dec in replays.items():
            st = state[name]
            dec = is_dec(w)
            active = st["active"] - st["rolloff"][:, :, w]
            if hedge:
                want = (None if follow is None or name != "rolling"
                        else np.rint(follow[i] / hg.dg).astype(np.int64))
                st["accrued"], widths = hg.decide(st["accrued"], active,
                                                  dk[:, w - 1], want, ties)
                out_t[i] = widths
            if not conv:
                inc = np.maximum(widths - active, 0.0)
                inc = np.where(dec & (inc > PURCHASE_EPS), inc, 0.0)
                active = active + inc
                level = active.sum(1)
            else:
                active_c = st["active_c"] - st["rolloff_c"][:, :, w]
                pinned = to_cloud(np.maximum(widths.sum(1),
                                             active.sum(1))[:, None])[:, 0]
                wc = np.maximum(tops_c - np.maximum(tops_c - wc0,
                                                    pinned[:, None]), 0.0)
                inc_c = np.maximum(wc - active_c, 0.0)
                inc_c = np.where(dec & (inc_c > PURCHASE_EPS), inc_c, 0.0)
                active_c = active_c + inc_c
                for k in range(kc_n):
                    st["rolloff_c"][:, k, w + c_terms[k]] += inc_c[:, k]
                need = np.maximum(week1 - active.sum(1), 0.0)
                alloc = allocate(active_c.sum(1), need)
                desired = np.maximum(widths - active, 0.0)
                lift = desired.sum(1)
                scale = np.where(lift > PURCHASE_EPS,
                                 np.maximum(lift - alloc, 0.0)
                                 / np.maximum(lift, 1e-9), 0.0)
                inc = desired * scale[:, None]
                inc = np.where(dec & (inc > PURCHASE_EPS), inc, 0.0)
                active = active + inc
                level = active.sum(1) + alloc
                st["active_c"] = active_c
                st["cost_c"][i] = (c_rates * active_c).sum(1) * HOURS_PER_WEEK
                if name == "rolling":
                    out_cinc[i] = inc_c
            for k in range(k_n):
                st["rolloff"][:, k, w + terms[k]] += inc[:, k]
            st["active"] = active
            cost = (rates * active).sum(1) * HOURS_PER_WEEK
            if spot:
                fl = np.maximum(floor, level)
                over = np.maximum(np.minimum(dw, fl[:, None])
                                  - level[:, None], 0.0).sum(1)
                cost = cost + od * over + s_rate * np.maximum(
                    dw - fl[:, None], 0.0).sum(1)
            else:
                cost = cost + od * np.maximum(dw - level[:, None], 0.0).sum(1)
            st["cost"][i] = cost
            if name == "rolling":
                out_inc[i] = inc

    def totals(st):
        per_row = st["cost"].astype(np.float64).sum(0).reshape(n_s, p_n)
        tot = per_row.sum(1)
        if conv:
            tot = tot + st["cost_c"].astype(np.float64).sum(0).reshape(
                n_s, c_n).sum(1)
        return tot

    roll = state["rolling"]
    weekly0 = roll["cost"][:, :p_n].astype(np.float64).sum(1)
    if conv:
        weekly0 = weekly0 + roll["cost_c"][:, :c_n].astype(np.float64).sum(1)
    ev = d[:, start * HOURS_PER_WEEK:]
    out = {
        "targets": out_t, "increments": out_inc, "conv_inc": out_cinc,
        "rolling": totals(roll), "weekly0": weekly0,
        "row_bill": roll["cost"].astype(np.float64).sum(0),
        "row_scale": np.asarray(ev, np.float64).mean(1),
        "ties": np.asarray(ties, np.float64),
    }
    if conv:
        out["cloud_scale"] = to_cloud(
            np.asarray(ev, np.float64).mean(1)[:, None])[:, 0]
    if "one_shot" in state:
        out["one_shot"] = totals(state["one_shot"])
        hs = hindsight_widths(ev, alphas, od)
        level = hs.sum(1)
        ev_wk = ev.reshape(r_n, -1, HOURS_PER_WEEK)
        over = np.maximum(ev_wk - level[:, None, None], 0.0).sum(2)
        committed = (rates * hs).sum(1) * HOURS_PER_WEEK
        per_row = (committed[:, None] + od * over).astype(np.float64).sum(1)
        out["hindsight"] = per_row.reshape(n_s, p_n).sum(1)
    return out


def merge(blocks: list[dict]) -> dict:
    """One replay's output from those of consecutive scenario blocks."""
    out = {}
    for k, v in blocks[0].items():
        if v is None or k == "weekly0":
            out[k] = v
        else:
            out[k] = np.concatenate([b[k] for b in blocks],
                                    axis=1 if v.ndim == 3 else 0)
    return out


def plan_block(args):
    """:func:`plan` on one scenario block, for a worker process:
    ``args`` is (cfg, req, keys, demand, numerics kind, block, follow)."""
    cfg, req, keys, demand, kind, block, follow = args
    return plan(cfg, req, keys, demand, Numerics(kind), block, follow)
