"""Commitment policies behind the rolling replay (paper §3.3.3 + baselines).

The weekly replay in ``repro.core.replan`` is a harness: roll expired
tranches off, let a *policy* pick this week's per-pool target stack, buy
only the increments, bill the week.  This module owns the policy side of
that contract so alternative purchasing strategies ride the same
``lax.scan`` step without touching the harness.  The planner and the
tournament (``repro.core.tournament``) both run that one step, and the
harness builds the context a policy sees:

    RollingPortfolioPolicy   the paper's Algorithm 1 loop: weekly prefix
                             refit -> per-horizon thresholds -> monotone
                             stack (the pre-PR replan body, op for op).
    OneShotPolicy            degenerate rolling policy with a single
                             decision week (what ``plan_fleet_pools``
                             prices at t0).
    HindsightPolicy          non-causal: the optimal constant stack on
                             the realized demand, rebought weekly so
                             expiring tranches run back-to-back.
    DeterministicHedgePolicy the break-even online algorithm of Ambati,
    RandomizedHedgePolicy    Urgaonkar & Sitaraman, *Hedge Your Bets:
                             Optimizing Long-Term Cloud Costs* (arXiv
                             2004.04302): forecast-free ski-rental per
                             capacity band, with the classical 2 and
                             e/(e-1) competitive-ratio guarantees.

A policy is two phases.  ``setup(ctx)`` runs once per replay at trace
time against a :class:`PolicyContext` (demand, cost lines, forecaster
prefix state, solver hooks) and returns ``(pstate0, decide)``; ``decide``
is the pure per-week function the scan body calls:

    pstate, Decision(targets, floor, yhat, is_decision)
        = decide(pstate, Observation(week, active, d_prev))

``pstate`` is an arbitrary pytree carried through the scan (the rolling
policy carries ``()`` so the default replay's carry — and therefore its
compiled program — is unchanged).  ``targets`` are absolute per-option
stack widths; the harness buys ``max(targets - active, 0)`` on weeks
where ``is_decision`` holds and never sells, so any policy inherits the
paper's commitments-only-expire semantics for free.

The hedging policies run classical ski-rental *per capacity band*: the
candidate range [0, top) per pool is cut into ``grid_size`` bands; each
band accrues the on-demand spend it would have absorbed while uncovered
by a commitment, and is committed (into the pool's cheapest available
SKU) once that spend reaches ``z x`` its buy price.  ``z = 1`` is the
deterministic break-even rule (competitive ratio <= 2); the randomized
variant draws ``z`` per band from the density ``e^z / (e - 1)`` on
(0, 1], the classical distribution with expected ratio e/(e-1) ~ 1.582.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import forecast as fc
from repro.core import portfolio as pf
from repro.core.demand import HOURS_PER_WEEK

# Competitive-ratio guarantees from Ambati et al. (arXiv 2004.04302):
# break-even deterministic ski rental is 2-competitive; the randomized
# threshold density e^z/(e-1) on (0, 1] achieves e/(e-1) in expectation.
DETERMINISTIC_CR_BOUND = 2.0
RANDOMIZED_CR_BOUND = math.e / (math.e - 1.0)


@dataclasses.dataclass
class PolicyContext:
    """Everything a policy may consult, assembled once per replay.

    Built in one place, ``replan._policy_context``, for every replay the
    planner and the tournament run: spot floors, migration
    recomposition and the grid solver ride in ``targets_for`` and
    ``compose_forecast``.  All array members are JAX arrays so the whole
    context can be closed over inside a traced program;
    ``solve_fn``/``targets_for``/``compose_forecast`` are trace-time
    callables, not runtime data."""

    demand: jnp.ndarray          # (P, T) whole-week demand, history + eval
    options: list
    clouds: tuple[str, ...]
    od: float
    rates: jnp.ndarray           # (K,) committed rates
    term_weeks: jnp.ndarray      # (K,) int32 terms
    avail: jnp.ndarray           # (P, K) option available on pool's cloud
    qs: jnp.ndarray              # (P, K) handover fractiles
    w_hours: jnp.ndarray         # (H,) horizon prefix lengths in hours
    start_weeks: int
    cadence_weeks: int
    horizon_weeks: int
    total_weeks: int
    state: fc.PrefixFitState
    solve_fn: Callable           # (state, week) -> beta  (scan or loop)
    irls_iters: int = 0
    #: carry the IRLS weight-adjustment moments in the scan state
    #: (frozen-weights incremental IRLS) instead of re-running full
    #: masked passes per week — see ``fc.irls_carry_init``.
    irls_carry: bool = False
    # yhat (P, Wh*168) -> (targets (P, K), spot floor (P,) | None)
    targets_for: Callable | None = None
    # migration hook: (yhat, week) -> recomposed yhat
    compose_forecast: Callable | None = None
    #: "weekly" (the harness cadence rule) or "breach": re-solve only in
    #: weeks where last week's realized demand exited the forecast band
    #: held since the previous decision (plus the mandatory start week).
    cadence_mode: str = "weekly"
    #: (q_lo, q_hi) forecast fractile pair that frames the breach band.
    breach_band: tuple = (0.05, 0.95)
    #: hour-budget multiplier: a week breaches when more than
    #: ``tolerance x nominal miss mass`` of its 168 hours exit the band.
    breach_tolerance: float = 4.0
    #: scenario-batched replays flatten (N, P) -> R demand rows; breach
    #: decisions are fleet-wide *per scenario*, so the mask is reduced
    #: over each block of ``num_pools / scenario_blocks`` rows.
    scenario_blocks: int = 1

    @property
    def num_pools(self) -> int:
        return self.demand.shape[0]

    @property
    def num_options(self) -> int:
        return self.qs.shape[-1]

    @property
    def horizon_hours(self) -> int:
        return self.horizon_weeks * HOURS_PER_WEEK


class Observation(NamedTuple):
    """Per-week inputs the harness hands to ``decide``."""

    week: jnp.ndarray            # scalar int32, absolute week index
    active: jnp.ndarray          # (P, K) committed stack after roll-offs
    d_prev: jnp.ndarray | None   # (P, 168) last week's realized demand
    #  (None unless the policy sets ``needs_prev_demand`` — the default
    #  harness program must not gain even a dead gather)
    #: (P, TRAIL_WEEKS*168) trailing realized demand window, the spread
    #: anchor for ``fc.anchored_fractile_levels``; gathered only under
    #: ``cadence_mode="breach"`` or calibration telemetry, None otherwise.
    d_trail: Any = None


class Decision(NamedTuple):
    """Per-week outputs of ``decide``."""

    targets: jnp.ndarray         # (P, K) absolute stack widths to hold
    floor: jnp.ndarray | None    # (P,) spot floor (forecasting + spot only)
    yhat: jnp.ndarray | None     # (P, H) forecast (None = non-forecasting)
    is_decision: jnp.ndarray     # bool: may this week buy?  scalar, or a
    #  per-row (P,) vector under ``cadence_mode="breach"`` (uniform
    #  within each scenario block)
    #: optional dict of extra per-week arrays the harness forwards into
    #: the scan outputs verbatim (breach mode emits the active band as
    #: ``band_lo``/``band_hi``); None on the default paths so the weekly
    #: compiled program is unchanged.
    extras: Any = None


class Policy:
    """Base policy: subclass and implement :meth:`setup`.

    The replay keeps one compiled program per policy *value*, so a policy
    with parameters is a frozen dataclass over them: equal parameters give
    equal, equal-hashing policies, and reuse the program."""

    name: str = "policy"
    #: produces a forecast (yhat) — required by the spot / migration /
    #: convertible bands, which all key on this week's forecast.
    forecasting: bool = False
    #: wants last week's realized demand in the Observation.
    needs_prev_demand: bool = False

    def setup(self, ctx: PolicyContext) -> tuple[Any, Callable]:
        raise NotImplementedError

    def _is_decision(self, ctx: PolicyContext, w) -> jnp.ndarray:
        """The harness cadence rule: every ``cadence_weeks`` from the
        start week; ``cadence_weeks == 0`` means the single start week
        (the one-shot baseline replay)."""
        if ctx.cadence_weeks > 0:
            return (w - ctx.start_weeks) % ctx.cadence_weeks == 0
        return w == ctx.start_weeks


@dataclasses.dataclass(frozen=True)
class RollingPortfolioPolicy(Policy):
    """The paper's rolling loop as a policy: re-fit the forecaster on the
    week-``w`` prefix (one gather + ridge solve against the cumulative
    normal equations), forecast the horizon, and run Algorithm 1 steps
    2-4 for the target stack.  This is the pre-refactor ``replan`` scan
    body verbatim — the default-policy goldens pin that equivalence."""

    name = "rolling_portfolio"
    forecasting = True

    def setup(self, ctx: PolicyContext):
        carry_irls = ctx.irls_carry and ctx.irls_iters > 0
        breach = ctx.cadence_mode == "breach"
        # Incremental IRLS: seed the scan state with the exact adjustment
        # moments on the start prefix; each week then solves against
        # prefix + carried moments and appends only the newest week's
        # block.  Off (the default) the pstate stays () and the compiled
        # program is unchanged.
        inner0 = (
            fc.irls_carry_init(ctx.state, ctx.start_weeks, ctx.irls_iters)
            if carry_irls else ()
        )
        if breach:
            q_lo, q_hi = ctx.breach_band
            # Integer hour budgets: a week breaches when strictly MORE
            # than tolerance x the nominal miss mass of its 168 hours
            # exit the band.  Counts and thresholds are exact ints so a
            # host-side python-loop oracle over the emitted bands
            # reproduces the decision mask bit-for-bit.
            allow_above = int(
                ctx.breach_tolerance * (1.0 - q_hi) * HOURS_PER_WEEK
            )
            allow_below = int(ctx.breach_tolerance * q_lo * HOURS_PER_WEEK)
            blocks = ctx.scenario_blocks
            rows_per = ctx.num_pools // blocks
            band0 = (
                jnp.zeros((ctx.num_pools,), jnp.float32),
                jnp.zeros((ctx.num_pools,), jnp.float32),
            )
            pstate0 = (inner0, band0)
        else:
            pstate0 = inner0

        def decide(pstate, obs: Observation):
            w = obs.week
            if breach:
                inner, (lo, hi) = pstate
            else:
                inner = pstate
            # Device scopes: the refit and forecast, then the stack and
            # spot solve (the trace's op names carry them).
            with jax.named_scope("forecast"):
                if carry_irls:
                    g_adj, r_adj = inner
                    beta = fc.solve_prefix_adjusted(
                        ctx.state, w, g_adj, r_adj
                    )
                    inner = fc.irls_carry_extend(
                        ctx.state, beta, g_adj, r_adj, w
                    )
                else:
                    beta = ctx.solve_fn(ctx.state, w)
                    beta = fc.irls_refine(ctx.state, beta, w, ctx.irls_iters)
                yhat = fc.predict_from_beta(
                    ctx.state, beta, w * HOURS_PER_WEEK, ctx.horizon_hours
                )
                if ctx.compose_forecast is not None:
                    yhat = ctx.compose_forecast(yhat, w)
            with jax.named_scope("solve"):
                targets, floor = ctx.targets_for(yhat)
            if not breach:
                return inner, Decision(
                    targets, floor, yhat, self._is_decision(ctx, w)
                )
            # Band breach on the most recent completed week: the band
            # held in the carry is the fractile pair of the forecast made
            # at the last decision week.
            above = (obs.d_prev > hi[:, None]).sum(-1)       # (R,) int
            below = (obs.d_prev < lo[:, None]).sum(-1)
            breach_row = (above > allow_above) | (below > allow_below)
            is_dec = (w == ctx.start_weeks) | breach_row
            # Fleet-wide per scenario: any pool breaching re-solves its
            # whole scenario block (blocks == 1 -> the whole fleet).
            scen = is_dec.reshape(blocks, rows_per).any(axis=1)
            is_dec = jnp.repeat(scen, rows_per)              # (R,) bool
            band = fc.anchored_fractile_levels(obs.d_trail, (q_lo, q_hi))
            lo = jnp.where(is_dec, band[:, 0], lo)
            hi = jnp.where(is_dec, band[:, 1], hi)
            return (inner, (lo, hi)), Decision(
                targets, floor, yhat, is_dec,
                {"band_lo": lo, "band_hi": hi},
            )

        return pstate0, decide


@dataclasses.dataclass(frozen=True)
class OneShotPolicy(RollingPortfolioPolicy):
    """Degenerate rolling policy: one decision at the start week, then
    tranches only expire — what ``plan_fleet_pools`` prices at t0."""

    name = "one_shot"

    def _is_decision(self, ctx: PolicyContext, w):
        return w == ctx.start_weeks


@dataclasses.dataclass(frozen=True)
class HindsightPolicy(Policy):
    """Non-causal reference: the optimal *constant* stack on the realized
    evaluation demand (billing lines, ``term_weighting=0``), held every
    week.  Deciding weekly makes expiring tranches rebuy back-to-back, so
    the replayed cost matches the analytic hindsight baseline."""

    name = "hindsight"

    def setup(self, ctx: PolicyContext):
        widths = pf.hindsight_stack(
            ctx.demand[:, ctx.start_weeks * HOURS_PER_WEEK:], ctx.options,
            ctx.clouds, od_rate=ctx.od,
        )                                                      # (P, K)

        def decide(pstate, obs: Observation):
            is_dec = jnp.asarray(True)
            return pstate, Decision(widths, None, None, is_dec)

        return (), decide


def _hedge_threshold(u: jnp.ndarray) -> jnp.ndarray:
    """Inverse CDF of the density e^z/(e-1) on (0, 1]: the classical
    randomized ski-rental threshold distribution."""
    return jnp.log1p(u * (math.e - 1.0))


@dataclasses.dataclass(frozen=True)
class DeterministicHedgePolicy(Policy):
    """Ambati et al.'s break-even hedging rule per capacity band.

    The candidate range [0, ``top_multiplier`` x history peak) of each
    pool is split into ``grid_size`` equal bands.  A band accrues the
    on-demand spend it absorbed last week whenever it sits above the
    committed stack top; once the accrued spend reaches ``z x`` the
    band's buy price (rate x term of the pool's cheapest available SKU)
    the band is committed and its meter resets — after the tranche
    expires the band starts saving for the next one.  No forecast, no
    solver: the guarantees are adversarial (total cost <= 2 x the
    per-band hindsight optimum for ``z = 1``).  Decisions fire every
    week regardless of the harness cadence — reacting on the week the
    meter crosses is the algorithm."""

    name = "deterministic_hedge"
    needs_prev_demand = True

    grid_size: int = 32
    top_multiplier: float = 1.5

    def __post_init__(self):
        if self.grid_size < 1:
            raise ValueError(f"grid_size must be >= 1, got {self.grid_size}")
        if self.top_multiplier <= 0:
            raise ValueError(
                f"top_multiplier must be > 0, got {self.top_multiplier}"
            )
        object.__setattr__(self, "grid_size", int(self.grid_size))
        object.__setattr__(
            self, "top_multiplier", float(self.top_multiplier)
        )

    def _thresholds(self, num_pools: int) -> jnp.ndarray:
        return jnp.ones((num_pools, self.grid_size), jnp.float32)

    def _band_spend(self, d, levels, dg, od):
        """(P, G) on-demand spend each band would have absorbed over the
        demand block ``d`` (P, T): od x clipped occupancy of the band."""
        occ = jnp.clip(
            d[:, None, :] - levels[:, :, None], 0.0, dg[:, None, None]
        )
        return od * occ.sum(-1)

    def setup(self, ctx: PolicyContext):
        num_p, num_k, g = ctx.num_pools, ctx.num_options, self.grid_size
        hist = ctx.demand[:, : ctx.start_weeks * HOURS_PER_WEEK]
        top = jnp.maximum(hist.max(-1), 1e-6) * self.top_multiplier  # (P,)
        dg = top / g
        levels = dg[:, None] * jnp.arange(g, dtype=jnp.float32)[None, :]
        # Each band's upper edge in one rounding, not as ``levels + dg``:
        # the compiled replay hoists that sum out of its loop and fuses it
        # with the multiply into one rounding, so a band's edge would sit
        # an ulp off where the op-by-op replay puts it, and a band that
        # the stack top just covers would keep accruing.
        uppers = dg[:, None] * jnp.arange(1, g + 1, dtype=jnp.float32)
        # One designated SKU per pool: cheapest rate available on its
        # cloud (ski rental hedges od vs ONE buy price; portfolio mixing
        # is the forecasting planner's game).
        rate_eff = jnp.where(ctx.avail, ctx.rates[None, :], jnp.inf)
        kstar = jnp.argmin(rate_eff, axis=-1)                    # (P,)
        onehot = jax.nn.one_hot(kstar, num_k, dtype=jnp.float32)
        # Finite-horizon Bahncard adaptation: tranches bill weekly while
        # active, so the most a stranded commitment can cost inside the
        # replay window is rate x min(term, window) — price the ski
        # rental at that, or a term longer than the window would push
        # break-even past the horizon and the rule degenerates to
        # never-commit.
        eff_term = jnp.minimum(
            ctx.term_weeks[kstar], ctx.total_weeks - ctx.start_weeks
        ).astype(jnp.float32)
        buy_unit = ctx.rates[kstar] * eff_term * HOURS_PER_WEEK  # (P,)
        band_price = buy_unit * dg                               # (P,)
        z = self._thresholds(num_p)                              # (P, G)
        # Pre-accrue the uncommitted history [0, start-1): the first
        # decision's Observation carries week start-1, so stopping one
        # week short here counts every hour exactly once.
        a0 = jnp.zeros((num_p, g), jnp.float32)
        pre = hist[:, : max(ctx.start_weeks - 1, 0) * HOURS_PER_WEEK]
        if pre.shape[-1]:
            a0 = a0 + self._band_spend(pre, levels, dg, ctx.od)

        def decide(pstate, obs: Observation):
            accrued = pstate
            stack_top = obs.active.sum(-1)                       # (P,)
            covered = uppers <= stack_top[:, None] + 1e-6
            spend = self._band_spend(obs.d_prev, levels, dg, ctx.od)
            accrued = jnp.where(covered, accrued, accrued + spend)
            commit = ~covered & (accrued >= z * band_price[:, None])
            accrued = jnp.where(commit, 0.0, accrued)
            width = dg * commit.sum(-1)                          # (P,)
            targets = (stack_top + width)[:, None] * onehot      # (P, K)
            return accrued, Decision(
                targets, None, None, jnp.asarray(True)
            )

        return a0, decide


@dataclasses.dataclass(frozen=True)
class RandomizedHedgePolicy(DeterministicHedgePolicy):
    """The randomized variant: each band draws its own threshold ``z``
    from the density e^z/(e-1) on (0, 1] at setup, lowering the expected
    competitive ratio from 2 to e/(e-1) against an oblivious adversary."""

    name = "randomized_hedge"

    seed: int = 0

    def __post_init__(self):
        super().__post_init__()
        object.__setattr__(self, "seed", int(self.seed))

    def _thresholds(self, num_pools: int) -> jnp.ndarray:
        u = jax.random.uniform(
            jax.random.PRNGKey(self.seed), (num_pools, self.grid_size)
        )
        return _hedge_threshold(u)


POLICIES: dict[str, Callable[[], Policy]] = {
    "rolling_portfolio": RollingPortfolioPolicy,
    "one_shot": OneShotPolicy,
    "hindsight": HindsightPolicy,
    "deterministic_hedge": DeterministicHedgePolicy,
    "randomized_hedge": RandomizedHedgePolicy,
}


def get_policy(policy: "Policy | str | None") -> Policy:
    """Resolve the ``policy=`` planner kwarg: None -> the paper's rolling
    loop, a registry name -> a fresh instance, an instance -> itself."""
    if policy is None:
        return RollingPortfolioPolicy()
    if isinstance(policy, Policy):
        return policy
    if isinstance(policy, str):
        try:
            return POLICIES[policy]()
        except KeyError:
            raise ValueError(
                f"unknown policy {policy!r}; known: {sorted(POLICIES)}"
            ) from None
    raise TypeError(f"policy must be a Policy, name or None, got {policy!r}")

