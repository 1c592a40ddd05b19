"""Multi-option commitment portfolios (paper §3 generalized; Table 2 SKUs).

The paper optimizes ONE commitment level against one on-demand premium, yet
its Table 2 lists eight savings-plan SKUs across three clouds with distinct
1y/3y discounts.  Mixing purchasing options strictly dominates any
single-option plan ("Hedge Your Bets", Ambati et al.; "No Reservations",
Ambati/Irwin/Shenoy): cheap long commitments cover the always-on demand
base, lighter short commitments the mid band, on-demand the peaks.

Model.  Capacity is built as a *stack* of tranches: option k covers the band
(s_{k-1}, s_k], on-demand everything above the stack top.  Each option is a
**cost line** over slice utilization: a capacity slice at height y is used in
the hours where demand f_t > y and idle otherwise, so with
u(y) = #{t: f_t < y} / T the per-hour cost of covering the slice with
option k is

    l_k(u) = alpha_k * (1 - u) + beta_k * u
       alpha_k : $/hour while the slice is USED
       beta_k  : $/hour while the slice sits IDLE
    committed option:  alpha = beta = committed rate r_k (paid regardless);
                       beta optionally discounted by term length — a
                       stranded 1y tranche stops billing 3x sooner than a
                       stranded 3y tranche (``term_weighting``).
    on-demand:         alpha = od_rate, beta = 0.

The paper's Eq (1) is the K=1 instance (alpha_1=0, beta_1=B, od_rate=A).

Because every l_k is linear in u and u(y) is monotone in y, the optimal
stack is the *lower envelope* of the K+1 lines: each option wins a
contiguous utilization interval, so each optimal threshold s_k is a weighted
quantile of f at the fractile where option k hands over to the next — the
exact stacked generalization of the A/(A+B) newsvendor quantile in
``commitment.optimal_commitment_quantile``.  The objective stays convex
piecewise-linear, so a grid solver over the Pallas over/under sweep serves
as the jit/vmap oracle (``optimal_portfolio_grid``).

Band-assignment solver (exact, O(T log T) per pool): the argmin of the K+1
lines over the T+1 discrete utilization levels i/T is *demand independent* —
one (T+1, K+1) argmin shared by every pool — and per-pool thresholds are
gathers into the pool's sorted demand.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.capacity import pricing

# Fail at import, not as a silently absurd plan, if the pricing data rows
# this module turns into cost lines ever stop satisfying their invariants.
pricing.validate_tables()


@dataclasses.dataclass(frozen=True)
class PurchaseOption:
    """One purchasable commitment SKU.

    ``rate`` is the committed $/unit-hour in the repo's normalized units
    (mean Table-2 3y committed rate = 1.0, so on-demand ~= 2.1).

    ``convertible`` marks the cloud-level exchangeable SKU class
    (``pricing.CONVERTIBLE_PLANS``): a convertible tranche is purchasable
    against a *cloud*, not a (cloud, region, machine-family) pool, and may
    be re-pinned to a different family of that cloud at every re-plan
    boundary — the lever that keeps long commitments useful through a
    hardware-generation migration.  The flexibility costs a discount
    haircut, so on a static fleet a convertible line never beats the
    matching standard line; its value is dynamic and the planners size it
    on cloud-level residual demand (see ``core.replan``)."""

    name: str
    cloud: str
    rate: float
    term_weeks: int
    convertible: bool = False


ON_DEMAND = "on-demand"


def options_from_pricing(
    plans: Sequence[pricing.SavingsPlan] | None = None,
    *,
    terms: Sequence[str] = ("1y", "3y"),
    clouds: Sequence[str] | None = None,
) -> list[PurchaseOption]:
    """Turn Table 2 rows into PurchaseOptions (1y and 3y per SKU), rates
    normalized so the mean 3y committed rate is 1.0 — the same unit the
    single-level planner prices commitments in."""
    plans = list(plans if plans is not None else pricing.SAVINGS_PLANS)
    if clouds is not None:
        plans = [p for p in plans if p.cloud in clouds]
    base = 1.0 - pricing.mean_discount_3y()
    out = []
    for p in plans:
        if "1y" in terms:
            out.append(PurchaseOption(
                f"{p.cloud}/{p.family}/1y", p.cloud,
                (1.0 - p.discount_1y) / base, 52,
            ))
        if "3y" in terms:
            out.append(PurchaseOption(
                f"{p.cloud}/{p.family}/3y", p.cloud,
                (1.0 - p.discount_3y) / base, 156,
            ))
    return out


def convertible_options_from_pricing(
    clouds: Sequence[str] | None = None,
    *,
    terms: Sequence[str] = ("1y", "3y"),
) -> list[PurchaseOption]:
    """The per-cloud convertible SKUs (``pricing.CONVERTIBLE_PLANS``):
    rate = (1 - (mean standard discount - haircut)) in the same normalized
    units as :func:`options_from_pricing`, one SKU per cloud per term —
    family-agnostic by construction."""
    if clouds is None:
        clouds = sorted(pricing.known_clouds())
    base = 1.0 - pricing.mean_discount_3y()
    out = []
    for c in clouds:
        d1, d3 = pricing.convertible_discounts(c)
        if "1y" in terms:
            out.append(PurchaseOption(
                f"{c}/convertible/1y", c, (1.0 - d1) / base, 52,
                convertible=True,
            ))
        if "3y" in terms:
            out.append(PurchaseOption(
                f"{c}/convertible/3y", c, (1.0 - d3) / base, 156,
                convertible=True,
            ))
    return out


def resolve_convertible(
    convertible, clouds: Sequence[str]
) -> list[PurchaseOption] | None:
    """Normalize the planner-facing ``convertible=`` argument: None/False
    disables (the legacy bit-identical path), True takes the default
    per-cloud SKUs for the clouds present in the fleet, and an explicit
    option list passes through (every option must be convertible)."""
    if convertible is None or convertible is False:
        return None
    if convertible is True:
        convertible = convertible_options_from_pricing(
            sorted(set(clouds))
        )
    if not isinstance(convertible, (list, tuple)) or not all(
        isinstance(o, PurchaseOption) and o.convertible for o in convertible
    ):
        raise TypeError(
            "convertible must be None/bool or a list of convertible "
            f"PurchaseOptions, got {convertible!r}"
        )
    # An empty list (e.g. a caller's cloud filter matched nothing) means
    # "no convertible SKUs exist" — the disabled path, not a zero-option
    # solve that would crash on conv_terms.max().
    return list(convertible) or None


def convertible_cloud_setup(
    conv_options: Sequence[PurchaseOption],
    pool_clouds: Sequence[str],
    *,
    term_weighting: float = 0.0,
    od_rate: float = 2.1,
):
    """Shared cloud-level machinery for the convertible band, used
    identically by the one-shot planner and the rolling replay so the two
    cannot drift apart: the sorted cloud axis, the (C, P) membership
    matrix, per-cloud convertible cost lines (wrong-cloud SKUs priced at
    on-demand, same trick as ``pool_option_lines``), handover fractiles,
    and the per-SKU terms.  Returns
    ``(clouds, member, alphas, betas, fractiles, term_weeks)``."""
    clouds = sorted(set(pool_clouds))
    member = jnp.asarray(
        [[1.0 if c == pc else 0.0 for pc in pool_clouds] for c in clouds],
        jnp.float32,
    )
    al, be, _ = pool_option_lines(
        conv_options, clouds, term_weighting=term_weighting,
        od_rate=od_rate,
    )
    qs = jax.vmap(
        functools.partial(handover_fractiles, od_rate=od_rate)
    )(al, be)
    terms = jnp.asarray(
        [o.term_weeks for o in conv_options], jnp.int32
    )
    return clouds, member, al, be, qs, terms


def truncate_convertible_stack(
    tops: jnp.ndarray, widths: jnp.ndarray, pinned: jnp.ndarray
) -> jnp.ndarray:
    """(C, Kc) convertible band widths: the cloud-total stack truncated
    below the pool-pinned level — option bands cover (top - width, top];
    everything under ``pinned`` (C,) belongs to the cheaper family-pinned
    standard SKUs, so convertible keeps only the part of each band above
    it."""
    return jnp.maximum(
        tops - jnp.maximum(tops - widths, pinned[:, None]), 0.0
    )


def allocate_convertible(
    conv_width: jnp.ndarray,
    excess: jnp.ndarray,
    membership: jnp.ndarray,
    *,
    rounds: int = 3,
) -> jnp.ndarray:
    """Re-pin each cloud's convertible capacity onto its pools for one
    period.

    ``conv_width`` (C,) is the live convertible width per cloud,
    ``excess`` (P,) each pool's forecast demand above its own pinned
    stack, ``membership`` (C, P) the 0/1 cloud-of-pool matrix.  Allocation
    is proportional-to-excess with ``rounds`` redistribution passes (a
    pool never receives more than its excess while another of its cloud
    still starves); capacity left over when a cloud's total excess is
    smaller than its convertible width stays unallocated — it bills its
    committed rate either way and covers nothing.  Pure array math so it
    runs inside the rolling replay's scan."""
    alloc = jnp.zeros_like(excess)
    need = excess
    rem = conv_width
    # Full float32 contractions: a TPU would otherwise round the demand
    # volumes to bfloat16 inside the cloud aggregation.
    highest = jax.lax.Precision.HIGHEST
    for _ in range(rounds):
        cloud_need = jnp.matmul(membership, need, precision=highest)  # (C,)
        give = jnp.matmul(
            membership.T, rem / jnp.maximum(cloud_need, 1e-9),
            precision=highest,
        ) * need                                             # (P,)
        give = jnp.minimum(give, need)
        alloc = alloc + give
        need = need - give
        rem = rem - jnp.matmul(membership, give, precision=highest)
    return alloc


def option_lines(
    options: Sequence[PurchaseOption],
    *,
    term_weighting: float = 0.0,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(alphas, betas) cost-line coefficients for ``options``.

    ``term_weighting`` in [0, 1] interpolates the idle-cost coefficient
    between exact in-window dollars (0.0: beta = rate — every active tranche
    bills all window hours) and term-proportional stranding (1.0:
    beta = rate * term/term_max — an idle tranche bills only until it
    expires, so short terms are cheaper to strand; this is what lets weaker
    1y discounts onto the envelope as a hedging mid-band)."""
    if not options:
        raise ValueError("portfolio requires at least one purchase option")
    rates = jnp.asarray([o.rate for o in options], jnp.float32)
    terms = jnp.asarray([o.term_weeks for o in options], jnp.float32)
    load = (1.0 - term_weighting) + term_weighting * terms / terms.max()
    return rates, rates * load


def pool_option_lines(
    options: Sequence[PurchaseOption],
    clouds: Sequence[str],
    *,
    term_weighting: float = 0.0,
    od_rate: float = 2.1,
) -> tuple[jnp.ndarray, jnp.ndarray, np.ndarray]:
    """Per-pool cost lines (P, K) for a fleet of pools on ``clouds``.

    Commitments are purchased per cloud/SKU (Table 2), so an option is
    purchasable in a pool only when their clouds match.  Rather than ragged
    per-pool option lists (which would break vmap over the P axis),
    unavailable options are priced *at* the on-demand rate (alpha = beta =
    od_rate): such a line never undercuts the on-demand line at any
    utilization u > 0, and the tie at u = 0 resolves to on-demand (listed
    first in every solver's argmin), so the envelope provably assigns them
    zero width.  Returns (alphas (P, K), betas (P, K), available (P, K))."""
    al, be = option_lines(options, term_weighting=term_weighting)
    avail = np.asarray(
        [[o.cloud == c for o in options] for c in clouds], bool
    )
    mask = jnp.asarray(avail)
    return (
        jnp.where(mask, al[None, :], od_rate),
        jnp.where(mask, be[None, :], od_rate),
        avail,
    )


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PortfolioPlan:
    """A stacked-commitment plan for one pool.

    Arrays are aligned with the input option list; options off the envelope
    get zero width.  ``levels[k]`` is the stack top of option k's band (==
    the bottom of the band when the width is zero).

    With a spot line (``spot_rate``/``spot_cap`` on the solvers) the plan
    additionally carries ``spot_floor`` — the demand level above which spot
    serves (on-demand covers (total, spot_floor], spot everything higher) —
    and ``spot_frac``, the demand-volume fraction routed to spot (<= the
    chance-constraint cap).  Both are None on spot-free plans, keeping the
    legacy pytree shape."""

    levels: jnp.ndarray       # (..., K) band tops
    widths: jnp.ndarray       # (..., K) band widths, >= 0
    total: jnp.ndarray        # (...,)   stack top = on-demand threshold
    cost: jnp.ndarray         # (...,)   objective value (cost-line dollars)
    spot_floor: jnp.ndarray | None = None   # (...,) spot band bottom
    spot_frac: jnp.ndarray | None = None    # (...,) demand volume on spot


def _stack_heights(
    has: jnp.ndarray, lo: jnp.ndarray, widths: jnp.ndarray, sentinel
) -> jnp.ndarray:
    """Geometric stack tops from per-option band widths: cumulative widths
    in envelope depth order (ascending first-band index ``lo``; options off
    the envelope sort last via ``sentinel``), scattered back to input-option
    order.  Shared by the exact and grid solvers."""
    order = jnp.argsort(jnp.where(has, lo, sentinel), axis=-1)
    inv = jnp.argsort(order, axis=-1)
    w_ord = jnp.take_along_axis(
        jnp.broadcast_to(widths, jnp.broadcast_shapes(widths.shape, order.shape)),
        jnp.broadcast_to(order, jnp.broadcast_shapes(widths.shape, order.shape)),
        axis=-1,
    )
    heights = jnp.cumsum(w_ord, axis=-1)
    return jnp.take_along_axis(
        heights, jnp.broadcast_to(inv, heights.shape), axis=-1
    )


def _band_assignment(
    t: int, alphas: jnp.ndarray, betas: jnp.ndarray, od_rate: float
) -> jnp.ndarray:
    """(T,) argmin option per capacity band; K = on-demand.

    Band j sits between sorted demand values j-1 and j, where exactly j of
    the T hours fall below it: per-height cost of covering it with option k
    is alpha_k*(T-j) + beta_k*j, vs od_rate*(T-j) uncovered.  On-demand is
    placed FIRST so cost ties (e.g. a zero-discount option) resolve to no
    commitment.

    A running minimum over the K lines (a strict ``<`` keeps the earlier
    line on a tie, as argmin does) never holds the (T, K) cost table:
    vmapped over rows with per-row lines — the hindsight baseline at
    fleet scale — that table is (R, T, K), 23 GB at R=16384 rows of
    three years, past a 16 GiB chip."""
    j = jnp.arange(t, dtype=jnp.float32)
    best_cost = jnp.asarray(od_rate, jnp.float32) * (t - j)
    best = jnp.zeros((t,), jnp.int32)
    for k in range(alphas.shape[0]):
        cost = alphas[k] * (t - j) + betas[k] * j
        better = cost < best_cost
        best = jnp.where(better, k + 1, best)
        best_cost = jnp.where(better, cost, best_cost)
    return best


@functools.partial(jax.jit, static_argnames=("od_rate",))
def optimal_portfolio_stack(
    f: jnp.ndarray,
    alphas: jnp.ndarray,
    betas: jnp.ndarray,
    *,
    od_rate: float = 2.1,
    spot_rate: jnp.ndarray | float | None = None,
    spot_cap: jnp.ndarray | float | None = None,
) -> PortfolioPlan:
    """Exact minimizer of the stacked cost-line objective. f (..., T).

    The lower-envelope intervals are computed once (demand independent);
    per-pool thresholds are gathers into sorted demand — vmap/jit friendly,
    O(T log T) per pool like the single-level quantile solver.

    ``spot_rate``/``spot_cap`` (scalars; vmap for per-pool values) add the
    spot line alpha = spot_rate, beta = 0 under the chance-constraint cap on
    the demand-volume fraction routed to spot (``core.spot``).  The capped
    optimum keeps the envelope shape: marginal spot saving per unit volume,
    l_best(u)/(1-u) - spot_rate, is nondecreasing in u, so spot takes the
    TOP of the demand distribution down to a floor — the larger of the
    envelope entry (where spot stops beating committed lines) and the
    volume cap (smallest floor whose above-volume fits the cap, snapped up
    to a band edge so the cap is never exceeded).  Committed bands above
    the floor are truncated; on-demand covers (stack top, floor].  With
    ``spot_rate=None`` (default) the computation is the legacy spot-free
    program, bit for bit."""
    t = f.shape[-1]
    k = alphas.shape[0]
    best = _band_assignment(t, alphas, betas, od_rate)  # (T,)
    opt = best - 1  # -1 = on-demand, 0..K-1 = options

    sorted_f = jnp.sort(f, axis=-1)  # (..., T); band j's top is sorted_f[j]
    bands = jnp.arange(t)
    mask = opt[None, :] == jnp.arange(k)[:, None]      # (K, T)
    has = mask.any(-1)
    hi = jnp.where(mask, bands[None, :], -1).max(-1)            # (K,)
    lo = jnp.where(mask, bands[None, :], t + 1).min(-1)         # (K,)

    def gather(idx):  # sorted_f[..., idx] with idx (K,) >= 0
        return jnp.take(sorted_f, idx, axis=-1)

    # Exact objective: integrate the winning line over every band.
    jf = bands.astype(jnp.float32)
    alph_all = jnp.concatenate([jnp.asarray([od_rate], jnp.float32), alphas])
    beta_all = jnp.concatenate([jnp.asarray([0.0], jnp.float32), betas])
    line_best = alph_all[best] * (t - jf) + beta_all[best] * jf     # (T,)
    h = jnp.diff(sorted_f, axis=-1, prepend=jnp.zeros_like(sorted_f[..., :1]))
    covered = (opt >= 0)

    if spot_rate is None:
        tops = gather(jnp.maximum(hi, 0))
        bottoms = jnp.where(lo > 0, gather(jnp.maximum(lo - 1, 0)), 0.0)
        widths = jnp.where(has, tops - bottoms, 0.0)
        # The committed bands tile a prefix of the capacity axis, so
        # cumulative widths in envelope depth order ARE the geometric tops.
        # The (has, lo) assignment is demand independent — one permutation
        # for every pool.
        heights = _stack_heights(has, lo, widths, t + 1)
        cost_committed = (h * line_best * covered).sum(-1)
        total = widths.sum(-1) + jnp.zeros_like(f[..., 0])
        over = jnp.maximum(f - total[..., None], 0.0).sum(-1)
        cost = cost_committed + od_rate * over

        shape = f.shape[:-1] + (k,)
        return PortfolioPlan(
            levels=jnp.broadcast_to(heights, shape),
            widths=jnp.broadcast_to(widths, shape),
            total=total,
            cost=cost,
        )

    sr = jnp.asarray(spot_rate, jnp.float32)
    sc = jnp.asarray(
        1.0 if spot_cap is None else spot_cap, jnp.float32
    )
    # Envelope bound: spot wins the top-contiguous region where its line
    # undercuts the base winner (strictly, so rate ties keep zero spot).
    spot_line = sr * (t - jf)                                       # (T,)
    spot_better = (spot_line < line_best).astype(jnp.int32)
    all_above = jnp.flip(jnp.cumprod(jnp.flip(spot_better)))
    j_env = jnp.where(all_above.any(), jnp.argmax(all_above), t)

    # Volume bound, per pool: vb[j] = spot volume if the floor sits at band
    # j's bottom (level sorted_f[j-1]); nonincreasing in j, so the first
    # band index inside the cap is the lowest admissible floor.
    total_vol = sorted_f.sum(-1)                                    # (...,)
    suffix = jnp.flip(jnp.cumsum(jnp.flip(sorted_f, -1), -1), -1)
    above_cnt = (t - 1 - bands).astype(f.dtype)
    va = (suffix - sorted_f) - above_cnt * sorted_f                 # (..., T)
    vb = jnp.concatenate([total_vol[..., None], va[..., :-1]], -1)
    feasible = vb <= sc * total_vol[..., None]
    j_vol = jnp.where(feasible.any(-1), jnp.argmax(feasible, -1), t)
    j_floor = jnp.maximum(j_env, j_vol)                             # (...,)

    floor_idx = jnp.clip(j_floor - 1, 0, t - 1)[..., None]
    floor = jnp.where(
        j_floor[..., None] > 0,
        jnp.take_along_axis(sorted_f, floor_idx, -1),
        0.0,
    )[..., 0]
    spot_vol = jnp.where(
        j_floor >= t,
        0.0,
        jnp.take_along_axis(
            vb, jnp.clip(j_floor, 0, t - 1)[..., None], -1
        )[..., 0],
    )

    # Committed bands truncate at the floor (their tops gather per pool
    # now — the floor is demand dependent even though the assignment isn't).
    hi2 = jnp.minimum(hi, j_floor[..., None] - 1)                 # (..., K)
    has2 = has & (lo <= hi2)
    tops = jnp.take_along_axis(
        jnp.broadcast_to(sorted_f, f.shape[:-1] + (t,)),
        jnp.clip(hi2, 0, t - 1), -1,
    )
    bottoms = jnp.where(lo > 0, gather(jnp.maximum(lo - 1, 0)), 0.0)
    widths = jnp.where(has2, tops - bottoms, 0.0)
    heights = _stack_heights(has2, lo, widths, t + 1)

    below = bands < j_floor[..., None]                            # (..., T)
    cost_committed = (h * line_best * covered * below).sum(-1)
    total = widths.sum(-1)
    over = jnp.maximum(f - total[..., None], 0.0).sum(-1)
    od_vol = jnp.maximum(over - spot_vol, 0.0)
    cost = cost_committed + od_rate * od_vol + sr * spot_vol

    shape = f.shape[:-1] + (k,)
    return PortfolioPlan(
        levels=jnp.broadcast_to(heights, shape),
        widths=jnp.broadcast_to(widths, shape),
        total=total,
        cost=cost,
        spot_floor=jnp.maximum(floor, total),
        spot_frac=spot_vol / jnp.maximum(total_vol, 1e-9),
    )


def portfolio_cost(
    f: jnp.ndarray,
    levels: jnp.ndarray,
    alphas: jnp.ndarray,
    betas: jnp.ndarray,
    *,
    od_rate: float = 2.1,
) -> jnp.ndarray:
    """Cost-line objective of an arbitrary monotone stack. f (..., T),
    levels (..., K) nondecreasing band tops *in stack order* (option k
    covers the band (levels[k-1], levels[k]]).  The brute-force/test
    oracle — reduces to ``commitment.commitment_cost`` at K=1, alpha=0."""
    prev = jnp.concatenate(
        [jnp.zeros_like(levels[..., :1]), levels[..., :-1]], axis=-1
    )
    fexp = f[..., None, :]                               # (..., 1, T)
    top = levels[..., :, None]
    bot = prev[..., :, None]
    used = jnp.clip(jnp.minimum(fexp, top) - bot, 0.0, None).sum(-1)
    width = levels - prev
    unused = width * f.shape[-1] - used
    over = jnp.maximum(f - levels[..., -1:], 0.0).sum(-1)
    return (alphas * used + betas * unused).sum(-1) + od_rate * over


def optimal_portfolio_grid(
    f: jnp.ndarray,
    alphas: jnp.ndarray,
    betas: jnp.ndarray,
    *,
    od_rate: float = 2.1,
    num_grid: int = 256,
    use_kernel: bool = False,
    weights: jnp.ndarray | None = None,
    spot_rate: jnp.ndarray | float | None = None,
    spot_cap: jnp.ndarray | float | None = None,
) -> PortfolioPlan:
    """Grid solver on the over/under sweep — the batched jit oracle.

    One sweep over candidate levels per pool yields exact per-cell
    used/idle integrals (d/dc of the over/under hinge sums), the envelope
    picks the best option per cell, thresholds land on cell edges
    (resolution span/num_grid).  With ``use_kernel`` the sweep runs through
    the Pallas 2-D kernel: P pools x G candidates in one HBM pass.

    ``alphas``/``betas`` may be (K,) shared lines or (P, K) per-pool lines
    (the ``pool_option_lines`` fleet shape).  ``weights`` (P, T) masks or
    reweights hours — a 0/1 prefix mask turns the sweep into Algorithm 1's
    per-horizon prefix solve (the rolling replanner batches its horizon
    prefixes through here; the idle integral of a masked-out hour is 0, so
    masked hours price nothing).

    ``spot_rate``/``spot_cap`` (scalars or (P,)) add the chance-constrained
    spot line (see ``optimal_portfolio_stack``): cells where spot undercuts
    the base winner flip to spot from the top down while their cumulative
    used-volume stays inside cap * total volume; the floor lands on a cell
    edge (same resolution as every other threshold)."""
    squeeze = f.ndim == 1
    if squeeze:
        f = f[None, :]
        if weights is not None and weights.ndim == 1:
            weights = weights[None, :]
    p, t = f.shape
    k = alphas.shape[-1]
    al = jnp.broadcast_to(jnp.atleast_2d(alphas), (p, k))
    be = jnp.broadcast_to(jnp.atleast_2d(betas), (p, k))
    w = jnp.ones_like(f) if weights is None else weights.astype(f.dtype)

    grid = jnp.linspace(0.0, 1.0, num_grid, dtype=jnp.float32)
    cs = f.max(-1, keepdims=True) * grid[None, :]        # (P, G) per-pool
    if use_kernel:
        from repro.kernels.commitment_sweep.ops import (
            commitment_sweep_over_under,
        )
        over, under = commitment_sweep_over_under(f, cs, w)
    else:
        from repro.kernels.commitment_sweep.ref import (
            commitment_sweep_over_under_ref,
        )
        over, under = commitment_sweep_over_under_ref(f, w, cs)

    used = over[:, :-1] - over[:, 1:]                    # (P, G-1) cell ints
    idle = under[:, 1:] - under[:, :-1]
    cell_cost = jnp.concatenate(
        [
            (od_rate * used)[:, None, :],
            al[:, :, None] * used[:, None, :]
            + be[:, :, None] * idle[:, None, :],
        ],
        axis=1,
    )  # (P, K+1, G-1); index 0 = on-demand (first wins ties)
    best = jnp.argmin(cell_cost, axis=1) - 1             # (P, G-1)

    spot_win = None
    if spot_rate is not None:
        sr = jnp.broadcast_to(jnp.asarray(spot_rate, jnp.float32), (p,))
        sc = jnp.broadcast_to(jnp.asarray(
            1.0 if spot_cap is None else spot_cap, jnp.float32
        ), (p,))
        base_cost = jnp.min(cell_cost, axis=1)           # (P, G-1)
        spot_cell = sr[:, None] * used
        elig = spot_cell < base_cost
        # Cumulative eligible volume at-or-above each cell; spot takes the
        # top cells whose running volume fits the chance-constraint cap.
        rev_cum = jnp.flip(jnp.cumsum(jnp.flip(elig * used, -1), -1), -1)
        total_vol = over[:, :1]                          # level 0 = all f
        spot_win = elig & (rev_cum <= sc[:, None] * total_vol)

    cells = jnp.arange(num_grid - 1)
    mask = best[:, None, :] == jnp.arange(k)[None, :, None]   # (P, K, G-1)
    if spot_win is not None:
        mask = mask & ~spot_win[:, None, :]
    has = mask.any(-1)
    hi = jnp.where(mask, cells[None, None, :], -1).max(-1)    # (P, K)
    lo = jnp.where(mask, cells[None, None, :], num_grid).min(-1)
    tops = jnp.take_along_axis(cs, jnp.maximum(hi + 1, 0), axis=-1)
    bottoms = jnp.take_along_axis(cs, jnp.clip(lo, 0, num_grid - 1), axis=-1)
    widths = jnp.where(has, tops - bottoms, 0.0)
    heights = _stack_heights(has, lo, widths, num_grid)

    spot_floor = spot_frac = None
    if spot_win is not None:
        cost = jnp.where(spot_win, spot_cell, base_cost).sum(-1)
        spot_vol = (spot_win * used).sum(-1)
        lo_spot = jnp.where(
            spot_win, cells[None, :], num_grid - 1
        ).min(-1, keepdims=True)
        spot_floor = jnp.take_along_axis(cs, lo_spot, axis=-1)[:, 0]
        spot_floor = jnp.maximum(spot_floor, widths.sum(-1))
        spot_frac = spot_vol / jnp.maximum(total_vol[:, 0], 1e-9)
    else:
        cost = jnp.min(cell_cost, axis=1).sum(-1)

    plan = PortfolioPlan(
        levels=heights, widths=widths, total=widths.sum(-1), cost=cost,
        spot_floor=spot_floor, spot_frac=spot_frac,
    )
    if squeeze:
        plan = PortfolioPlan(
            levels=plan.levels[0], widths=plan.widths[0],
            total=plan.total[0], cost=plan.cost[0],
            spot_floor=None if spot_floor is None else plan.spot_floor[0],
            spot_frac=None if spot_frac is None else plan.spot_frac[0],
        )
    return plan


@functools.partial(jax.jit, static_argnames=("od_rate", "resolution"))
def handover_fractiles(
    alphas: jnp.ndarray,
    betas: jnp.ndarray,
    *,
    od_rate: float = 2.1,
    resolution: int = 4096,
) -> jnp.ndarray:
    """(K,) utilization fractile u*_k where option k hands over to the next
    envelope occupant; 0.0 marks options off the envelope (zero width).
    These are the per-option critical fractiles: the optimal threshold of
    option k on ANY demand curve is its weighted u*_k-quantile — what the
    horizon planner evaluates on forecast prefixes.

    Like :func:`_band_assignment`, a running minimum over the K lines
    keeps every temporary at (resolution,), and compiling the whole loop
    as one program keeps them out of device memory.  The replay vmaps this
    over its rows, whose cost lines are not sharded: the (resolution, K)
    table was (R, 4096, 16), 8.6 GB on one chip at R=32768, and the loop
    run op by op left that chip 6.75 GB above the other three."""
    u = jnp.linspace(0.0, 1.0, resolution)
    best_cost = od_rate * (1.0 - u)
    best = jnp.full(u.shape, -1, jnp.int32)              # -1 = od
    for k in range(alphas.shape[0]):
        cost = alphas[k] * (1.0 - u) + betas[k] * u
        better = cost < best_cost
        best = jnp.where(better, k, best)
        best_cost = jnp.where(better, cost, best_cost)
    hi = jnp.stack([
        jnp.where(best == k, u, -1.0).max() for k in range(alphas.shape[0])
    ])                                                   # (K,)
    return jnp.where(hi >= 0, hi, 0.0)


@dataclasses.dataclass
class PortfolioSpend:
    """Real-dollar accounting of a stack over an evaluation window.

    ``spot`` is the expected-rate bill of the demand above the spot floor
    (0.0 on spot-free plans); ``spot_chip_hours`` the volume that rode
    spot."""

    committed: np.ndarray         # (K,) committed spend per option
    on_demand: float
    total: float
    all_on_demand: float
    savings_vs_on_demand: float
    spot: float = 0.0
    spot_chip_hours: float = 0.0


def portfolio_spend(
    f: jnp.ndarray,
    widths: jnp.ndarray,
    options: Sequence[PurchaseOption],
    *,
    od_rate: float = 2.1,
    spot_rate: float | None = None,
    spot_floor: float | None = None,
    level_offset: float = 0.0,
) -> PortfolioSpend:
    """In-window dollars: every active tranche bills its committed rate for
    all hours; demand above the stack pays on-demand — except, with a spot
    band (``spot_rate``/``spot_floor``), demand above the floor bills at
    the effective spot rate instead.

    ``level_offset`` lifts the effective serving level above the pool's
    own stack without billing here — the convertible allocation a
    cloud-level tranche re-pins onto this pool (its committed rate bills
    at cloud level, in the caller's accounting)."""
    t = f.shape[-1]
    rates = np.asarray([o.rate for o in options])
    w = np.asarray(widths)
    committed = rates * w * t
    total_level = float(w.sum()) + float(level_offset)
    over = float(jnp.maximum(f - total_level, 0.0).sum())
    spot_vol = 0.0
    spot_cost = 0.0
    if spot_rate is not None:
        floor = max(float(spot_floor), total_level)
        spot_vol = float(jnp.maximum(f - floor, 0.0).sum())
        spot_cost = float(spot_rate) * spot_vol
        over = max(over - spot_vol, 0.0)
    od = od_rate * over
    all_od = od_rate * float(f.sum())
    total = float(committed.sum()) + od + spot_cost
    return PortfolioSpend(
        committed=committed,
        on_demand=od,
        total=total,
        all_on_demand=all_od,
        # A pool can sit empty over the window (e.g. its training job ended):
        # no demand means nothing to save on.
        savings_vs_on_demand=1.0 - total / all_od if all_od > 0 else 0.0,
        spot=spot_cost,
        spot_chip_hours=spot_vol,
    )
