"""Adversarial scenario families for the policy tournament (§2 taxonomy).

The paper evaluates one strategy on one realized demand trace; the
tournament (``repro.core.tournament``) instead scores every policy across
the canonical workload taxonomy cloud cost planners are judged on —
steady, burst, cyclic, declining, unpredictable — with N seeded paths per
family, so competitive-ratio and regret numbers are *distributions*, not
anecdotes.

Each family reuses the ``synthetic_pool_set`` drivers: the same
:func:`repro.core.demand.synth_demand` trend x seasonality x AR(1) model
(clouds cycling aws/azure/gcp so pool keys line up with the Table-2
purchase options), then applies a family-specific transform with its own
seeded generator:

    steady         flat trend, mild seasonality, low noise
    burst          steady base + rare short multiplicative spikes
    cyclic         strong weekly + 4-week modulation on top
    declining      negative annual growth (a sunsetting fleet)
    unpredictable  regime-switching level shifts + heavy noise

Every family has a *defining property* the test-suite asserts per seed
(burst exceedance counts, cyclic lag-168 autocorrelation, declining
trend sign, ...), and every path is a pure function of
``(family, base_seed)`` — reproducibility is part of the contract.

The rolling replay's scenario futures (:func:`scenario_batch`, the
:data:`PERTURBATIONS` of the realized trace) are built in two steps: the
host makes each (scenario, pool) row's generator calls and keeps only
their few draws (:func:`host_draws`), and one compiled device module per
family turns the realized rows and those draws into the float32 futures,
on the device the replay scans them on.  Host callers take ``np.asarray``
of the same rows.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import demand as dm
from repro.core.demand import HOURS_PER_WEEK
from repro.launch import mesh as mesh_mod

FAMILIES: tuple[str, ...] = (
    "steady", "burst", "cyclic", "declining", "unpredictable",
)

#: Perturbation families for :func:`scenario_batch` — transforms of the
#: *realized* trace (scenario 0 is always the realized path verbatim):
#:
#:     realized   N identical copies of the realized trace (the batching
#:                identity: ``n_scenarios=1`` IS today's single-path replay)
#:     burst      rare short multiplicative spikes (the §2 burst transform)
#:     regime     piecewise-constant level shifts (the unpredictable
#:                transform) — demand migrates without warning
#:     growth     a seeded exponential drift ramp, up or down
#:     scale      one lognormal level multiplier per pool — "our forecast
#:                of absolute fleet size is off by x%"
PERTURBATIONS: tuple[str, ...] = (
    "realized", "burst", "regime", "growth", "scale",
)

# Growth/scale perturbation knobs (annualized drift range, level sigma).
GROWTH_RANGE = (-0.35, 0.45)
SCALE_SIGMA = 0.20

_CLOUDS = ("aws", "azure", "gcp")

# Burst family knobs (shared with the coverage tests).
BURST_EVERY_WEEKS = 4          # ~one spike per this many weeks
BURST_FACTOR = 3.0             # spike multiplier
BURST_LEN_HOURS = (6, 24)      # spike duration range
# Unpredictable family knobs.
REGIME_SEGMENTS = 5
REGIME_RANGE = (0.5, 1.8)


def _family_config(family: str, pool: int) -> dm.DemandConfig:
    """The per-pool driver config, varied across pools the same way
    ``traces._pool_configs`` varies the synthetic artifact."""
    base = 40.0 * (1.5 ** (pool % 3))
    if family == "steady":
        return dm.DemandConfig(
            base_level=base, annual_growth=0.0,
            diurnal_amplitude=0.10 + 0.02 * (pool % 3),
            weekly_amplitude=0.12 + 0.02 * (pool % 4),
            noise_sigma=0.04,
        )
    if family == "burst":
        return dm.DemandConfig(
            base_level=base, annual_growth=0.0,
            diurnal_amplitude=0.08, weekly_amplitude=0.10,
            noise_sigma=0.05,
        )
    if family == "cyclic":
        return dm.DemandConfig(
            base_level=base, annual_growth=0.0,
            diurnal_amplitude=0.35 + 0.05 * (pool % 2),
            weekly_amplitude=0.45 + 0.05 * (pool % 3),
            noise_sigma=0.05,
        )
    if family == "declining":
        return dm.DemandConfig(
            base_level=1.6 * base, annual_growth=-0.90,
            diurnal_amplitude=0.10, weekly_amplitude=0.12,
            noise_sigma=0.05,
        )
    if family == "unpredictable":
        return dm.DemandConfig(
            base_level=base, annual_growth=0.0,
            diurnal_amplitude=0.10, weekly_amplitude=0.12,
            noise_sigma=0.15,
        )
    raise ValueError(f"unknown family {family!r}; known: {FAMILIES}")


def _num_bursts(t: int) -> int:
    return max(1, t // (BURST_EVERY_WEEKS * HOURS_PER_WEEK))


def _burst_draws(rng: np.random.Generator, t: int):
    """Start hours and lengths of the bursts of one ``t``-hour series, in
    the order the generator draws them (length, then start, per burst)."""
    at, ln = [], []
    for _ in range(_num_bursts(t)):
        ln.append(int(rng.integers(*BURST_LEN_HOURS)))
        at.append(int(rng.integers(0, max(t - ln[-1], 1))))
    return at, ln


def _regime_draws(rng: np.random.Generator, t: int):
    """Sorted inner edges and per-segment multipliers of one series."""
    edges = np.sort(rng.integers(1, t, size=REGIME_SEGMENTS - 1))
    mult = rng.uniform(*REGIME_RANGE, size=REGIME_SEGMENTS)
    return edges, mult


def _transform(
    family: str, y: np.ndarray, rng: np.random.Generator
) -> np.ndarray:
    """Family-specific post-transform on one pool's hourly series."""
    t = y.shape[-1]
    if family == "burst":
        y = y.copy()
        for at, ln in zip(*_burst_draws(rng, t)):
            y[at:at + ln] *= BURST_FACTOR
        return y
    if family == "cyclic":
        # A 4-week business cycle on top of the weekly/diurnal pattern —
        # the autocorrelation structure the family is named for.
        phase = rng.uniform(0.0, 2.0 * np.pi)
        month = 1.0 + 0.3 * np.sin(
            2.0 * np.pi * np.arange(t) / (4 * HOURS_PER_WEEK) + phase
        )
        return y * month
    if family == "unpredictable":
        # Piecewise-constant regime multipliers: level shifts no
        # smooth structural fit anticipates.
        edges, mult = _regime_draws(rng, t)
        levels = np.repeat(
            mult, np.diff(np.concatenate([[0], edges, [t]]))
        )
        return y * levels
    return y


@dataclasses.dataclass(frozen=True)
class ScenarioConfig:
    """Scenario axis of the batched rolling replay (``scenarios=`` on
    ``replan_fleet_pools`` / ``PlanRequest.scenarios``).

    ``n_scenarios`` demand futures are derived from the realized trace by
    the ``family`` perturbation (:data:`PERTURBATIONS`); scenario 0 is
    always the realized path itself, so ladders and goldens anchor on it
    and ``n_scenarios=1`` with the default ``"realized"`` family is
    *bit-identical* to the unbatched replay.  ``chunk`` bounds how many
    scenarios one compiled replay program carries (memory relief on a
    single host; ``None`` runs all N in one program)."""

    n_scenarios: int = 1
    family: str = "realized"
    seed: int = 0
    chunk: int | None = None

    def __post_init__(self):
        if self.n_scenarios < 1:
            raise ValueError(
                f"n_scenarios must be >= 1, got {self.n_scenarios}"
            )
        if self.family not in PERTURBATIONS:
            raise ValueError(
                f"unknown scenario family {self.family!r}; "
                f"known: {PERTURBATIONS}"
            )
        if self.chunk is not None and self.chunk < 1:
            raise ValueError(f"chunk must be >= 1 or None, got {self.chunk}")


def resolve_scenarios(
    spec: "ScenarioConfig | int | None",
) -> "ScenarioConfig | None":
    """Normalize the ``scenarios=`` spelling: ``None`` stays off,
    an int means ``ScenarioConfig(n_scenarios=int)``."""
    if spec is None or isinstance(spec, ScenarioConfig):
        return spec
    if isinstance(spec, bool):
        raise TypeError("scenarios= takes an int or ScenarioConfig, not bool")
    if isinstance(spec, int):
        return ScenarioConfig(n_scenarios=spec)
    raise TypeError(
        f"scenarios= takes None, an int, or a ScenarioConfig, "
        f"got {type(spec).__name__}"
    )


# A perturbation family is three functions of one (s, p) row: ``shapes(t)``
# lists each draw's trailing shape and host dtype for a ``t``-hour batch;
# ``draw(rng, t)`` makes the row's generator calls and returns one value
# per entry of ``shapes``; ``rows(y, *draws)`` is the device's float32 map
# from the broadcast realized rows (n, P, T) and the stacked draws
# (n, P, *shape) to the family's futures.


def _growth_rows(y, g):
    t = jnp.arange(y.shape[-1], dtype=jnp.float32)
    return y * jnp.exp(g[..., None] * t / (52.0 * HOURS_PER_WEEK))


def _scale_rows(y, mult):
    return y * mult[..., None]


def _burst_rows(y, at, ln):
    # One select per burst in draw order, so overlapping bursts compound
    # through the same float32 products as the in-place host transform.
    t = jnp.arange(y.shape[-1], dtype=jnp.int32)
    for k in range(at.shape[-1]):
        a, n = at[..., k, None], ln[..., k, None]
        y = jnp.where((t >= a) & (t < a + n), y * BURST_FACTOR, y)
    return y


def _regime_rows(y, edges, mult):
    # Hours from the k-th sorted edge on take multiplier k + 1; the last
    # edge passed wins, so an empty segment (repeated edge) is skipped.
    t = jnp.arange(y.shape[-1], dtype=jnp.int32)
    level = mult[..., :1]
    for k in range(edges.shape[-1]):
        level = jnp.where(
            t >= edges[..., k, None], mult[..., k + 1, None], level
        )
    return y * level


_PERTURB = {
    "realized": (lambda t: (), lambda rng, t: (), lambda y: y),
    "burst": (
        lambda t: (((_num_bursts(t),), np.int64),) * 2,
        _burst_draws,
        _burst_rows,
    ),
    "regime": (
        lambda t: (((REGIME_SEGMENTS - 1,), np.int64),
                   ((REGIME_SEGMENTS,), np.float64)),
        _regime_draws,
        _regime_rows,
    ),
    "growth": (
        lambda t: (((), np.float64),),
        lambda rng, t: (rng.uniform(*GROWTH_RANGE),),
        _growth_rows,
    ),
    "scale": (
        lambda t: (((), np.float64),),
        lambda rng, t: (rng.lognormal(0.0, SCALE_SIGMA),),
        _scale_rows,
    ),
}


def _futures(rows, n, first, demand, *draws):
    """(n, P, T) futures from the realized ``demand`` (P, T) and the
    stacked draws; when ``first`` the batch opens at scenario 0, which
    stays the realized trace verbatim."""
    y = jnp.broadcast_to(demand, (n,) + demand.shape)
    keep = (jnp.arange(n) == 0) & first
    return jnp.where(keep[:, None, None], y, rows(y, *draws))


#: One compiled device module per family, cached on its shapes.
_FUTURES = {
    family: jax.jit(functools.partial(_futures, rows), static_argnums=(0, 1))
    for family, (_, _, rows) in _PERTURB.items()
}


def _scenario_range(cfg: ScenarioConfig, lo: int, hi: int | None):
    hi = cfg.n_scenarios if hi is None else hi
    if not 0 <= lo < hi <= cfg.n_scenarios:
        raise ValueError(
            f"scenarios [{lo}, {hi}) outside [0, {cfg.n_scenarios})"
        )
    return lo, hi


def host_draws(
    cfg: ScenarioConfig, num_pools: int, num_hours: int,
    lo: int = 0, hi: int | None = None,
) -> list[np.ndarray]:
    """The ``cfg.family`` draws of scenarios ``lo .. hi - 1`` of a
    ``num_pools`` x ``num_hours`` batch, one (hi - lo, P, *shape) array
    per draw.  Row (s, p) comes from the generator seeded on
    ``(1_000_003 x family index, cfg.seed, s, p)``; scenario 0 draws
    nothing (its entries are zero and unused)."""
    lo, hi = _scenario_range(cfg, lo, hi)
    shapes, draw, _ = _PERTURB[cfg.family]
    out = [np.zeros((hi - lo, num_pools) + shape, dtype)
           for shape, dtype in shapes(num_hours)]
    if not out:
        return out
    fam_idx = PERTURBATIONS.index(cfg.family)
    for s in range(max(lo, 1), hi):
        for p in range(num_pools):
            rng = np.random.default_rng(
                (1_000_003 * fam_idx, cfg.seed, s, p)
            )
            for arr, v in zip(out, draw(rng, num_hours)):
                arr[s - lo, p] = v
    return out


def placed_bytes(
    cfg: ScenarioConfig, num_pools: int, num_hours: int,
    lo: int = 0, hi: int | None = None,
) -> int:
    """Bytes :func:`scenario_batch` places on the device for scenarios
    ``lo .. hi - 1``: the realized rows and the draws, each in 4-byte
    words (float32 / int32)."""
    lo, hi = _scenario_range(cfg, lo, hi)
    per_row = sum(
        int(np.prod(shape)) for shape, _ in _PERTURB[cfg.family][0](num_hours)
    )
    return 4 * num_pools * (num_hours + (hi - lo) * per_row)


def scenario_batch(
    demand: np.ndarray, cfg: ScenarioConfig,
    lo: int = 0, hi: int | None = None,
) -> jax.Array:
    """(hi - lo, P, T) scenarios ``lo .. hi - 1`` (all N by default) of the
    batch derived from the realized ``demand`` (P, T), as a device array.

    Scenario 0 is the realized trace verbatim; scenarios ``s >= 1`` apply
    the ``cfg.family`` perturbation with a generator seeded on
    ``(family, cfg.seed, s, pool)`` — every batch is a pure function of
    (demand, cfg), reproducibility being part of the contract exactly as
    for :func:`scenario_path`, and a slice is the same rows as the whole
    batch's.  The generator calls run on the host (:func:`host_draws`);
    the realized rows and those draws are placed on the device, where one
    compiled module per family builds the rows in float32.  Host callers
    take ``np.asarray`` of the result: the very rows a replay scans."""
    demand = np.asarray(demand, np.float32)
    if demand.ndim != 2:
        raise ValueError(f"demand must be (P, T), got shape {demand.shape}")
    lo, hi = _scenario_range(cfg, lo, hi)
    draws = [
        a.astype(np.float32 if a.dtype.kind == "f" else np.int32)
        for a in host_draws(cfg, *demand.shape, lo, hi)
    ]
    # Draws shard over the scenario axis like the replay's rows, so on
    # several devices each builds its own scenarios' rows.
    return _FUTURES[cfg.family](
        hi - lo, lo == 0, jnp.asarray(demand),
        *(mesh_mod.shard_rows(a) for a in draws),
    )


def scenario_keys(num_pools: int) -> tuple[dm.PoolKey, ...]:
    """Pool keys for a scenario fleet, cloud-cycled like the artifact."""
    return tuple(
        (_CLOUDS[i % 3], f"region_{i % 4}", f"type_{i:02d}")
        for i in range(num_pools)
    )


def scenario_path(
    family: str,
    *,
    num_pools: int = 3,
    num_weeks: int = 40,
    seed: int = 0,
) -> np.ndarray:
    """One (P, T) demand path of ``family`` at ``seed``, T in whole weeks."""
    if family not in FAMILIES:
        raise ValueError(f"unknown family {family!r}; known: {FAMILIES}")
    num_hours = num_weeks * HOURS_PER_WEEK
    fam_idx = FAMILIES.index(family)
    rows = []
    for p in range(num_pools):
        cfg = _family_config(family, p)
        key = jax.random.PRNGKey(100_000 * fam_idx + 100 * seed + p)
        y = np.asarray(dm.synth_demand(num_hours, cfg, key=key))
        rng = np.random.default_rng((fam_idx, seed, p))
        rows.append(_transform(family, y, rng))
    return np.stack(rows).astype(np.float32)


def scenario_paths(
    family: str,
    *,
    num_pools: int = 3,
    num_weeks: int = 40,
    num_seeds: int = 32,
    base_seed: int = 0,
) -> np.ndarray:
    """(N, P, T) seeded paths of one family — the tournament's unit of
    coverage (N >= 32 by default so ratio/regret tails are populated)."""
    return np.stack([
        scenario_path(
            family, num_pools=num_pools, num_weeks=num_weeks,
            seed=base_seed + s,
        )
        for s in range(num_seeds)
    ])


def scenario_pool_set(
    family: str,
    *,
    num_pools: int = 3,
    num_weeks: int = 40,
    seed: int = 0,
) -> dm.PoolSet:
    """One scenario path wrapped as a :class:`~repro.core.demand.PoolSet`
    so the full planner surface (``plan_fleet_pools``) runs on it."""
    demand = scenario_path(
        family, num_pools=num_pools, num_weeks=num_weeks, seed=seed
    )
    return dm.PoolSet(
        keys=scenario_keys(num_pools),
        demand=demand,
        configs={
            k: _family_config(family, i)
            for i, k in enumerate(scenario_keys(num_pools))
        },
    )
