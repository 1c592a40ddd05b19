"""Seeded demand for the benchmark's fleets, made on the device in one
jitted call.

The arithmetic is the program's synthetic generator (``traces.
synthetic_pool_set`` / ``demand.synth_demand``; for a turnover fleet the
transfer of ``generations.migrate_pool_set``), copied here so the
yardstick cannot move with the program, and vectorised over pools:

    y_p(t) = base_p (1 + growth_p)^(t / 8760)
             x (1 + diurnal_p cos(2 pi (hod - 15) / 24))
             x (1 + weekly_p (0.4 - weekend))
             x (1 - holiday_drop [Dec 24 .. Jan 1])
             x (1 + ar_p(t)),   ar_p(t) = 0.95 ar_p(t-1) + noise eps_p(t)

A turnover fleet comes in (old family, successor) pool pairs: the old
pool carries the pair's demand, and from it the successor takes the
logistic share s_g(t) = sigmoid(rate_g (t - midpoint_g)) at 1/(1 +
uplift_g) VMs per old VM, while every pool shrinks by the software
deflator (1 + sw)^(-t / 8760).

Rows come out in sorted key order, as ``PoolSet.from_dict`` sorts them.
Each plan of a run then scales every pool by its own lognormal factor,
drawn from (seed, plan index), so no two plans of a run see the same
demand.
"""

from __future__ import annotations

import math

import numpy as np

HOURS_PER_DAY = 24
HOURS_PER_WEEK = 168
DAYS_PER_YEAR = 365
HOURS_PER_YEAR = HOURS_PER_DAY * DAYS_PER_YEAR
CLOUDS = ("aws", "azure", "gcp")
LOGISTIC_1090 = 2.0 * math.log(9.0)


def synthetic_pools(num_pools: int):
    """Keys and per-pool (base, growth, diurnal, weekly) of the synthetic
    artifact fleet (``traces._pool_configs``)."""
    keys, params = [], []
    for i in range(num_pools):
        keys.append((CLOUDS[i % 3], f"region_{i % 4}", f"type_{i:02d}"))
        params.append((40.0 * 1.5 ** (i % 4), 0.35 + 0.1 * (i % 5),
                       0.10 + 0.02 * (i % 3), 0.12 + 0.02 * (i % 4)))
    return keys, params


def turnover_pools(num_pools: int, generations: list[dict]):
    """Keys, per-pool parameters and the (old, new) pairs of a turnover
    fleet (``traces._turnover_pool_configs``): pairs cycle through the
    successor table, one region per pass; successors start empty."""
    if num_pools < 2 or num_pools % 2:
        raise ValueError(f"a turnover fleet needs an even pool count, "
                         f"got {num_pools}")
    keys, params, pairs = [], [], []
    for i in range(num_pools // 2):
        g = generations[i % len(generations)]
        region = f"region_{i // len(generations)}"
        keys.append((g["cloud"], region, g["old_family"]))
        params.append((60.0 * 1.5 ** (i % 3), 0.35 + 0.1 * (i % 4),
                       0.10 + 0.02 * (i % 3), 0.12 + 0.02 * (i % 4)))
        keys.append((g["cloud"], region, g["new_family"]))
        params.append((0.0, 0.0, 0.0, 0.0))
        pairs.append((len(keys) - 2, len(keys) - 1, g))
    return keys, params, pairs


def _key(seed: int):
    import jax

    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def fleet(cfg: dict, seed: int):
    """(keys, demand (P, T) float32 host array) of ``cfg["fleet"]``."""
    import jax
    import jax.numpy as jnp

    spec = cfg["fleet"]
    model = cfg["demand_model"]
    num_hours = spec["weeks"] * HOURS_PER_WEEK
    if spec["kind"] == "synthetic":
        keys, params = synthetic_pools(spec["num_pools"])
        pairs = []
    elif spec["kind"] == "turnover":
        keys, params, pairs = turnover_pools(
            spec["num_pools"], cfg["pricing"]["generations"])
    else:
        raise ValueError(f"unknown fleet kind {spec['kind']!r}")
    par = np.asarray(params, np.float32)                        # (P, 4)
    sw = float(model["software_efficiency_per_year"]) if pairs else 0.0
    src = np.asarray([p[0] for p in pairs], np.int32)
    dst = np.asarray([p[1] for p in pairs], np.int32)
    gen = [p[2] for p in pairs]
    mid = np.asarray([(g["launch_week"] + 0.5 * g["span_weeks"])
                      * HOURS_PER_WEEK for g in gen], np.float32)
    rate = np.asarray([LOGISTIC_1090 / (g["span_weeks"] * HOURS_PER_WEEK)
                       for g in gen], np.float32)
    inv_gain = np.asarray([1.0 / (1.0 + g["perf_uplift"]) for g in gen],
                          np.float32)

    def make(key):
        t = jnp.arange(num_hours, dtype=jnp.float32)
        base, growth, diurnal, weekly = (par[:, i:i + 1] for i in range(4))
        trend = base * jnp.power(1.0 + growth, t / HOURS_PER_YEAR)
        hod = jnp.mod(t, HOURS_PER_DAY)
        dow = jnp.mod(t // HOURS_PER_DAY, 7)
        prof = (1.0 + diurnal * jnp.cos(2.0 * jnp.pi * (hod - 15.0)
                                        / HOURS_PER_DAY))
        prof = prof * (1.0 + weekly * (0.4 - (dow >= 5)))
        doy = jnp.mod(t // HOURS_PER_DAY, DAYS_PER_YEAR)
        start = model["holiday_start_day"]
        hol = (doy >= start) & (doy < start + model["holiday_len_days"])
        y = trend * prof * (1.0 - model["holiday_drop"] * hol)
        eps = jax.random.normal(key, y.shape, jnp.float32)
        u = model["noise_sigma"] * eps
        # ar(t) = 0.95 ar(t-1) + u(t): a linear recurrence, associative in
        # (a, b) -> x = a x_prev + b.
        a = jnp.full_like(u, model["ar_coef"])
        _, ar = jax.lax.associative_scan(
            lambda l, r: (l[0] * r[0], r[0] * l[1] + r[1]), (a, u), axis=1)
        y = jnp.maximum(y * (1.0 + ar), 0.0)
        if pairs:
            share = jax.nn.sigmoid(rate[:, None] * (t[None, :] - mid[:, None]))
            moved = y[src] * share
            y = y.at[src].add(-moved).at[dst].add(moved * inv_gain[:, None])
            y = y * jnp.exp(-math.log1p(sw) / HOURS_PER_YEAR * t)
        return y

    demand = np.asarray(jax.jit(make)(_key(seed)))
    order = sorted(range(len(keys)), key=lambda i: keys[i])
    return tuple(keys[i] for i in order), np.ascontiguousarray(demand[order])


def plan_scales(seed: int, plan: int, num_pools: int, sigma: float):
    """(P,) per-pool demand multipliers of plan ``plan`` of a run."""
    rng = np.random.default_rng([seed, plan, 0x5CA1E])
    return rng.lognormal(0.0, sigma, num_pools).astype(np.float32)


def scenario_seed(seed: int, plan: int) -> int:
    """The scenario generator's seed for plan ``plan`` of a run."""
    return int(np.random.default_rng([seed, plan, 0x5CE9]).integers(2**31))
