"""Adversarial policy tournament: competitive ratio + regret, vmapped.

The paper scores one strategy on one realized trace; this rig scores
every :mod:`repro.core.policy` policy across the §2 workload taxonomy
(``repro.data.scenarios``): for each policy, ONE compiled program runs
the weekly replay over every (family x seed) demand path at once —
``jit(vmap(path_cost))`` over the stacked (F*N, P, T) batch — and the
per-path hindsight-optimal constant stack (the same reference
``replan_fleet_pools`` reports) is computed once in its own vmapped
program and shared by all policies.

Reported per (policy, family, seed):

    competitive ratio   realized cost / hindsight-optimal cost  (>= 1)
    regret              realized cost - hindsight-optimal cost

so the hedging policies' classical guarantees (<= 2 deterministic,
<= e/(e-1) randomized, Ambati et al. arXiv 2004.04302) become *testable
distributions* instead of citations, and every future policy change has
a scoreboard: tests pin the deterministic bound on steady fleets and the
rolling planner's margin over both hedges on the declining fleet.

Each path runs the planner's own weekly step (``replan._replay_step``,
built by ``replan._replay_inputs``) with no spot / migration /
convertible band — those key on the forecasting planner's weekly yhat:
roll off expired tranches, let the policy decide, buy increments on
decision weeks, bill committed rates plus on-demand overflow.  The
hindsight reference is ``portfolio.hindsight_stack``, the planner's
own.  ``backend="loop"`` replays the same step as a Python loop with
the direct prefix solve (``replan``'s loop backend).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Literal, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.capacity import pricing
from repro.core import forecast as fc
from repro.core import policy as pol
from repro.core import portfolio as pf
from repro.core import replan as rp
from repro.core.demand import HOURS_PER_WEEK
from repro.data import scenarios as sc
from repro.launch import mesh as mesh_mod
from repro.obs import spans as obs_spans

pricing.validate_tables()

DEFAULT_POLICIES = (
    "rolling_portfolio", "one_shot", "deterministic_hedge",
    "randomized_hedge",
)


@dataclasses.dataclass
class TournamentReport:
    """Per-(policy, family, seed) outcome grid plus summaries."""

    policies: tuple[str, ...]
    families: tuple[str, ...]
    num_seeds: int
    start_weeks: int
    cadence_weeks: int
    horizon_weeks: int
    cost: np.ndarray               # (Pol, F, N) realized replay cost
    hindsight_cost: np.ndarray     # (F, N) per-path hindsight optimum
    competitive_ratio: np.ndarray  # (Pol, F, N) cost / hindsight
    regret: np.ndarray             # (Pol, F, N) cost - hindsight
    #: wall time, stamped by callers (benchmarks/examples) — core modules
    #: are wall-clock-free by contract (analysis rule R2)
    elapsed_s: float = 0.0

    def family_stats(self, policy: str, family: str) -> dict:
        i = self.policies.index(policy)
        j = self.families.index(family)
        cr, rg = self.competitive_ratio[i, j], self.regret[i, j]
        return {
            "cr_mean": float(cr.mean()),
            "cr_p95": float(np.quantile(cr, 0.95)),
            "cr_max": float(cr.max()),
            "regret_mean": float(rg.mean()),
            "regret_max": float(rg.max()),
        }

    def summary(self) -> dict:
        return {
            p: {f: self.family_stats(p, f) for f in self.families}
            for p in self.policies
        }

    def to_markdown(self) -> str:
        """Mean competitive ratio per policy x family, one screen."""
        head = "| policy | " + " | ".join(self.families) + " |"
        sep = "|---" * (len(self.families) + 1) + "|"
        rows = [head, sep]
        for i, p in enumerate(self.policies):
            cells = " | ".join(
                f"{self.competitive_ratio[i, j].mean():.3f}"
                for j in range(len(self.families))
            )
            rows.append(f"| {p} | {cells} |")
        return "\n".join(rows)


def _path_cost(policy: pol.Policy, demand, *, options, clouds, od, cfg,
               start_weeks, cadence_weeks, horizon_weeks, backend):
    """Total replay cost of ``policy`` on one (P, T) demand path, run
    through the planner's own weekly step (``replan._replay_step``):
    commitments only, quantile solver, billing lines."""
    spec, arrays = rp._replay_inputs(
        demand, options, clouds, od=od, term_weighting=0.0, cfg=cfg,
        start_weeks=start_weeks, horizon_weeks=horizon_weeks,
        cadence_weeks=cadence_weeks, policy=policy,
    )
    num_p, num_k = demand.shape[0], len(options)
    zeros = (
        jnp.zeros((num_p, num_k), jnp.float32),
        jnp.zeros((num_p, num_k, spec.sched_len), jnp.float32),
    )
    if backend == "scan":
        step, carry0 = rp._replay_step(spec, arrays, zeros, fc.solve_prefix)
        ys = jax.lax.scan(
            step, carry0, jnp.arange(start_weeks, spec.total_weeks)
        )[1]
    else:
        ys = rp._replay_loop(
            spec, arrays, zeros, range(start_weeks, spec.total_weeks)
        )
    return ys["committed"].sum() + ys["od"].sum()


def _hindsight_cost(demand, *, options, clouds, od, start_weeks):
    """Per-path hindsight optimum: the hindsight stack
    (``portfolio.hindsight_stack``, the reference ``replan_fleet_pools``
    reports regret against) billed over the evaluation weeks."""
    total_weeks = demand.shape[-1] // HOURS_PER_WEEK
    ev = demand[:, start_weeks * HOURS_PER_WEEK: total_weeks * HOURS_PER_WEEK]
    widths = pf.hindsight_stack(ev, options, clouds, od_rate=od)
    rates = jnp.asarray([o.rate for o in options], jnp.float32)
    level = widths.sum(-1)
    over = jnp.maximum(ev - level[:, None], 0.0).sum(-1)
    committed = (
        (rates * widths).sum(-1)
        * (total_weeks - start_weeks) * HOURS_PER_WEEK
    )
    return committed.sum() + od * over.sum()


def run_tournament(
    policies: Sequence["pol.Policy | str"] = DEFAULT_POLICIES,
    families: Sequence[str] = sc.FAMILIES,
    *,
    num_pools: int = 3,
    num_weeks: int = 48,
    num_seeds: int = 32,
    base_seed: int = 0,
    start_weeks: int = 20,
    cadence_weeks: int = 2,
    horizon_weeks: int = 8,
    options: list | None = None,
    od_rate: float | None = None,
    cfg: fc.ForecastConfig = fc.ForecastConfig(),
    backend: Literal["scan", "loop"] = "scan",
    spans: "obs_spans.SpanRecorder | None" = None,
) -> TournamentReport:
    """Run the policy tournament: ONE compiled replay program per policy
    over every (family x seed) path, scored against per-path hindsight.

    Paths come from :func:`repro.data.scenarios.scenario_paths` (N >= 32
    seeds per family by default); clouds cycle aws/azure/gcp exactly as
    the synthetic artifact's pools do, so the Table-2 purchase options
    apply unchanged.

    ``spans`` (a :class:`repro.obs.spans.SpanRecorder`) brackets the
    hindsight pass and each policy's compiled replay with caller-side
    wall-clock spans; the clock read stays in ``repro.obs.spans``, so the
    tournament core itself remains clock-free (rules R2/R7) and
    ``spans=None`` does no timing work at all."""
    resolved = [pol.get_policy(p) for p in policies]
    families = tuple(families)
    options = options if options is not None else pf.options_from_pricing()
    od = od_rate if od_rate is not None else pricing.on_demand_premium()
    clouds = tuple(c for c, _, _ in sc.scenario_keys(num_pools))

    paths = np.stack([
        sc.scenario_paths(
            f, num_pools=num_pools, num_weeks=num_weeks,
            num_seeds=num_seeds, base_seed=base_seed,
        )
        for f in families
    ])                                      # (F, N, P, T)
    num_f = len(families)
    # Shard the (F*N) path axis across local devices when available (no-op
    # on one device): the vmapped replays are embarrassingly parallel per
    # path, so placing the batch once shards every policy's program.
    flat = mesh_mod.shard_rows(jnp.asarray(
        paths.reshape(num_f * num_seeds, num_pools, -1), jnp.float32
    ))

    replay = functools.partial(
        _path_cost, options=options, clouds=clouds, od=od, cfg=cfg,
        start_weeks=start_weeks, cadence_weeks=cadence_weeks,
        horizon_weeks=horizon_weeks, backend=backend,
    )

    with obs_spans.span(spans, "tournament/hindsight", phase="execute"):
        hs = jax.jit(jax.vmap(
            lambda d: _hindsight_cost(
                d, options=options, clouds=clouds, od=od,
                start_weeks=start_weeks,
            )
        ))(flat)
        hindsight = np.asarray(hs, np.float64).reshape(num_f, num_seeds)

    cost = np.empty((len(resolved), num_f, num_seeds), np.float64)
    for i, policy in enumerate(resolved):
        # One compiled program per policy: the vmap batches every
        # family's every seed through the same replay.  Each path sets the
        # policy up over its own P rows (the randomized hedge draws its
        # thresholds per path), so paths stay the vmap axis rather than
        # joining the row axis.
        with obs_spans.span(
            spans, f"tournament/{policy.name}", phase="execute"
        ):
            totals = jax.jit(jax.vmap(functools.partial(replay, policy)))(
                flat
            )
            cost[i] = np.asarray(totals, np.float64).reshape(
                num_f, num_seeds
            )

    return TournamentReport(
        policies=tuple(p.name for p in resolved),
        families=families,
        num_seeds=num_seeds,
        start_weeks=start_weeks,
        cadence_weeks=cadence_weeks,
        horizon_weeks=horizon_weeks,
        cost=cost,
        hindsight_cost=hindsight,
        competitive_ratio=cost / hindsight[None],
        regret=cost - hindsight[None],
    )
