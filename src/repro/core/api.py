"""Unified planner request API: one frozen :class:`PlanRequest` in, one
plan out.

The planner surface grew band by band — spot, migration, convertibles,
policies, scenario batching — and with it ``plan_fleet_pools`` grew a
kwarg soup whose rolling-mode knobs were invisible ``**rolling_kw``
pass-throughs.  This module is the redesigned front door:

    request = PlanRequest(
        pools=pools,
        mode="rolling",
        rolling=RollingConfig(cadence_weeks=2, start_weeks=26),
        spot=True,
        scenarios=ScenarioConfig(n_scenarios=32, family="regime"),
    )
    report = plan(request)

Everything is validated eagerly in ``__post_init__`` — an unknown policy
name, a bool where a config belongs, or a rolling-only knob on a one-shot
request fails at *construction*, not three bands deep into a jitted replay.
The legacy ``plan_fleet_pools(pools, mode=..., cadence_weeks=...)``
spelling still works: it is now a thin shim that builds the equivalent
``PlanRequest`` (emitting a ``DeprecationWarning`` for loose rolling
kwargs) and calls :func:`plan`, so both spellings are bit-identical by
construction — and golden-tested to stay that way.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Literal

from repro.core import forecast as fc
from repro.core import policy as pol
from repro.data.scenarios import ScenarioConfig, resolve_scenarios
from repro.obs import spans as obs_spans
from repro.obs.config import TelemetryConfig, resolve_telemetry

__all__ = [
    "PlanRequest",
    "RollingConfig",
    "ScenarioConfig",
    "TelemetryConfig",
    "plan",
]

_SOLVERS = ("quantile", "grid")
_BACKENDS = ("scan", "loop")
_MODES = ("one_shot", "rolling")


@dataclasses.dataclass(frozen=True)
class RollingConfig:
    """Rolling-replay knobs of a :class:`PlanRequest` (``mode="rolling"``).

    The defaults reproduce ``replan_fleet_pools``'s defaults exactly; see
    :func:`repro.core.replan.replan_fleet_pools` for the semantics of each
    field.  ``irls_carry`` makes ``irls_iters > 0`` cheap per replayed
    week by carrying reweighted normal-equation moments in the scan state
    instead of re-running the masked design pass."""

    cadence_weeks: int = 1
    start_weeks: int | None = None
    solver: Literal["quantile", "grid"] = "quantile"
    num_grid: int = 128
    use_kernel: bool = False
    irls_iters: int = 0
    irls_carry: bool = False
    backend: Literal["scan", "loop"] = "scan"
    compare: bool = True
    #: "weekly" re-solves on the ``cadence_weeks`` grid (the default,
    #: bit-identical to pre-cadence builds); "breach" re-solves only in
    #: weeks where last week's realized demand exited the forecast band
    #: held since the previous decision (forecasting policies only).
    cadence: Literal["weekly", "breach"] = "weekly"
    #: (q_lo, q_hi) forecast fractiles framing the breach band.
    breach_band: tuple = (0.05, 0.95)
    #: a week breaches when more than ``tolerance x nominal miss mass``
    #: of its 168 hours exit the band (exact integer hour budget).
    breach_tolerance: float = 4.0

    def __post_init__(self):
        if self.cadence_weeks < 1:
            raise ValueError(
                f"cadence_weeks must be >= 1, got {self.cadence_weeks}"
            )
        if self.cadence not in ("weekly", "breach"):
            raise ValueError(
                f"unknown cadence {self.cadence!r}; "
                "known: ('weekly', 'breach')"
            )
        if self.cadence == "breach" and self.cadence_weeks != 1:
            raise ValueError(
                "cadence='breach' evaluates every week and masks "
                "decisions itself; combine it with cadence_weeks=1, "
                f"got cadence_weeks={self.cadence_weeks}"
            )
        if len(self.breach_band) != 2:
            raise ValueError(
                f"breach_band must be a (lo, hi) pair, got {self.breach_band}"
            )
        lo, hi = self.breach_band
        if not 0.0 < lo < hi < 1.0:
            raise ValueError(
                "breach_band must be an increasing fractile pair inside "
                f"(0, 1), got {self.breach_band}"
            )
        if self.breach_tolerance <= 0.0:
            raise ValueError(
                f"breach_tolerance must be > 0, got {self.breach_tolerance}"
            )
        if self.start_weeks is not None and self.start_weeks < 1:
            raise ValueError(
                f"start_weeks must be >= 1 or None, got {self.start_weeks}"
            )
        if self.solver not in _SOLVERS:
            raise ValueError(
                f"unknown solver {self.solver!r}; known: {_SOLVERS}"
            )
        if self.num_grid < 2:
            raise ValueError(f"num_grid must be >= 2, got {self.num_grid}")
        if self.irls_iters < 0:
            raise ValueError(
                f"irls_iters must be >= 0, got {self.irls_iters}"
            )
        if self.backend not in _BACKENDS:
            raise ValueError(
                f"unknown backend {self.backend!r}; known: {_BACKENDS}"
            )


@dataclasses.dataclass(frozen=True)
class PlanRequest:
    """One planner invocation, fully specified and eagerly validated.

    ``pools`` carries the (P, T) demand; the optional band configs nest
    their own dataclasses (:class:`repro.core.spot.SpotConfig`,
    :class:`repro.capacity.generations.MigrationConfig`, convertible
    purchase options, a :class:`repro.core.policy.Policy` or registry
    name, a :class:`repro.data.scenarios.ScenarioConfig`), each keeping
    the ``True``/int conveniences of the kwarg spelling.  Rolling-only
    knobs live in ``rolling``; setting them on a one-shot request is a
    construction-time error rather than a silently ignored kwarg."""

    pools: Any
    options: list | None = None
    mode: Literal["one_shot", "rolling"] = "one_shot"
    horizon_weeks: int = 8
    od_rate: float | None = None
    term_weighting: float = 0.0
    forecast: fc.ForecastConfig = dataclasses.field(
        default_factory=fc.ForecastConfig
    )
    spot: Any = None            # SpotConfig | bool | None
    migration: Any = None       # MigrationConfig | bool | None
    convertible: Any = None     # list[PurchaseOption] | bool | None
    policy: Any = None          # Policy | str | None
    scenarios: "ScenarioConfig | int | None" = None
    telemetry: "TelemetryConfig | bool | None" = None
    rolling: RollingConfig = dataclasses.field(default_factory=RollingConfig)

    def __post_init__(self):
        from repro.capacity import generations as gn
        from repro.core import spot as spot_mod

        if self.mode not in _MODES:
            raise ValueError(
                f"unknown mode {self.mode!r}; known: {_MODES}"
            )
        if self.horizon_weeks < 1:
            raise ValueError(
                f"horizon_weeks must be >= 1, got {self.horizon_weeks}"
            )
        if not isinstance(self.rolling, RollingConfig):
            raise TypeError(
                "rolling= takes a RollingConfig, got "
                f"{type(self.rolling).__name__}"
            )
        if not isinstance(self.forecast, fc.ForecastConfig):
            raise TypeError(
                "forecast= takes a ForecastConfig, got "
                f"{type(self.forecast).__name__}"
            )
        # Band configs: run each resolver once so malformed specs fail
        # here (the planner re-resolves identically — both are pure).
        if self.spot is not None and not isinstance(self.spot, bool):
            if not isinstance(self.spot, spot_mod.SpotConfig):
                raise TypeError(
                    "spot= takes a SpotConfig, bool, or None, got "
                    f"{type(self.spot).__name__}"
                )
        gn.resolve_migration(self.migration)
        if isinstance(self.policy, str) and self.policy not in pol.POLICIES:
            raise ValueError(
                f"unknown policy {self.policy!r}; "
                f"known: {tuple(pol.POLICIES)}"
            )
        resolve_scenarios(self.scenarios)
        resolve_telemetry(self.telemetry)
        if self.mode == "one_shot":
            if self.policy is not None:
                raise ValueError("policy= applies to mode='rolling' only")
            if self.scenarios is not None:
                raise ValueError(
                    "scenarios= applies to mode='rolling' only"
                )
            if resolve_telemetry(self.telemetry) is not None:
                raise ValueError(
                    "telemetry= applies to mode='rolling' only (the "
                    "ledger decomposes the weekly replay)"
                )
            if self.rolling != RollingConfig():
                raise ValueError(
                    "rolling= knobs were set on a mode='one_shot' request"
                )

    def rolling_kwargs(self) -> dict:
        """The ``replan_fleet_pools`` keyword spelling of ``rolling`` —
        the single source of truth the legacy shim and :func:`plan` share."""
        return dataclasses.asdict(self.rolling)


def plan(request: PlanRequest):
    """Canonical planner entry: execute one :class:`PlanRequest`.

    Returns :class:`repro.core.planner.FleetPoolsPlan` for one-shot
    requests and :class:`repro.core.replan.RollingPlanReport` for rolling
    ones — exactly what the legacy ``plan_fleet_pools`` spelling returns
    for the same configuration (golden-tested bit-identical)."""
    if not isinstance(request, PlanRequest):
        raise TypeError(
            f"plan() takes a PlanRequest, got {type(request).__name__}"
        )
    # Late import: planner -> replan -> policy all import at module scope;
    # api sits in front of them without joining the cycle.
    from repro.core import planner

    common = dict(
        horizon_weeks=request.horizon_weeks,
        od_rate=request.od_rate,
        term_weighting=request.term_weighting,
        cfg=request.forecast,
        spot=request.spot,
        migration=request.migration,
        convertible=request.convertible,
    )
    if request.mode == "one_shot":
        return planner._plan_fleet_pools_one_shot(
            request.pools, request.options, **common
        )
    from repro.core import replan

    # The call as a stage of its own: the replay's locals (the host
    # scenario batch among them) are freed as it returns, after its
    # ``replan`` stage has closed.
    with obs_spans.stage("api/plan"):
        return replan.replan_fleet_pools(
            request.pools, request.options, **common,
            policy=request.policy, scenarios=request.scenarios,
            telemetry=request.telemetry,
            **request.rolling_kwargs(),
        )
