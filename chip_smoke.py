#!/usr/bin/env python3
"""Bring-up smoke run of the rolling planner on a TPU.

    python chip_smoke.py            # one chip: reference, kernel, scale
    python chip_smoke.py --chips 4  # four chips: the sharded scale replay

Drives the planner's main path, ``repro.core.api.plan(PlanRequest(
mode="rolling"))``, once per phase at sizes its users run, and checks
what comes out.  One chip:

1. device gate: no TPU, no run (there is no CPU fallback);
2. reference: the paper-shaped estate (12 pools, 3 years hourly, spot +
   migration + convertible bands, cost ledger on) on the chip and on the
   host CPU in this process — totals within ``CPU_GAP_RTOL``, the
   forecaster within ``FORECAST_RTOL`` of a float64 solve of the same
   normal equations, the ledger reconciled to its 1e-5 gate;
3. kernel: the grid solver with the Pallas sweep against its jnp
   reference, and proof that the sweep was compiled for the chip;
4. scale: ``bench_fleet_scale``'s full fleet, P=1024 pools x N scenarios
   x 156 weeks, with scenario 0 held to the unbatched replay.

``--chips 4`` runs only the scale replay with its rows sharded over four
chips, against the same scenarios with their rows on one chip.

The data comes from a seed and is made on the host.  Everything runs in
this one process, which holds the chip(s).  Times are one cold pass with
compilation included: a smoke run, not a benchmark.  The last line of
standard output is the JSON verdict; any failed check exits 1 without it.
A summary is also written to ``chiprun_out/chip_smoke[_4chips].json``.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))

#: Chip vs host-CPU gap allowed on the reference estate's rolling,
#: one-shot and hindsight totals.  The replay's purchases are discrete (a
#: tranche is bought or not), and the chip's transcendentals (the Fourier
#: design's sin/cos at arguments up to ~3e4 rad, exp, log) round
#: differently from the CPU's.  The same CPU code moves the 3-pool test
#: replays by up to 3.6% between two x86 machines; this 12-pool estate is
#: steadier (its totals shift by <1e-6 under 1e-6 input noise on the CPU).
#: 5e-3 is half the 1e-2 outer bound.
CPU_GAP_RTOL = 5e-3
#: Forecast at the first decision week vs a float64 solve of the same
#: normal equations.  Full float32 matmuls land ~1e-5 away; inputs rounded
#: to bfloat16 (the TPU's default matmul precision) land ~1e-2 away.
FORECAST_RTOL = 1e-3
#: Pallas sweep vs its jnp reference, as in tests/test_kernels.py.
SWEEP_RTOL = 2e-4
#: Scenario 0 of a batch vs the unbatched replay, where not bit-identical.
SCENARIO0_RTOL = 1e-6

#: The reference estate: the paper's setting (three clouds, per-(cloud,
#: region, family) pools with turnover, three years hourly, weekly
#: decisions, 8-week horizon).
REFERENCE = dict(num_pools=12, weeks=156, horizon_weeks=8)
#: ``bench_fleet_scale``'s full shape.  N=16 on one chip: it peaks at
#: 8.1 GB of a v5e's 16 GiB, and at N=32 the hindsight solve alone needs
#: 11.5 GB of temporaries next to demand and log-demand (3.4 GB each).
#: Four chips carry N=32 with the rows sharded.
SCALE = dict(num_pools=1024, weeks=156, start_weeks=26, horizon_weeks=8,
             family="growth")
SCALE_SCENARIOS = {1: 16, 4: 32}
#: Scenarios of the four-chip batch replayed with their rows on one chip
#: (the first four: enough rows to fill a chip's tiles, and the replay
#: stays short next to the sharded one).
ONE_CHIP_SCENARIOS = 4
HOURS_PER_WEEK = 168


class Checks:
    """Named pass/fail results; every phase runs, failures are listed."""

    def __init__(self):
        self.results: list[dict] = []

    def check(self, name: str, ok: bool, detail: str = "") -> bool:
        self.results.append({"check": name, "ok": bool(ok), "detail": detail})
        print(f"  [{'ok' if ok else 'FAIL'}] {name}: {detail}", flush=True)
        return bool(ok)

    @property
    def failed(self) -> list[str]:
        return [r["check"] for r in self.results if not r["ok"]]


class CompileClock:
    """Backend compile seconds and persistent-cache hits, from JAX's own
    monitoring events (the compile event includes cache reads)."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration_secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration_secs
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> dict:
        return {"compile_s": self.seconds, "compiles": self.compiles,
                "cache_hits": self.cache_hits}


def rel_gap(a, b) -> float:
    import numpy as np

    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-30)))


def peak_bytes(devices) -> list[int]:
    return [int(d.memory_stats()["peak_bytes_in_use"]) for d in devices]


@contextlib.contextmanager
def row_placement():
    """Within the block, records the rows each device holds of the arrays
    the replay makes: its scenario rows (``mesh.shard_rows``), the
    forecaster's log-demand (``forecast.prefix_fit_state``) and every leaf
    of each eager ``jax.lax.scan``'s final carry.  Yields
    ``{"demand" | "logy" | "carry": [(shape, {device id: rows})]}``."""
    import jax

    from repro.core import forecast as fc
    from repro.launch import mesh as mesh_mod

    seen = {"demand": [], "logy": [], "carry": []}
    scan, shard_rows = jax.lax.scan, mesh_mod.shard_rows
    prefix_fit_state = fc.prefix_fit_state

    def note(name, arr):
        if isinstance(arr, jax.Array) and arr.ndim and not isinstance(
            arr, jax.core.Tracer
        ):
            seen[name].append((arr.shape, {
                s.device.id: s.data.shape[0] for s in arr.addressable_shards
            }))

    def recording_scan(f, init, xs=None, *args, **kwargs):
        carry, ys = scan(f, init, xs, *args, **kwargs)
        for leaf in jax.tree.leaves(carry):
            note("carry", leaf)
        return carry, ys

    def recording_shard_rows(rows):
        out = shard_rows(rows)
        note("demand", out)
        return out

    def recording_prefix_fit_state(*args, **kwargs):
        state = prefix_fit_state(*args, **kwargs)
        note("logy", state.logy)
        return state

    jax.lax.scan = recording_scan
    mesh_mod.shard_rows = recording_shard_rows
    fc.prefix_fit_state = recording_prefix_fit_state
    try:
        yield seen
    finally:
        jax.lax.scan = scan
        mesh_mod.shard_rows = shard_rows
        fc.prefix_fit_state = prefix_fit_state


def host_pools(**kw):
    """A seeded synthetic fleet, generated on the host CPU backend (it is
    per-pool eager work: set-up, not the planner's path)."""
    import jax

    from repro.data import traces

    with jax.default_device(jax.devices("cpu")[0]):
        return traces.synthetic_pool_set(**kw)


def reference_request(pools):
    from repro.core import api
    from repro.obs.config import TelemetryConfig

    return api.PlanRequest(
        pools=pools, mode="rolling",
        horizon_weeks=REFERENCE["horizon_weeks"],
        spot=True, migration=True, convertible=True,
        telemetry=TelemetryConfig(ledger=True),
        rolling=api.RollingConfig(cadence_weeks=1, compare=True),
    )


def forecast_precision(checks: Checks, pools, start_weeks: int) -> dict:
    """The chip's forecast at the first decision week against a float64
    solve of the same normal equations (the chip's own design matrix and
    log-demand, so only contraction and solve precision can differ), and
    the chip's ``jnp.linalg.solve`` against a float64 solve of the chip's
    own cumulative normal equations."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import forecast as fc

    cfg = fc.ForecastConfig()
    horizon = REFERENCE["horizon_weeks"] * HOURS_PER_WEEK
    state = fc.prefix_fit_state(
        jnp.asarray(pools.demand, jnp.float32), cfg, horizon_hours=horizon,
        min_prefix_hours=start_weeks * HOURS_PER_WEEK,
    )
    beta = fc.solve_prefix(state, start_weeks)
    t0 = start_weeks * HOURS_PER_WEEK
    chip_fc = np.asarray(fc.predict_from_beta(state, beta, t0, horizon))

    x = np.asarray(state.x, np.float64)
    logy = np.asarray(state.logy, np.float64)
    ridge = cfg.ridge * np.eye(x.shape[1])
    xf = x[t0:t0 + horizon]
    gram = x[:t0].T @ x[:t0] + ridge
    beta64 = np.linalg.solve(gram, (logy[:, :t0] @ x[:t0]).T).T
    ref_fc = np.exp(beta64 @ xf.T)
    fc_gap = rel_gap(chip_fc, ref_fc)
    checks.check(
        "forecast vs float64 normal equations", fc_gap <= FORECAST_RTOL,
        f"max rel {fc_gap:.3e} (tol {FORECAST_RTOL:g}, week {start_weeks})",
    )

    g_chip = np.asarray(state.gram_prefix[start_weeks - 1], np.float64)
    r_chip = np.asarray(state.rhs_prefix[:, start_weeks - 1], np.float64)
    beta_solve64 = np.linalg.solve(g_chip + ridge, r_chip.T).T
    solve_gap = rel_gap(
        np.exp(np.asarray(beta, np.float64) @ xf.T),
        np.exp(beta_solve64 @ xf.T),
    )
    checks.check(
        "jnp.linalg.solve vs float64 solve", solve_gap <= FORECAST_RTOL,
        f"forecast max rel {solve_gap:.3e} (tol {FORECAST_RTOL:g})",
    )
    # For contrast, not gated: the forecast contraction at the TPU's
    # default precision (inputs rounded to bfloat16).
    xf_dev = jax.lax.dynamic_slice_in_dim(state.x, t0, horizon, axis=0)
    bf16_fc = np.asarray(jnp.exp(jnp.matmul(beta, xf_dev.T)))
    bf16_gap = rel_gap(bf16_fc, ref_fc)
    print(f"  (default-precision forecast contraction: max rel "
          f"{bf16_gap:.3e} vs float64)", flush=True)
    return {"forecast_gap": fc_gap, "solve_gap": solve_gap,
            "default_precision_gap": bf16_gap}


def phase_reference(checks: Checks) -> dict:
    import jax

    from repro.core import api

    pools = host_pools(
        num_pools=REFERENCE["num_pools"],
        num_hours=HOURS_PER_WEEK * REFERENCE["weeks"], migration=True,
    )
    req = reference_request(pools)
    chip = api.plan(req)
    with jax.default_device(jax.devices("cpu")[0]):
        host = api.plan(req)
    out = {"pools": pools.num_pools, "weeks": REFERENCE["weeks"]}
    for name in ("total_cost", "one_shot_cost", "hindsight_cost"):
        a, b = getattr(chip, name), getattr(host, name)
        gap = rel_gap(a, b)
        out[name] = {"chip": a, "cpu": b, "rel_gap": gap}
        checks.check(
            f"reference {name} chip vs cpu", gap <= CPU_GAP_RTOL,
            f"chip {a!r} cpu {b!r} rel {gap:.3e} (tol {CPU_GAP_RTOL:g})",
        )
    rec = chip.ledger.reconcile(chip)
    out["reconcile_max_rel"] = rec["max_rel"]
    checks.check(
        "ledger reconciles with the report", rec["ok"],
        f"max rel {rec['max_rel']:.3e} (gate {rec['rtol']:g})",
    )
    out.update(forecast_precision(checks, pools, chip.start_weeks))
    return out


def phase_kernel(checks: Checks) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import api
    from repro.kernels.commitment_sweep import ops

    pools = host_pools(
        num_pools=REFERENCE["num_pools"],
        num_hours=HOURS_PER_WEEK * REFERENCE["weeks"], migration=True,
    )
    reps = {}
    for use_kernel in (True, False):
        reps[use_kernel] = api.plan(api.PlanRequest(
            pools=pools, mode="rolling",
            horizon_weeks=REFERENCE["horizon_weeks"],
            rolling=api.RollingConfig(
                solver="grid", use_kernel=use_kernel, compare=False
            ),
        ))
    kern, ref = reps[True], reps[False]
    scale = float(np.abs(ref.targets).max())
    levels_ok = np.allclose(
        kern.targets, ref.targets, rtol=SWEEP_RTOL, atol=SWEEP_RTOL * scale
    )
    level_gap = float(np.abs(kern.targets - ref.targets).max())
    checks.check(
        "grid plan levels: Pallas sweep vs jnp", levels_ok,
        f"max abs {level_gap:.3e} of levels up to {scale:.1f} "
        f"(rtol {SWEEP_RTOL:g}); bit-identical "
        f"{np.array_equal(kern.targets, ref.targets)}",
    )
    cost_gap = rel_gap(kern.total_cost, ref.total_cost)
    checks.check(
        "grid total: Pallas sweep vs jnp", cost_gap <= SWEEP_RTOL,
        f"rel {cost_gap:.3e}",
    )
    # The sweep shape the replay launches each week: horizon prefixes
    # folded into rows (see replan.grid_prefix_levels).
    rows = pools.num_pools * REFERENCE["horizon_weeks"]
    hours = REFERENCE["horizon_weeks"] * HOURS_PER_WEEK
    f = jax.ShapeDtypeStruct((rows, hours), jnp.float32)
    cs = jax.ShapeDtypeStruct((rows, api.RollingConfig().num_grid),
                              jnp.float32)
    hlo = jax.jit(ops.commitment_sweep_over_under).lower(
        f, cs, f
    ).compile().as_text()
    checks.check(
        "sweep compiled as a Mosaic kernel", "tpu_custom_call" in hlo,
        f"tpu_custom_call in the compiled sweep at ({rows}, "
        f"{cs.shape[1]}, {hours})",
    )
    return {"level_max_abs": level_gap, "total_rel_gap": cost_gap,
            "bit_identical": bool(np.array_equal(kern.targets, ref.targets))}


def scale_request(pools, n_scenarios: int | None):
    from repro.core import api
    from repro.data.scenarios import ScenarioConfig

    return api.PlanRequest(
        pools=pools, mode="rolling", horizon_weeks=SCALE["horizon_weeks"],
        scenarios=(
            None if n_scenarios is None else ScenarioConfig(
                n_scenarios=n_scenarios, family=SCALE["family"], seed=0
            )
        ),
        rolling=api.RollingConfig(
            cadence_weeks=1, start_weeks=SCALE["start_weeks"], compare=True
        ),
    )


def scenario_totals(rep) -> dict:
    import numpy as np

    return {
        "rolling": np.asarray(rep.scenario_cost),
        "one_shot": np.asarray(rep.scenario_one_shot_cost),
        "hindsight": np.asarray(rep.scenario_hindsight_cost),
    }


def scale_pools():
    return host_pools(
        num_pools=SCALE["num_pools"],
        num_hours=HOURS_PER_WEEK * SCALE["weeks"],
    )


def phase_scale(checks: Checks, recorder, clock) -> dict:
    import jax
    import numpy as np

    from repro.core import api

    n = SCALE_SCENARIOS[1]
    pools = scale_pools()
    c0 = clock.seconds
    with recorder.span("scale_batched_replay", phase="execute") as sp:
        rep = api.plan(scale_request(pools, n))
    wall, compile_s = sp.duration_s, clock.seconds - c0
    peak = peak_bytes(jax.devices()[:1])[0]
    print(f"  P={pools.num_pools} N={n} {SCALE['weeks']} weeks: "
          f"{wall:.1f} s wall (cold, compile included; {compile_s:.1f} s "
          f"compiling), peak {peak} bytes on the chip", flush=True)
    tot = scenario_totals(rep)
    checks.check(
        "scale costs finite",
        all(np.isfinite(v).all() and v.shape == (n,) for v in tot.values()),
        f"{n} scenarios x rolling/one-shot/hindsight",
    )
    base = api.plan(scale_request(pools, None))
    gaps = {
        "rolling": rel_gap(tot["rolling"][0], base.total_cost),
        "one_shot": rel_gap(tot["one_shot"][0], base.one_shot_cost),
        "hindsight": rel_gap(tot["hindsight"][0], base.hindsight_cost),
    }
    target_diff = float(np.abs(rep.targets[:, 0] - base.targets).max())
    identical = target_diff == 0.0 and max(gaps.values()) == 0.0
    checks.check(
        "scenario 0 vs unbatched replay",
        max(gaps.values()) <= SCENARIO0_RTOL,
        f"bit-identical {identical}; total rel gaps {gaps}; "
        f"max target diff {target_diff:.3e} (tol {SCENARIO0_RTOL:g})",
    )
    return {"pools": pools.num_pools, "scenarios": n, "replay_wall_s": wall,
            "replay_compile_s": compile_s, "peak_bytes": peak,
            "scenario0_bit_identical": identical,
            "scenario0_gaps": gaps, "scenario0_target_max_diff": target_diff,
            "mean_cost": float(rep.total_cost)}


def phase_sharded(checks: Checks, recorder, clock) -> dict:
    """The scale replay with rows sharded over four chips, against the same
    scenarios with their rows on one chip."""
    import jax
    import numpy as np

    from repro.core import api

    devices = jax.devices()
    n, k = SCALE_SCENARIOS[4], ONE_CHIP_SCENARIOS
    pools = scale_pools()
    c0 = clock.seconds
    with recorder.span("sharded_replay", phase="execute") as sp, \
            row_placement() as placed:
        rep = api.plan(scale_request(pools, n))
    wall, compile_s = sp.duration_s, clock.seconds - c0
    peaks = peak_bytes(devices)
    print(f"  P={pools.num_pools} N={n} over {len(devices)} chips: "
          f"{wall:.1f} s wall (cold, compile included; {compile_s:.1f} s "
          f"compiling), peak bytes per chip {peaks}", flush=True)
    tot = scenario_totals(rep)
    checks.check(
        "sharded costs finite",
        all(np.isfinite(v).all() and v.shape == (n,) for v in tot.values()),
        f"{n} scenarios x rolling/one-shot/hindsight",
    )

    rows = pools.num_pools * n
    per_chip = rows // len(devices)
    rolloff = [c for c in placed["carry"] if len(c[0]) == 3]
    for name, arrays in (("demand", placed["demand"]),
                         ("logy", placed["logy"]),
                         ("roll-off carry", rolloff)):
        arrays = [a for a in arrays if a[0][0] == rows]
        checks.check(
            f"{name} rows split over the chips",
            bool(arrays) and all(
                len(split) == len(devices)
                and set(split.values()) == {per_chip} for _, split in arrays
            ),
            f"(shape, rows per chip) {arrays} of {rows} rows",
        )
    # Had any of them been whole on chip 0 as well, chip 0's peak would
    # stand above the others by at least the smallest of them.
    smallest = 4 * min(
        (int(np.prod(shape)) for shape, _ in rolloff), default=0
    )
    checks.check(
        "chip 0 holds no whole copy",
        peaks[0] - min(peaks) < smallest,
        f"peaks {peaks}; whole roll-off carry {smallest} bytes",
    )

    with jax.default_device(devices[0]):
        one = api.plan(scale_request(pools, k))
    one_tot = scenario_totals(one)
    identical = all(
        np.array_equal(tot[key][:k], one_tot[key]) for key in tot
    )
    gaps = {key: rel_gap(tot[key][:k], one_tot[key]) for key in tot}
    checks.check(
        f"sharded scenarios 0..{k - 1} vs their rows on one chip",
        max(gaps.values()) <= SCENARIO0_RTOL,
        f"bit-identical {identical}; max rel gaps {gaps} "
        f"(tol {SCENARIO0_RTOL:g})",
    )
    return {"pools": pools.num_pools, "scenarios": n, "chips": len(devices),
            "replay_wall_s": wall, "replay_compile_s": compile_s,
            "peak_bytes": peaks,
            "one_chip_scenarios": k, "bit_identical": identical,
            "rel_gaps": gaps, "mean_cost": float(rep.total_cost)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: every phase on one chip; 4: only the scale "
                         "replay sharded over four chips")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform}); "
              "this run has no CPU fallback", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jaxlib

    from repro.launch.compile_cache import enable_compile_cache
    from repro.obs.spans import SpanRecorder

    cache_dir = enable_compile_cache()
    try:
        from importlib.metadata import version

        libtpu = version("libtpu")
    except Exception:  # noqa: BLE001 - version is informational only
        libtpu = "unknown"
    kind = devices[0].device_kind
    print(f"device: {kind} x{len(devices)}; jax {jax.__version__}, jaxlib "
          f"{jaxlib.__version__}, libtpu {libtpu}; compile cache "
          f"{cache_dir}", flush=True)

    clock = CompileClock()
    recorder = SpanRecorder()
    checks = Checks()
    if args.chips == 4:
        phases = [("sharded", lambda: phase_sharded(checks, recorder, clock))]
    else:
        phases = [
            ("reference", lambda: phase_reference(checks)),
            ("kernel", lambda: phase_kernel(checks)),
            ("scale", lambda: phase_scale(checks, recorder, clock)),
        ]
    summary = {"device": {"kind": kind, "count": len(devices)},
               "jax": jax.__version__, "libtpu": libtpu, "phases": {}}
    with recorder.span("chip_smoke", phase="execute") as whole:
        for name, run in phases:
            print(f"[{name}]", flush=True)
            before = clock.snapshot()
            with recorder.span(name, phase="execute") as sp:
                try:
                    result = run()
                except Exception:  # noqa: BLE001 - report, then go on
                    result = {"error": traceback.format_exc()}
                    checks.check(f"{name} phase ran", False,
                                 result["error"].strip().splitlines()[-1])
                    print(result["error"], file=sys.stderr, flush=True)
            after = clock.snapshot()
            result["wall_s"] = sp.duration_s
            result["compile"] = {
                key: after[key] - before[key] for key in after
            }
            summary["phases"][name] = result
            print(f"[{name}] {sp.duration_s:.1f} s wall (cold, compile "
                  f"included); compile {result['compile']}", flush=True)
    summary.update(wall_s=whole.duration_s, compile=clock.snapshot(),
                   checks=checks.results)
    print(f"total {whole.duration_s:.1f} s wall; compile "
          f"{clock.snapshot()}", flush=True)
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    suffix = "_4chips" if args.chips == 4 else ""
    with open(os.path.join(out_dir, f"chip_smoke{suffix}.json"), "w") as f:
        json.dump(summary, f, indent=2, default=float)
    if checks.failed:
        print(f"chip_smoke: FAILED {checks.failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
