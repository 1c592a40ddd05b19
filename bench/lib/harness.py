"""One run of one benchmark cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

finds the cell in ``BENCHMARK.json`` and its parts by name:
``bench/configs/<config>.json`` (the deployment), ``bench/traffic/<mix>.
json`` (the request and how requests vary), ``bench/cells/<cell>.json``
(the limits of the numbers compared) and ``bench/metrics/<metric>.py``
(one reader per per-layer metric).

Set-up makes the fleet from the seed, prepares every request the window
can use (each plan scales every pool by its own factor drawn from (seed,
plan index), and a scenario plan gets its own scenario seed), and runs
one warm-up plan of the cell's shape.  The window then calls
``api.plan`` in a closed loop, one request at a time, until the first
plan that ends after ``--seconds``.  With ``--trace 1`` the profiler
records the first ``trace_plans`` plans of the window, and the run
reports the per-layer metrics in place of the end-to-end ones.

Once the window has closed and the chip's peak memory is read, a sample
of the window's plans, drawn from the seed, is replayed by the plain
reference (``lib/reference.py``) on the host, its scenarios split over a
few worker processes, and each gap is held to its limit.  The replay is
handed the program's weekly buys, by which it settles the hedge's ties
(``reference.TIE``).
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import multiprocessing
import os
import shutil
import sys
import time

from concurrent.futures import ProcessPoolExecutor

import numpy as np

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# Host processes the reference's scenario blocks are split over.
REFERENCE_WORKERS = 4


class NoAccelerator(RuntimeError):
    pass


def load_cell(root: str, workload: str) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    cell = cells[workload]
    bench = os.path.join(root, "bench")

    def read(*parts):
        with open(os.path.join(bench, *parts)) as f:
            return json.load(f)

    return {
        "cell": cell,
        "config": read("configs", f"{cell['config']}.json"),
        "traffic": read("traffic", f"{cell['traffic']}.json"),
        "limits": read("cells", f"{workload}.json")["limits"],
        "end_to_end": [m for m in spec["end_to_end"]
                       if workload in m.get("workloads", [workload])],
        "per_layer": [m for m in spec["per_layer"]
                      if workload in m.get("workloads", [workload])],
        "metrics_dir": os.path.join(bench, "metrics"),
    }


def read_metric(metrics_dir: str, name: str, record: dict):
    """The per-layer metric ``name`` from its reader, or None."""
    path = os.path.join(metrics_dir, f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(record)


def devices_or_fail(chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoAccelerator(f"no TPU: JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX found "
                            f"{len(devs)}")
    return devs


def request_for(cfg: dict, traffic: dict, pools, scen_seed: int):
    """The cell's ``PlanRequest`` on ``pools``.  The traffic's
    ``request.rolling``, ``request.telemetry`` and ``request.scenarios``
    are the program's ``RollingConfig``, ``TelemetryConfig`` and
    ``ScenarioConfig`` fields, passed on as they stand (the configuration
    gives ``start_weeks`` unless the traffic does; each plan gets its own
    scenario seed)."""
    from repro.capacity import generations as gn
    from repro.capacity import pricing
    from repro.core import api, portfolio as pf
    from repro.core import forecast as fc
    from repro.core import spot as spot_mod
    from repro.obs.config import TelemetryConfig

    from lib import deployment as dep

    req = traffic["request"]
    mk = lambda o, conv=False: pf.PurchaseOption(  # noqa: E731
        o.name, o.cloud, o.rate, o.term_weeks, convertible=conv)
    p = cfg["pricing"]
    scen = req.get("scenarios")
    tel = req.get("telemetry")
    return api.PlanRequest(
        pools=pools, mode="rolling",
        options=[mk(o) for o in dep.options(p)],
        od_rate=dep.od_rate(p),
        horizon_weeks=cfg["horizon_weeks"],
        forecast=fc.ForecastConfig(**cfg["forecast"]),
        spot=spot_mod.SpotConfig(**cfg["spot"]) if req["spot"] else None,
        migration=gn.MigrationConfig(
            generations=tuple(pricing.Generation(**g)
                              for g in p["generations"]),
            software_efficiency_per_year=cfg["demand_model"][
                "software_efficiency_per_year"],
            share_prior_weight=cfg["share_prior_weight"],
        ) if req["migration"] else None,
        convertible=[mk(o, True) for o in dep.convertible_options(
            p, pools.clouds)] if req["convertible"] else None,
        scenarios=(api.ScenarioConfig(**scen, seed=scen_seed)
                   if scen else None),
        telemetry=TelemetryConfig(**tel) if tel is not None else None,
        policy=req.get("policy"),
        rolling=api.RollingConfig(**{"start_weeks": cfg.get("start_weeks"),
                                     **req.get("rolling", {})}),
    )


def reference_request(preq, traffic: dict) -> dict:
    """What the reference replays, read off the built ``PlanRequest``
    ``preq``, so the reference sees every knob that reached the program
    (it refuses those it does not implement)."""
    r, scen, tel = preq.rolling, preq.scenarios, preq.telemetry
    policy = preq.policy
    out = {
        "policy": policy if isinstance(policy, str) or policy is None
        else policy.name,
        "spot": preq.spot is not None, "migration": preq.migration is not None,
        "convertible": preq.convertible is not None,
        "ledger": bool(tel is not None and tel.ledger),
        "start_weeks": r.start_weeks, "cadence_weeks": r.cadence_weeks,
        "solver": r.solver, "cadence": r.cadence, "compare": r.compare,
        "irls_iters": r.irls_iters,
        "scenarios": None,
    }
    if scen is not None:
        out["scenarios"] = dict(traffic.get("scenario_model", {}),
                                n=scen.n_scenarios, family=scen.family,
                                seed=scen.seed)
    return out


def shape_of(preq) -> dict:
    """The scan's shape in the request, for the per-layer readers."""
    n = preq.scenarios.n_scenarios if preq.scenarios is not None else 1
    return {"rows": len(preq.pools.keys) * n,
            "horizon_weeks": preq.horizon_weeks,
            "horizon_hours": preq.horizon_weeks * 168,
            "num_grid": preq.rolling.num_grid}


def reference_plan(cfg: dict, req: dict, keys, demand,
                   kind: str = "float64", follow=None) -> dict:
    """The reference's replay of ``req``, its scenarios split over up to
    ``REFERENCE_WORKERS`` host processes (each its own interpreter; none
    touches JAX), all ended before it returns.  ``follow`` is the
    program's weekly buys per row (``compare.row_buys``)."""
    from lib import reference

    n = req["scenarios"]["n"] if req.get("scenarios") else 1
    p = len(keys)
    cuts = np.linspace(0, n, min(REFERENCE_WORKERS, n) + 1).astype(int)
    jobs = [(cfg, req, keys, demand, kind, (int(a), int(b)),
             None if follow is None else follow[:, a * p:b * p])
            for a, b in zip(cuts[:-1], cuts[1:])]
    if len(jobs) == 1:
        return reference.plan_block(jobs[0])
    ctx = multiprocessing.get_context("spawn")
    with ProcessPoolExecutor(len(jobs), mp_context=ctx) as pool:
        return reference.merge(list(pool.map(reference.plan_block, jobs)))


def block(report):
    import jax

    leaves = [x for v in vars(report).values() for x in jax.tree.leaves(v)
              if isinstance(x, jax.Array)]
    jax.block_until_ready(leaves)


def run(args, *, root: str, require_accelerator: bool = True,
        t_start: float | None = None) -> tuple[dict, list[str]]:
    """One run; returns (result line, check lines)."""
    t_start = time.perf_counter() if t_start is None else t_start
    parts = load_cell(root, args.workload)
    cfg, traffic = parts["config"], parts["traffic"]
    import jax

    devs = (devices_or_fail(parts["cell"]["chips"]) if require_accelerator
            else jax.devices())
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f).get(devs[0].device_kind)
    if peaks is None and require_accelerator:
        raise KeyError(f"no peaks for device kind {devs[0].device_kind!r} "
                       "in bench/peaks.json")
    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(root, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    from repro.core import api
    from repro.core.demand import PoolSet
    from repro.data import scenarios as sc_mod

    from lib import compare, data
    from lib import trace as tr

    seed = args.seed
    phases = {"start": time.perf_counter() - t_start}
    keys, base = data.fleet(cfg, seed)
    phases["fleet"] = time.perf_counter() - t_start
    sigma = traffic["plan_scale_sigma"]
    n_max = traffic["max_plans"]

    def inputs(i):
        demand = base * data.plan_scales(seed, i, len(keys), sigma)[:, None]
        pools = PoolSet(keys=keys, demand=demand)
        s_seed = data.scenario_seed(seed, i)
        return pools, s_seed, request_for(cfg, traffic, pools, s_seed)

    plans = [inputs(i) for i in range(n_max + 1)]
    phases["requests"] = time.perf_counter() - t_start
    watch = tr.CompileWatch()
    warm = api.plan(plans[0][2])
    block(warm)
    del warm
    phases["warm_up"] = time.perf_counter() - t_start

    # Host spans round the program's scenario generator, by module
    # attribute, so the trace can name what the host did in a gap.
    gen = sc_mod.scenario_batch

    def traced_batch(*a, **k):
        with jax.profiler.TraceAnnotation("scenario_batch"):
            return gen(*a, **k)

    sc_mod.scenario_batch = traced_batch
    trace_dir = os.path.join(root, ".bench_trace", args.workload)
    n_traced = traffic["trace_plans"] if args.trace else 0
    # The plans checked are a sample of the window's, drawn from the seed:
    # the check_plans of least priority.  Only those answers are kept.
    prio = np.random.default_rng([seed, 0xC4EC]).random(n_max + 1)
    k_check = traffic["check_plans"]
    answers, wall = {}, []
    setup_s = time.perf_counter() - t_start
    try:
        t0, w0 = time.perf_counter(), time.time()
        if n_traced:
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(trace_dir,
                                     profiler_options=tr.options())
        i = 1
        while True:
            if i > n_max:
                raise RuntimeError(
                    f"the window needs more than max_plans={n_max} plans")
            p0 = time.time()
            with jax.profiler.TraceAnnotation("plan"):
                rep = api.plan(plans[i][2])
                block(rep)
            wall.append((p0, time.time()))
            answers[i] = compare.answer(rep)
            del rep
            if len(answers) > k_check:
                del answers[max(answers, key=prio.__getitem__)]
            if n_traced and i == n_traced:
                jax.profiler.stop_trace()
            if time.perf_counter() - t0 >= args.seconds:
                break
            i += 1
        window_s = time.perf_counter() - t0
        if n_traced and i < n_traced:
            jax.profiler.stop_trace()
    finally:
        sc_mod.scenario_batch = gen
    n = len(wall)
    peak = max(int(d.memory_stats()["peak_bytes_in_use"])
               if d.memory_stats() else 0 for d in devs)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    window = watch.summary(w0, time.time())
    result = {"correct": None, "attempted": n, "failed": 0}
    if args.trace:
        n_tr = min(n, n_traced)
        events = tr.load(trace_dir)
        span = tr.window_of(events, "plan")
        # The first traced plan's annotation opened at wall[0][0].
        offset = span[0] - int(wall[0][0] * 1e9)
        red = tr.reduce(events, span, labels=watch.labels(offset))
        record = {
            "plans": n_tr,
            "trace": red,
            "compile": watch.summary(wall[0][0], wall[n_tr - 1][1]),
            "scenario_gen_s": tr.host_spans(events, "scenario_batch", span),
            "shape": shape_of(plans[1][2]),
            "peaks": peaks,
        }
        metrics = {}
        for m in parts["per_layer"]:
            v = read_metric(parts["metrics_dir"], m["name"], record)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        result["breakdown"] = red["breakdown"]
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        values = {"setup_s": setup_s, "plan_s": window_s / n,
                  "peak_hbm_gb": peak / 1e9}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in parts["end_to_end"]}
    result["metrics"] = metrics
    result["device"] = device

    # The reference runs on the host once the window has closed.
    check = sorted(answers)
    names = list(parts["limits"])
    worst = {k: 0.0 for k in names}
    ties = []
    t_ref = time.perf_counter()
    for j in check:
        pools, _, preq = plans[j]
        ref = reference_plan(cfg, reference_request(preq, traffic), keys,
                             pools.demand,
                             follow=compare.row_buys(answers[j]))
        ties.append(ref["ties"])
        for k, v in compare.gaps(answers[j], ref, names).items():
            worst[k] = max(worst[k], v)
    ties = np.concatenate(ties)
    t_ref = time.perf_counter() - t_ref
    failed = [k for k in names if not worst[k] <= parts["limits"][k]]
    result["correct"] = not failed
    result["failed"] = len(check) if failed else 0
    result["checks"] = {k: {"value": worst[k], "limit": parts["limits"][k]}
                        for k in names}
    lines = ["set-up phases, s from process start: " + ", ".join(
                 f"{k} {v:.3f}" for k, v in phases.items()),
             f"compile in window: {window}",
             f"checked plans {check} of {n} in {t_ref:.1f} s",
             f"hedge ties followed: {len(ties)}, widest margin "
             f"{ties.max() if len(ties) else 0.0!r}"]
    lines += [f"check {k}: {worst[k]!r} limit {parts['limits'][k]!r}"
              f"{'' if k not in failed else '  FAILED'}" for k in names]
    return result, lines


def main(argv=None, *, t_start: float | None = None) -> int:
    ap = argparse.ArgumentParser(description="one run of a benchmark cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    root = os.path.dirname(BENCH)
    try:
        result, lines = run(args, root=root, t_start=t_start)
    except NoAccelerator as e:
        print(f"bench: {e}; this benchmark has no CPU fallback",
              file=sys.stderr)
        return 1
    for line in lines:
        print(line, file=sys.stderr)
    print(json.dumps(result))
    return 0
