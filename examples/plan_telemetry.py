"""Telemetry walkthrough: where every dollar of a rolling plan went —
and whether the forecast bands that priced its risk were calibrated.

    PYTHONPATH=src python examples/plan_telemetry.py \
        [--ledger-out LEDGER.jsonl] [--spans-out SPANS.json] \
        [--calib-out CALIB.jsonl] [--calib-fail-above DRIFT]

`telemetry=TelemetryConfig(calibration=True, provenance=True)` on a
rolling :class:`~repro.core.api.PlanRequest` makes the replay scan emit
its own billing decomposition (per-week x per-pool x per-source),
the weekly forecast fractile levels scored against realized demand
(:class:`repro.obs.CalibrationCube`), and per-week decision records
(:class:`repro.obs.DecisionLog`).  The ledger's weekly row-sums must
reconcile with the report's weekly costs to f32 machine precision, and
the calibration coverage must stay inside the drift gate; this example
**exits nonzero on reconciliation drift or calibration-gate breach**,
which is exactly the gate the CI bench-smoke job runs.

Wall time is recorded with the span profiler (`repro.obs.spans`): the
example's own spans, and inside `with recording(rec):` the planner's
host stages (`replan/...`, with the bytes each moves between host and
device).  The planner core itself never reads a clock (analysis rules
R2/R7).

The exported JSONL round-trips through the CLI:

    python -m repro.obs report LEDGER.jsonl
    python -m repro.obs diff  A.jsonl B.jsonl --fail-above 1.0
    python -m repro.obs calib CALIB.jsonl --fail-above 0.5
"""

import argparse
import sys

from repro.core import api
from repro.data import traces
from repro.obs import SpanRecorder, TelemetryConfig, recording


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--ledger-out", default=None, metavar="PATH",
                    help="export the cost ledger as JSONL")
    ap.add_argument("--spans-out", default=None, metavar="PATH",
                    help="export the wall-clock span report as JSON")
    ap.add_argument("--calib-out", default=None, metavar="PATH",
                    help="export the calibration cube as JSONL")
    # The demo fleet trends hard (migration ramps), so the trailing-window
    # bands under-cover by design — exactly the miscalibration the cube is
    # built to surface.  The default gate is therefore generous; steady
    # fleets sit well under 0.05 (see tests/test_obs.py::TestCalibration).
    ap.add_argument("--calib-fail-above", type=float, default=0.5,
                    metavar="DRIFT",
                    help="exit 1 when max |coverage - nominal| exceeds "
                         "this (default %(default)s)")
    args = ap.parse_args()

    rec = SpanRecorder()
    with rec.span("example/pools", phase="host"):
        pools = traces.synthetic_pool_set(
            num_pools=4, num_hours=24 * 7 * 20, migration=True,
        )

    # All bands on: spot floor, migration-aware forecaster, cloud-level
    # convertible commitments — the richest bill the planner can produce.
    with rec.span("example/plan", phase="execute"), recording(rec):
        rep = api.plan(api.PlanRequest(
            pools=pools, mode="rolling",
            rolling=api.RollingConfig(cadence_weeks=2, start_weeks=6,
                                      compare=False),
            horizon_weeks=4,
            spot=True, migration=True, convertible=True,
            telemetry=TelemetryConfig(calibration=True, provenance=True),
        ))
    led = rep.ledger

    print("== cost attribution (weeks "
          f"{int(led.weeks[0])}..{int(led.weeks[-1])}) ==")
    print("spend by source:")
    for s, v in sorted(led.by_source().items(), key=lambda kv: -kv[1]):
        print(f"  {s:24s} {v:14,.2f}")
    print("spend by entity:")
    for e, v in sorted(led.by_entity().items(), key=lambda kv: -kv[1]):
        print(f"  {e:28s} {v:14,.2f}")

    econ = led.unit_economics()
    print("\n== unit economics ==")
    print(f"  total cost              {econ['total_cost']:14,.2f}")
    print(f"  idle committed hours    {econ['idle_committed_hours']:14,.0f}"
          f"  ({econ['idle_fraction']:.1%} of committed)")
    print(f"  mean pool utilization   {econ['utilization_mean']:14.1%}")
    print(f"  cost per used chip-hour "
          f"{econ['cost_per_used_chip_hour']:14.4f}")

    one_cell = led.attribute(week=int(led.weeks[-1]),
                             pool=led.entities[0])
    print(f"\none cell of the bill — week {int(led.weeks[-1])}, "
          f"{led.entities[0]}: {one_cell:,.2f}")

    cube = rep.calibration
    print("\n== forecast calibration ==")
    print(cube.report())

    dlog = rep.decision_log
    print("\n== decision provenance ==")
    for k, v in dlog.summary().items():
        print(f"  {k:24s} {v}")
    last_dec = int(dlog.decision_weeks[-1])
    exp = dlog.explain(last_dec)
    print(f"  binding constraints at week {last_dec}: "
          + ", ".join(f"{p}={d['binding']}"
                      for p, d in sorted(exp["pools"].items())))

    with rec.span("example/export", phase="host"):
        if args.ledger_out:
            led.to_jsonl(args.ledger_out)
            print(f"wrote {args.ledger_out}")
        if args.spans_out:
            rec.to_json(args.spans_out)
            print(f"wrote {args.spans_out}")
        if args.calib_out:
            cube.to_jsonl(args.calib_out)
            print(f"wrote {args.calib_out}")

    print("\n== wall-clock spans ==")
    print(rec.report())

    # The CI gates: ledger row-sums must reconcile with the report, and
    # forecast coverage must stay inside the drift budget.
    res = led.reconcile(rep)
    print(f"\nreconciliation: max_rel {res['max_rel']:.2e} "
          f"(gate {res['rtol']:.0e}) -> "
          f"{'OK' if res['ok'] else 'DRIFT'}")
    drift = cube.max_coverage_drift
    print(f"calibration: max coverage drift {drift:.3f} "
          f"(gate {args.calib_fail_above:.3f}) -> "
          f"{'OK' if drift <= args.calib_fail_above else 'BREACH'}")
    if not res["ok"]:
        print(f"reconciliation drift: {res}", file=sys.stderr)
        sys.exit(1)
    if drift > args.calib_fail_above:
        print(f"calibration gate breach: drift {drift:.4f} > "
              f"{args.calib_fail_above:.4f}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
