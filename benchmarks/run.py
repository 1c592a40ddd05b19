"""Benchmark harness: one function per paper table/figure + kernel micro.

Prints ``name,us_per_call,derived`` CSV.  Roofline terms come from the
dry-run artifacts (see benchmarks/roofline.py and EXPERIMENTS.md §Roofline);
this harness covers the paper-results reproduction and kernel throughputs.

Flags:
    --quick        tiny shapes / fewer iters — the CI `bench-smoke` mode.
                   Kernel benches still run their kernel-vs-reference
                   tolerance checks, so a kernel regression fails the job.
    --json PATH    also write rows + failures as JSON (the CI artifact),
                   stamped with provenance (schema version, git SHA, seed,
                   JAX/numpy/backend versions, platform) so BENCH_ci.json
                   trajectories are comparable across machines and
                   commits, plus a per-bench wall-clock span breakdown
                   (``repro.obs.spans``).
    --spans PATH   also write the span report as its own JSON artifact.
    --seed N       PRNG seed threaded to every bench (default 0), so two
                   runs at the same seed produce identical `derived`
                   columns — the CI BENCH_ci.json artifact is stable run
                   to run (timing columns aside).
    --filter S     run only benches whose function name contains S
                   (case-insensitive substring, e.g. `--filter migration`
                   runs just bench_migration_scan) — lets CI or a dev
                   iterate on one bench without rerunning everything.
                   Unknown filters (zero matches) exit nonzero.

Exit status is nonzero if any bench raises (including a failed
kernel-vs-reference check inside a bench).
"""

from __future__ import annotations

import argparse
import json
import os
import platform
import subprocess
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: BENCH_ci.json payload schema; bump when the payload shape changes.
BENCH_SCHEMA_VERSION = 2


def _git_sha() -> str:
    """Commit provenance for the JSON artifact: CI env var if present,
    else the working tree's HEAD, else "unknown"."""
    for var in ("GITHUB_SHA", "CI_COMMIT_SHA"):
        sha = os.environ.get(var)
        if sha:
            return sha
    try:
        return subprocess.run(
            ["git", "-C", _ROOT, "rev-parse", "HEAD"],
            capture_output=True, text=True, timeout=10, check=True,
        ).stdout.strip()
    except Exception:  # noqa: BLE001
        return "unknown"


def main(argv=None) -> None:
    # Robust to invocation directory: repo root (for `benchmarks.*`) and
    # src (for `repro.*`) both land on the path.
    for p in (os.path.join(_ROOT, "src"), _ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="tiny-shape smoke mode (CI bench-smoke job)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write results JSON (e.g. BENCH_ci.json)")
    ap.add_argument("--spans", default=None, metavar="PATH",
                    help="write the wall-clock span report JSON")
    ap.add_argument("--seed", type=int, default=0,
                    help="PRNG seed for every bench (stable derived values)")
    ap.add_argument("--filter", default=None, metavar="SUBSTR",
                    help="only run benches whose name contains SUBSTR")
    args = ap.parse_args(argv)

    from benchmarks.kernel_benches import (
        ALL_KERNEL_BENCHES,
        commitment_sweep_kernel_stats,
    )
    from benchmarks.paper_benches import ALL_PAPER_BENCHES
    from repro.launch.compile_cache import enable_compile_cache
    from repro.obs.spans import SpanRecorder

    enable_compile_cache()

    benches = ALL_PAPER_BENCHES + ALL_KERNEL_BENCHES
    if args.filter is not None:
        want = args.filter.lower()
        benches = [b for b in benches if want in b.__name__.lower()]
        if not benches:
            names = [b.__name__ for b in
                     ALL_PAPER_BENCHES + ALL_KERNEL_BENCHES]
            raise SystemExit(
                f"--filter {args.filter!r} matches no bench; "
                f"available: {names}"
            )

    rec = SpanRecorder()
    print("name,us_per_call,derived")
    rows, failures = [], []
    for bench in benches:
        try:
            with rec.span(bench.__name__, phase="execute"):
                for name, us, derived in bench(
                    quick=args.quick, seed=args.seed
                ):
                    rows.append({"name": name, "us_per_call": us,
                                 "derived": derived})
                    print(f"{name},{us:.1f},{derived}")
        except Exception as e:  # noqa: BLE001
            failures.append({"bench": bench.__name__, "error": repr(e)})
            print(f"{bench.__name__},NaN,FAILED: {e!r}")

    if args.json:
        import jax
        import numpy as np

        payload = {
            "schema_version": BENCH_SCHEMA_VERSION,
            "git_sha": _git_sha(),
            "quick": args.quick,
            "seed": args.seed,
            "filter": args.filter,
            "python": platform.python_version(),
            "platform": platform.platform(),
            "jax": jax.__version__,
            "numpy": np.__version__,
            "backend": jax.default_backend(),
            "rows": rows,
            "failures": failures,
            "spans": rec.summary(),
            "kernel_stats": commitment_sweep_kernel_stats(args.quick),
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"wrote {args.json}: {len(rows)} rows, "
              f"{len(failures)} failures", file=sys.stderr)
    if args.spans:
        rec.to_json(args.spans)
        print(f"wrote {args.spans}: {len(rec.spans)} spans",
              file=sys.stderr)

    if failures:
        raise SystemExit(f"{len(failures)} benches failed: {failures}")


if __name__ == "__main__":
    main()
