"""A checkout-shaped directory holding the benchmark's cells cut to a
size the CPU runs in seconds, for the tests."""

from __future__ import annotations

import argparse
import json
import os
import shutil

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def make_root(tmp: str) -> str:
    """Copy BENCHMARK.json and bench/ to ``tmp``, with every config cut
    to a few weeks (a synthetic fleet to a few pools) and every traffic
    mix to a few plans over two futures."""
    dst = os.path.join(tmp, "bench")
    shutil.copytree(BENCH, dst, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp)
    for name in os.listdir(os.path.join(dst, "configs")):
        path = os.path.join(dst, "configs", name)
        with open(path) as f:
            cfg = json.load(f)
        cfg["fleet"]["weeks"] = 40
        cfg["horizon_weeks"] = 4
        if cfg["fleet"]["kind"] == "synthetic":
            cfg["fleet"]["num_pools"] = 6
            cfg["start_weeks"] = 26
        with open(path, "w") as f:
            json.dump(cfg, f)
    for name in os.listdir(os.path.join(dst, "traffic")):
        path = os.path.join(dst, "traffic", name)
        with open(path) as f:
            tr = json.load(f)
        tr["max_plans"] = 3
        tr["check_plans"] = 1
        if tr["request"].get("scenarios"):
            tr["request"]["scenarios"]["n_scenarios"] = 2
        with open(path, "w") as f:
            json.dump(tr, f)
    return tmp


def args(workload: str, seed: int = 2**31 + 7, trace: int = 0):
    return argparse.Namespace(workload=workload, seed=seed, seconds=0.0,
                              trace=trace)
