"""The numbers that decide ``correct``: gaps between what a timed plan
returned and the plain reference on the same inputs.

Each gap is the widest over the plans checked:

* ``targets``: weekly solver target per (row, SKU), over the row's mean
  evaluation demand — the forecaster and the solver;
* ``purchases``: weekly standard and convertible buys, over the row's (or
  cloud's) mean evaluation demand — the bands' purchases;
* ``rolling``, ``one_shot``, ``hindsight``: relative gap of each
  scenario's total;
* ``total``: relative gap of the rolling total over all scenarios;
* ``row_p90``, ``row_max``: the 90th percentile and the largest, over
  the scan's rows, of the relative gap of a row's rolling bill summed
  over the weeks (committed, on-demand and spot spend) — the buys of
  every row, where a total would let a fault in a few rows hide;
* ``ledger``: relative gap of the cost ledger's weekly spend against the
  reference's weekly bill (scenario 0).

A plan whose arrays do not have the reference's shape gets an infinite
gap."""

from __future__ import annotations

import numpy as np


def answer(report) -> dict:
    """The fields of a report the comparison reads (references only; no
    copies or arithmetic while the window runs)."""
    return {
        "targets": report.targets,
        "increments": report.increments,
        "conv_inc": report.conv_increments,
        "rolling": (report.scenario_cost if report.scenario_cost is not None
                    else report.total_cost),
        "one_shot": (report.scenario_one_shot_cost
                     if report.scenario_one_shot_cost is not None
                     else report.one_shot_cost),
        "hindsight": (report.scenario_hindsight_cost
                      if report.scenario_hindsight_cost is not None
                      else report.hindsight_cost),
        "ledger": report.ledger,
        "bills": (report.committed_cost, report.on_demand_cost,
                  report.spot_cost),
    }


def row_bills(bills) -> np.ndarray:
    """(R,) rolling bill per scan row, summed over the weeks; rows in
    (scenario, pool) order."""
    weekly = sum(np.asarray(b, np.float64) for b in bills if b is not None)
    return weekly.sum(0).reshape(-1)


def row_buys(ans: dict) -> np.ndarray:
    """(W, R) weekly buys per scan row summed over the SKUs; the
    reference settles the hedge's ties by them."""
    return _rows(ans["increments"]).sum(-1)


def reference_answer(out: dict) -> dict:
    """A reference replay's output in the shape of :func:`answer` (the
    controls are compared through it)."""
    return {"targets": out["targets"], "increments": out["increments"],
            "conv_inc": out["conv_inc"], "rolling": out["rolling"],
            "one_shot": out.get("one_shot"),
            "hindsight": out.get("hindsight"), "ledger": None,
            "bills": (out["row_bill"][None],)}


def _rows(a):
    """(S, N, P, K) -> (S, N P, K); (S, R, K) passes through."""
    a = np.asarray(a, np.float64)
    return a.reshape(a.shape[0], -1, a.shape[-1]) if a.ndim == 4 else a


def _scaled(prog, ref, scale):
    prog = _rows(prog)
    if prog.shape != ref.shape:
        return float("inf")
    return float(np.max(np.abs(prog - ref) / scale[None, :, None]))


def _rel(prog, ref):
    prog = np.atleast_1d(np.asarray(prog, np.float64))
    ref = np.atleast_1d(np.asarray(ref, np.float64))
    if prog.shape != ref.shape:
        return float("inf")
    return float(np.max(np.abs(prog - ref) / np.abs(ref)))


def gaps(ans: dict, ref: dict, names) -> dict:
    """{name: gap} for the numbers in ``names``."""
    out = {}
    for name in names:
        if name == "targets":
            g = _scaled(ans["targets"], ref["targets"], ref["row_scale"])
        elif name == "purchases":
            g = _scaled(ans["increments"], ref["increments"],
                        ref["row_scale"])
            if ref.get("conv_inc") is not None:
                g = max(g, _scaled(ans["conv_inc"], ref["conv_inc"],
                                   ref["cloud_scale"])
                        if ans["conv_inc"] is not None else float("inf"))
        elif name in ("rolling", "one_shot", "hindsight"):
            g = _rel(ans[name], ref[name])
        elif name == "total":
            g = _rel(np.sum(np.asarray(ans["rolling"], np.float64)),
                     np.sum(ref["rolling"]))
        elif name in ("row_p90", "row_max"):
            prog, want = row_bills(ans["bills"]), ref["row_bill"]
            if prog.shape != want.shape:
                g = float("inf")
            else:
                gap = np.abs(prog - want) / np.abs(want)
                g = float(np.max(gap) if name == "row_max"
                          else np.quantile(gap, 0.9))
        elif name == "ledger":
            led = ans["ledger"]
            g = (float("inf") if led is None else
                 _rel(led.cost.sum((1, 2)), ref["weekly0"]))
        else:
            raise KeyError(f"unknown comparison {name!r}")
        out[name] = g if np.isfinite(g) else float("inf")
    return out
