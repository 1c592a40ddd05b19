"""A configuration's purchase options and market terms, worked out from
the numbers its file states (paper Table 2 and the spot, convertible and
generation tables).  Both the request the benchmark sends and the plain
reference take them from here, so the program's own pricing tables
never enter the yardstick."""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Option:
    name: str
    cloud: str
    rate: float
    term_weeks: int


def mean_discount_3y(pricing: dict) -> float:
    plans = pricing["savings_plans"]
    return sum(p["discount_3y"] for p in plans) / len(plans)


def od_rate(pricing: dict) -> float:
    """On-demand price in units of the mean 3y committed price."""
    return 1.0 / (1.0 - mean_discount_3y(pricing))


def options(pricing: dict) -> list[Option]:
    """Pool-pinned SKUs: 1y and 3y per Table-2 row, rates normalised so
    the mean 3y committed rate is 1."""
    base = 1.0 - mean_discount_3y(pricing)
    terms = pricing["terms_weeks"]
    out = []
    for p in pricing["savings_plans"]:
        out.append(Option(f"{p['cloud']}/{p['family']}/1y", p["cloud"],
                          (1.0 - p["discount_1y"]) / base, terms["1y"]))
        out.append(Option(f"{p['cloud']}/{p['family']}/3y", p["cloud"],
                          (1.0 - p["discount_3y"]) / base, terms["3y"]))
    return out


def convertible_options(pricing: dict, clouds) -> list[Option]:
    """Per-cloud exchangeable SKUs: the cloud's mean standard discount
    less its haircut, for each cloud present, in sorted order."""
    base = 1.0 - mean_discount_3y(pricing)
    terms = pricing["terms_weeks"]
    cuts = {h["cloud"]: h for h in pricing["convertible_haircuts"]}
    out = []
    for c in sorted(set(clouds)):
        rows = [p for p in pricing["savings_plans"] if p["cloud"] == c]
        d1 = sum(p["discount_1y"] for p in rows) / len(rows)
        d3 = sum(p["discount_3y"] for p in rows) / len(rows)
        out.append(Option(f"{c}/convertible/1y", c,
                          (1.0 - (d1 - cuts[c]["haircut_1y"])) / base,
                          terms["1y"]))
        out.append(Option(f"{c}/convertible/3y", c,
                          (1.0 - (d3 - cuts[c]["haircut_3y"])) / base,
                          terms["3y"]))
    return out


def spot_line(cfg: dict, cloud: str, od: float) -> tuple[float, float]:
    """(effective rate, volume cap) of a pool's spot band on ``cloud``:
    stationary availability a = recovery / (hazard + recovery),
    eff = a (spot price + hazard requeue od) + (1 - a) od, and the chance
    constraint cap = (1 - target) / (1 - a) (1 - buffer), clipped to
    [0, 1] and 0 where spot is no cheaper than on-demand."""
    m = {r["cloud"]: r for r in cfg["pricing"]["spot_markets"]}[cloud]
    s = cfg["spot"]
    a = m["recovery_per_hour"] / max(
        m["hazard_per_hour"] + m["recovery_per_hour"], 1e-12)
    eff = (a * ((1.0 - m["discount"]) * od
                + m["hazard_per_hour"] * s["requeue_hours"] * od)
           + (1.0 - a) * od)
    cap = min(max((1.0 - s["availability_target"]) / max(1.0 - a, 1e-9)
                  * (1.0 - s["risk_buffer"]), 0.0), 1.0)
    return eff, (cap if eff < od else 0.0)
