"""The program's own host stages in a traced run.

The planner marks its host stages with ``repro.obs.spans.stage``: a
``jax.profiler.TraceAnnotation`` each (``api/plan``, ``replan``,
``replan/...``), on the device trace's clock, with its byte counters
(``h2d_bytes``, ``d2h_bytes``) as the event's stats.  The readers of the
stage metrics take them from the profile the harness writes under the
checkout (``.bench_trace/``), which is still there while they read.  A
profile whose ``plan`` window is not the one the harness reduced for the
record belongs to another run, and gives nothing to read; so does a
program that marks no stages.
"""

from __future__ import annotations

import glob
import os
import re

from lib import trace as tr

#: The harness's profiles, under the root of the checkout.
TRACE_DIR = ".bench_trace"
#: The program's own stages.
PROGRAM = re.compile(r"^(replan|api/plan)(/|$)")

_loaded: dict = {}


def load_host(log_dir: str) -> list[dict]:
    """Host events of the newest ``.xplane.pb`` under ``log_dir``, each
    with its ``stats``; none where there is no profile.  The last file
    read is kept, since every reader of one run asks for it."""
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        return []
    st = os.stat(paths[-1])
    key = (paths[-1], st.st_mtime_ns, st.st_size)
    if key not in _loaded:
        import jax

        data = jax.profiler.ProfileData.from_file(paths[-1])
        out = []
        for plane in data.planes:
            if plane.name != tr.HOST_PLANE:
                continue
            for line in plane.lines:
                out.extend({"plane": plane.name, "line": line.name,
                            "name": ev.name, "start_ns": int(ev.start_ns),
                            "dur_ns": int(ev.duration_ns),
                            "stats": dict(ev.stats)} for ev in line.events)
        _loaded.clear()
        _loaded[key] = out
    return _loaded[key]


def _program(events, window):
    lo, hi = window
    return [e for e in events if e["plane"] == tr.HOST_PLANE
            and PROGRAM.match(e["name"]) and lo <= e["start_ns"] < hi]


def spans(events, window) -> dict[str, list[float]]:
    """Stage name -> seconds of each of the program's stages that opened
    inside ``window``."""
    out: dict[str, list[float]] = {}
    for e in _program(events, window):
        out.setdefault(e["name"], []).append(e["dur_ns"] / 1e9)
    return out


def counters(events, window) -> dict[str, int]:
    """Counter name -> its sum over the program's stages that opened
    inside ``window`` (the stages' integer event stats)."""
    out: dict[str, int] = {}
    for e in _program(events, window):
        for k, v in e.get("stats", {}).items():
            if isinstance(v, int):
                out[k] = out.get(k, 0) + v
    return out


def of(record: dict, reader: str):
    """``{"spans": ..., "counters": ...}`` over the traced plans of
    ``record``, from the profile of the checkout that holds ``reader``
    (a reader's ``__file__``, under ``bench/metrics/``); None where that
    profile is missing or is not the record's."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(reader))))
    events = load_host(os.path.join(root, TRACE_DIR))
    window = tr.window_of(events, "plan")
    if window is None or not record.get("plans") or (
            (window[1] - window[0]) / 1e9
            != record.get("trace", {}).get("window_s")):
        return None
    return {"spans": spans(events, window),
            "counters": counters(events, window)}


def seconds_per_plan(record: dict, reader: str, stage: str):
    """Seconds per traced plan in ``stage``, or None where it is absent."""
    got = of(record, reader)
    if got is None or not got["spans"].get(stage):
        return None
    return sum(got["spans"][stage]) / record["plans"]


def gb_per_plan(record: dict, reader: str, counter: str):
    """``counter``'s bytes per traced plan in gigabytes (1e9 bytes), or
    None where no stage carries it."""
    got = of(record, reader)
    if got is None or counter not in got["counters"]:
        return None
    return got["counters"][counter] / 1e9 / record["plans"]
