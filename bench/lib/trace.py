"""Reduction of a profiler trace to the benchmark's device numbers.

A trace is read once into a flat list of events, each a dict with
``plane``, ``line``, ``name``, ``start_ns`` and ``dur_ns``.  Everything
below is a pure function of that list, so a small recorded trace in the
tests pins the arithmetic:

* busy: the union of the intervals of device operations ("XLA Ops" lines
  of the ``/device:TPU:n`` planes) inside the window, averaged over the
  devices that ran anything;
* modules: device seconds per compiled module ("XLA Modules" lines),
  keyed by the module name with its trailing ``(id)`` dropped;
* ops: device seconds and launches per operation, named by its HLO
  instruction and kind (``%fusion.87 fusion``); an op inside a loop is
  counted apart from the loop op that encloses it;
* idle gaps: stretches of the window with no device operation, each
  labelled by what the host did at its middle: one of JAX's compile
  phases (its own monitoring spans, moved onto the trace's clock), else
  the innermost of the benchmark's annotations (``scenario_batch``,
  ``plan``).
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
#: The benchmark's own host annotations.
ANNOTATIONS = ("plan", "scenario_batch")


def options():
    """Profiler options: host events without the Python tracer, whose
    millions of frames per plan would swamp the trace."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def op_name(hlo: str) -> str:
    """``%fusion.87 = f32[8]{0} fusion(...), kind=...`` -> ``%fusion.87
    fusion``."""
    name, _, rest = hlo.partition(" = ")
    if not rest:
        return hlo
    if rest.startswith("("):
        depth = 0
        for i, c in enumerate(rest):
            depth += (c == "(") - (c == ")")
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest.split(" ", 1)[1] if " " in rest else ""
    kind = re.match(r"\s*([\w-]+)\(", rest)
    return f"{name} {kind.group(1)}" if kind else name


def load(log_dir: str) -> list[dict]:
    """Events of the newest ``.xplane.pb`` under ``log_dir``: device ops
    and modules, and every host event."""
    import jax

    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = jax.profiler.ProfileData.from_file(paths[-1])
    out = []
    for plane in data.planes:
        device = DEVICE_PLANE.match(plane.name) is not None
        if not device and plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                name = op_name(ev.name) if device else ev.name
                out.append({"plane": plane.name, "line": line.name,
                            "name": name, "start_ns": int(ev.start_ns),
                            "dur_ns": int(ev.duration_ns)})
    return out


def _union(intervals):
    """Merged, sorted [(start, end)] of possibly overlapping intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def module_name(name: str) -> str:
    """``jit_scan(123)`` -> ``jit_scan``."""
    return re.sub(r"\(\d+\)$", "", name)


def window_of(events, annotation: str):
    """(start_ns, end_ns) spanned by the host events named ``annotation``,
    or None."""
    spans = [(e["start_ns"], e["start_ns"] + e["dur_ns"]) for e in events
             if e["plane"] == HOST_PLANE and e["name"] == annotation]
    if not spans:
        return None
    return min(s for s, _ in spans), max(e for _, e in spans)


def reduce(events, window, *, labels=(), top: int = 10) -> dict:
    """Device numbers over ``window`` = (start_ns, end_ns).  ``labels``
    [(name, start_ns, end_ns)] name what the host did, ahead of the
    host events in the trace."""
    lo, hi = window
    win = (hi - lo) / 1e9
    per_device: dict[str, list] = {}
    modules: dict[str, float] = {}
    ops: dict[str, float] = {}
    op_counts: dict[str, int] = {}
    for e in events:
        if DEVICE_PLANE.match(e["plane"]) is None:
            continue
        s, t = _clip(e["start_ns"], e["start_ns"] + e["dur_ns"], lo, hi)
        if t <= s:
            continue
        if e["line"] == OPS_LINE:
            per_device.setdefault(e["plane"], []).append((s, t))
            ops[e["name"]] = ops.get(e["name"], 0.0) + (t - s) / 1e9
            op_counts[e["name"]] = op_counts.get(e["name"], 0) + 1
        elif e["line"] == MODULES_LINE:
            m = module_name(e["name"])
            modules[m] = modules.get(m, 0.0) + (t - s) / 1e9
    busy_by_dev = {d: _union(iv) for d, iv in per_device.items()}
    busy = (sum(sum(b - a for a, b in u) for u in busy_by_dev.values())
            / 1e9 / max(len(busy_by_dev), 1))
    gaps = []
    for u in busy_by_dev.values():
        edges = [lo] + [x for iv in u for x in iv] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((a, b))
    host = [(e["name"], e["start_ns"], e["start_ns"] + e["dur_ns"])
            for e in events if e["plane"] == HOST_PLANE
            and e["name"] in ANNOTATIONS]
    labelled = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        mid = (a + b) // 2
        name = "no host annotation"
        for group in (list(labels), host):
            cover = [x for x in group if x[1] <= mid < x[2]]
            if cover:
                name = min(cover, key=lambda x: x[2] - x[1])[0]
                break
        labelled.append([name, (b - a) / 1e9])
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:top]
    return {
        "window_s": win,
        "busy_s": busy,
        "devices": len(busy_by_dev),
        "modules": modules,
        "ops": ops,
        "op_counts": op_counts,
        "breakdown": {"device_ops": [[k, v] for k, v in top_ops],
                      "idle_gaps": labelled},
    }


def host_spans(events, name: str, window) -> list[float]:
    """Seconds of each host event ``name`` inside ``window``."""
    lo, hi = window
    return [e["dur_ns"] / 1e9 for e in events
            if e["plane"] == HOST_PLANE and e["name"] == name
            and lo <= e["start_ns"] < hi]


class CompileWatch:
    """JAX's own compile events (tracing, lowering, backend compile —
    which includes persistent-cache reads) as time spans, and the cache's
    hit and miss counts."""

    SPANS = ("/jax/core/compile/jaxpr_trace_duration",
             "/jax/core/compile/jaxpr_to_mlir_module_duration",
             "/jax/core/compile/backend_compile_duration")
    COUNTS = ("/jax/compilation_cache/cache_hits",
              "/jax/compilation_cache/cache_misses")

    def __init__(self):
        import jax

        self.spans: list[tuple[str, float, float]] = []
        self.counts: list[tuple[str, float]] = []
        jax.monitoring.register_event_time_span_listener(self._span)
        jax.monitoring.register_event_listener(self._event)

    def _span(self, event, start, end, **_):
        if event in self.SPANS:
            self.spans.append((event, start, end))

    def _event(self, event, **_):
        if event in self.COUNTS:
            import time

            self.counts.append((event, time.time()))

    def labels(self, offset_ns: int) -> list:
        """[(phase, start_ns, end_ns)] of every compile span on a clock
        that reads ``offset_ns`` ahead of ``time.time()``."""
        return [(e.rsplit("/", 1)[1].replace("_duration", ""),
                 int(s * 1e9) + offset_ns, int(t * 1e9) + offset_ns)
                for e, s, t in self.spans]

    def summary(self, lo: float, hi: float) -> dict:
        """Over wall-clock [lo, hi): seconds per kind, the union of all
        three (nested traces are not counted twice), backend compiles
        (cache reads included), and cache hits and misses."""
        inside = [(e, max(s, lo), min(t, hi)) for e, s, t in self.spans
                  if t > lo and s < hi]
        out = {k.rsplit("/", 1)[1]: sum(t - s for e, s, t in inside if e == k)
               for k in self.SPANS}
        out["union_s"] = sum(b - a for a, b in _union(
            [(s, t) for _, s, t in inside]))
        out["backend_compiles"] = sum(
            1 for e, _, _ in inside if e == self.SPANS[2])
        for k in self.COUNTS:
            out[k.rsplit("/", 1)[1]] = sum(
                1 for e, t in self.counts if e == k and lo <= t < hi)
        return out
