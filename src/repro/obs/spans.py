"""Spans: the program's host stages on the profiler's clock, and the
caller-side wall-clock span recorder (the observability layer's host
timer).

The planner core is wall-clock-free by contract — analysis rule R2 bans
clock reads from ``core/``/``capacity/``/``kernels/``/``data/``/``serve/``,
and rule R7 extends the ban to the whole of ``src/repro`` — so *this
module* is the single sanctioned place a wall-clock is read.

Program code marks where its host work happens with :func:`stage`:

    with obs_spans.stage("replan/place_rows", h2d_bytes=batch.nbytes):
        demand = mesh_mod.shard_rows(batch)

A stage opens a ``jax.profiler.TraceAnnotation`` of the same name, so
under ``jax.profiler`` it lands on the ``/host:CPU`` plane of the device
trace, on the trace's own clock, with its integer *counts* (bytes moved,
say) as the event's stats.  When a :class:`SpanRecorder` has been made
active with :func:`recording`, the stage is also recorded there, counts
included:

    rec = SpanRecorder()
    with obs_spans.recording(rec):
        report = api.plan(request)
    print(rec.report())

With neither a profiler nor an active recorder a stage costs one
inactive ``TraceMe``: it reads no clock and allocates no :class:`Span`.
Stages bracket *host* calls only; a stage inside a traced function would
fire once, at trace time (rule R7 flags it).

Callers that own a recorder can still pass it explicitly:
``span(recorder, name, phase)`` shares the stage's implementation, so
the tournament's spans appear in traces too, and ``span(None, ...)``
records nothing.  Spans nest (the recorder keeps a stack, so
``report()`` renders a tree) and carry a coarse *phase* tag —
``"compile"`` (tracing + XLA compile), ``"execute"`` (device compute),
``"host"`` (numpy/report assembly, I/O) — the three buckets a JAX
program's wall time actually splits into.  A span changes when the
machine does, a golden never.
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import json
import time

import jax

PHASES = ("compile", "execute", "host")


@dataclasses.dataclass
class Span:
    """One recorded interval.  ``parent`` indexes into the recorder's span
    list (-1 for roots); ``depth`` is the nesting level at entry."""

    name: str
    phase: str
    start_s: float
    duration_s: float = 0.0
    depth: int = 0
    parent: int = -1
    counts: dict = dataclasses.field(default_factory=dict)

    def to_dict(self) -> dict:
        return {
            "name": self.name,
            "phase": self.phase,
            "start_s": self.start_s,
            "duration_s": self.duration_s,
            "depth": self.depth,
            "parent": self.parent,
            "counts": dict(self.counts),
        }


class SpanRecorder:
    """Append-only wall-clock span log with a nesting stack.

    The clock defaults to ``time.perf_counter`` (monotonic, high
    resolution); tests inject a fake clock to keep themselves
    deterministic."""

    def __init__(self, clock=time.perf_counter):
        self._clock = clock
        self.spans: list[Span] = []
        self._stack: list[int] = []

    @contextlib.contextmanager
    def span(self, name: str, phase: str = "host", **counts: int):
        """Record ``name``, with its ``counts``, for the duration of the
        ``with`` body."""
        if phase not in PHASES:
            raise ValueError(f"unknown phase {phase!r}; known: {PHASES}")
        idx = len(self.spans)
        self.spans.append(Span(
            name=name, phase=phase, start_s=self._clock(),
            depth=len(self._stack),
            parent=self._stack[-1] if self._stack else -1,
            counts=counts,
        ))
        self._stack.append(idx)
        try:
            yield self.spans[idx]
        finally:
            self._stack.pop()
            self.spans[idx].duration_s = (
                self._clock() - self.spans[idx].start_s
            )

    # -- summaries ---------------------------------------------------------

    @property
    def total_s(self) -> float:
        """Wall time covered by root spans (nested spans not double-counted)."""
        return sum(s.duration_s for s in self.spans if s.parent == -1)

    def summary(self) -> dict[str, dict]:
        """name -> {count, total_s, mean_s, phase, counts} over all spans
        (``counts`` sums each counter over the spans of that name)."""
        out: dict[str, dict] = {}
        for s in self.spans:
            agg = out.setdefault(
                s.name,
                {"count": 0, "total_s": 0.0, "phase": s.phase, "counts": {}},
            )
            agg["count"] += 1
            agg["total_s"] += s.duration_s
            for k, v in s.counts.items():
                agg["counts"][k] = agg["counts"].get(k, 0) + v
        for agg in out.values():
            agg["mean_s"] = agg["total_s"] / agg["count"]
        return out

    def by_phase(self) -> dict[str, float]:
        """phase -> total seconds (nested spans attributed to their own
        phase; a parent's *self* time is its duration minus its children)."""
        child_time: dict[int, float] = {}
        for s in self.spans:
            if s.parent >= 0:
                child_time[s.parent] = (
                    child_time.get(s.parent, 0.0) + s.duration_s
                )
        out = {p: 0.0 for p in PHASES}
        for i, s in enumerate(self.spans):
            self_s = s.duration_s - child_time.get(i, 0.0)
            out[s.phase] += max(self_s, 0.0)
        return out

    def report(self) -> str:
        """The span tree, one line per span, indented by nesting depth."""
        lines = ["span                                   phase     seconds"]
        for s in self.spans:
            label = "  " * s.depth + s.name
            counts = "".join(f"  {k}={v:,}" for k, v in s.counts.items())
            lines.append(
                f"{label:38s} {s.phase:9s} {s.duration_s:9.4f}{counts}"
            )
        for p, t in self.by_phase().items():
            lines.append(f"{'total ' + p:38s} {'':9s} {t:9.4f}")
        return "\n".join(lines)

    def to_dicts(self) -> list[dict]:
        return [s.to_dict() for s in self.spans]

    def to_json(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(
                {"spans": self.to_dicts(), "by_phase": self.by_phase()},
                f, indent=2,
            )


#: The recorder :func:`stage` records into, set by :func:`recording`.
_ACTIVE: contextvars.ContextVar[SpanRecorder | None] = contextvars.ContextVar(
    "repro_obs_recorder", default=None
)


@contextlib.contextmanager
def recording(recorder: SpanRecorder):
    """Make ``recorder`` the one every :func:`stage` inside the ``with``
    body records into (this thread or task only)."""
    token = _ACTIVE.set(recorder)
    try:
        yield recorder
    finally:
        _ACTIVE.reset(token)


@contextlib.contextmanager
def _interval(recorder, name: str, phase: str, counts: dict):
    """One span: a profiler annotation, and a recorded :class:`Span` when
    ``recorder`` is not None."""
    if phase not in PHASES:
        raise ValueError(f"unknown phase {phase!r}; known: {PHASES}")
    with jax.profiler.TraceAnnotation(name, **counts):
        if recorder is None:
            yield None
        else:
            with recorder.span(name, phase=phase, **counts) as s:
                yield s


@contextlib.contextmanager
def stage(name: str, phase: str = "host", **counts: int):
    """A host stage of the program: a profiler annotation named ``name``
    whose stats are ``counts``, recorded by the recorder active when the
    stage opens, if there is one."""
    with _interval(_ACTIVE.get(), name, phase, counts) as s:
        yield s


def span(recorder: SpanRecorder | None, name: str, phase: str = "host"):
    """A stage recorded by ``recorder`` (an explicit one, not the active
    one); ``span(None, ...)`` annotates the trace and records nothing."""
    return _interval(recorder, name, phase, {})
