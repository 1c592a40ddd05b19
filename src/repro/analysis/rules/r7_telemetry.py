"""R7 — telemetry hygiene.

Two contracts introduced with the observability layer (``repro.obs``):

* **No side-channel output in traced code.**  ``print`` / ``logging.*``
  calls inside a traced function (scan bodies, jit/vmap targets, kernels —
  the same :class:`~repro.analysis.callgraph.CallGraph` set R1 walks) fire
  at *trace time*, not per step: they print once during compilation and
  then never again, which reads as telemetry but measures nothing.  Real
  per-step observability flows through the cost-attribution ledger
  (``telemetry=`` on the planner) or host-side callbacks — never ambient
  stdout from inside a trace.  The same holds for the span profiler's
  ``span``/``stage``: inside a trace they bracket tracing, once, not the
  step, so they belong round host calls only.

* **``repro.obs.spans`` is the only wall-clock entry point.**  R2 already
  bans clock reads from the determinism-scoped packages; R7 extends the
  ban to *all* of ``src/repro`` so timing is uniformly recorded as spans
  (``SpanRecorder``) instead of ad-hoc ``time.time()`` pairs — one
  profiler, one report format, one place a clock is read.  The single
  sanctioned read site is ``repro/obs/spans.py`` itself.

Benchmarks and examples live outside ``src/`` and are not scanned; they
are the intended *consumers* of the span profiler, not subjects of it.
"""

from __future__ import annotations

import ast

from repro.analysis.astutils import dotted
from repro.analysis.callgraph import CallGraph
from repro.analysis.engine import Finding, Rule
from repro.analysis.rules.r2_determinism import (
    CLOCK_CALLS,
    _in_scope as _r2_scope,
)

#: the one module allowed to read a wall clock (the span profiler).
CLOCK_ALLOWLIST = ("src/repro/obs/spans.py",)

#: the span profiler's context managers, under every import spelling.
SPAN_CALLS = frozenset(
    f"{mod}.{fn}" for mod in ("repro.obs", "repro.obs.spans")
    for fn in ("span", "stage")
)


def _logging_target(node: ast.Call, imports) -> str | None:
    """Resolve ``logging.info(...)``-style calls; None if not logging."""
    name = dotted(node.func)
    if name is None:
        return None
    full = imports.resolve(name)
    if full == "logging" or full.startswith("logging."):
        return full
    return None


def run(ctx) -> list[Finding]:
    findings: list[Finding] = []

    # -- (a) print/logging inside traced functions -------------------------
    graph = CallGraph(ctx)
    for tf in graph.traced:
        info = tf.module
        rel = ctx.relpath(info.path)
        fname = tf.name
        body = tf.node.body if isinstance(tf.node.body, list) \
            else [ast.Expr(tf.node.body)]

        # Nested defs are traced in their own right; don't double-report.
        nested: set[int] = set()
        for stmt in body:
            for node in ast.walk(stmt):
                if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                     ast.Lambda)) and node is not tf.node:
                    for sub in ast.walk(node):
                        nested.add(id(sub))
                    nested.discard(id(node))

        def emit(node, detail, message):
            findings.append(Finding(
                rule="R7", file=rel, line=getattr(node, "lineno", 0),
                key=f"R7:{rel}:{fname}:{detail}",
                message=f"in traced `{fname}` ({tf.entry}): {message}",
            ))

        for stmt in body:
            for node in ast.walk(stmt):
                if id(node) in nested or not isinstance(node, ast.Call):
                    continue
                callee = node.func
                if isinstance(callee, ast.Name) and callee.id == "print":
                    emit(node, "print",
                         "`print()` inside a trace fires once at compile "
                         "time, not per step; route telemetry through the "
                         "ledger/spans instead")
                    continue
                name = dotted(callee)
                full = info.imports.resolve(name) if name else None
                if full in SPAN_CALLS:
                    emit(node, full,
                         f"`{name}()` inside a trace brackets tracing, "
                         "once, not the step; open spans and stages round "
                         "host calls only")
                    continue
                log = _logging_target(node, info.imports)
                if log is not None:
                    emit(node, log,
                         f"`{log}()` inside a trace fires at compile time, "
                         "not per step; it is not telemetry")

    # -- (b) wall-clock reads outside repro.obs.spans ----------------------
    for info in ctx.modules.values():
        rel = ctx.relpath(info.path)
        # R2 already polices its determinism scopes; the span profiler is
        # the sanctioned read site.
        if _r2_scope(rel) or rel in CLOCK_ALLOWLIST:
            continue
        imports = info.imports

        def cemit(node, detail, message):
            findings.append(Finding(
                rule="R7", file=rel, line=getattr(node, "lineno", 0),
                key=f"R7:{rel}:{detail}",
                message=message,
            ))

        for node in ast.walk(info.tree):
            if isinstance(node, ast.ImportFrom) and node.module == "time":
                for a in node.names:
                    if f"time.{a.name}" in CLOCK_CALLS:
                        cemit(node, f"import-time.{a.name}",
                              f"`from time import {a.name}`: wall-clock "
                              "reads belong in repro.obs.spans "
                              "(SpanRecorder), the one sanctioned timer")

        handled: set[int] = set()
        for node in ast.walk(info.tree):
            if isinstance(node, ast.Call):
                name = dotted(node.func)
                if name is None:
                    continue
                full = imports.resolve(name)
                if full in CLOCK_CALLS:
                    handled.add(id(node.func))
                    cemit(node, full,
                          f"`{full}()` outside repro.obs.spans; record a "
                          "span with SpanRecorder instead of an ad-hoc "
                          "timer")
        for node in ast.walk(info.tree):
            if isinstance(node, ast.Attribute) and id(node) not in handled:
                name = dotted(node)
                if name is None:
                    continue
                full = imports.resolve(name)
                if full in CLOCK_CALLS:
                    cemit(node, full,
                          f"reference to wall-clock `{full}` outside "
                          "repro.obs.spans")
    return findings


rule = Rule(
    id="R7",
    title="telemetry hygiene: no prints in traces, spans own the clock",
    run=run,
)
