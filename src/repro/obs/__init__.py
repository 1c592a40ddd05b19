"""Observability layer: cost-attribution ledger, forecast calibration,
decision provenance, span profiler, kernel stats.

Five layers, all strictly outside the traced planning core (rules
R2/R7):

- ``obs.ledger`` — :class:`~repro.obs.ledger.CostLedger`, the per-week x
  per-pool x per-source billing decomposition materialized from a
  telemetry-enabled rolling replay; JSONL export, ``diff`` comparator,
  unit-economics summaries.
- ``obs.calibration`` — :class:`~repro.obs.calibration.CalibrationCube`,
  the per (week x pool x fractile) forecast-calibration scores (hit
  coverage vs nominal, pinball loss, band widths) scored against the
  demand the scan billed; same JSONL round-trip + ``diff`` guarantees.
- ``obs.provenance`` — :class:`~repro.obs.provenance.DecisionLog`, the
  queryable per-week decision record (buys per SKU, roll-offs, binding
  constraints) answering "why does week w hold this stack".
- ``obs.spans`` — :func:`~repro.obs.spans.stage`, the planner's host
  stages as profiler annotations with byte counters, and
  :class:`~repro.obs.spans.SpanRecorder`, the sanctioned wall clock
  (compile / execute / host phases), which records those stages inside
  ``with obs.recording(rec):``.
- ``obs.kernelstats`` — :class:`~repro.obs.kernelstats.KernelStats` for
  the Pallas commitment-sweep launch shapes.

Enable per request: ``api.PlanRequest(..., telemetry=True)`` or
``telemetry=obs.TelemetryConfig(calibration=True, provenance=True)``;
``telemetry=None`` (the default) keeps every plan path bit-identical.
``python -m repro.obs`` reports/diffs exported ledgers and calibration
cubes.
"""

from repro.obs.calibration import (
    CalibrationCube,
    CalibrationDiff,
    calibration_from_arrays,
)
from repro.obs.config import TelemetryConfig, resolve_telemetry
from repro.obs.kernelstats import KernelStats, sweep_kernel_stats
from repro.obs.ledger import CostLedger, LedgerDiff, ledger_from_report
from repro.obs.provenance import DecisionLog, decision_log_from_arrays
from repro.obs.spans import Span, SpanRecorder, recording, span, stage

__all__ = [
    "TelemetryConfig",
    "resolve_telemetry",
    "KernelStats",
    "sweep_kernel_stats",
    "CostLedger",
    "LedgerDiff",
    "ledger_from_report",
    "CalibrationCube",
    "CalibrationDiff",
    "calibration_from_arrays",
    "DecisionLog",
    "decision_log_from_arrays",
    "Span",
    "SpanRecorder",
    "recording",
    "span",
    "stage",
]
