"""Tests for ``repro.analysis`` — the trace-safety / determinism /
kernel-contract static analyzer.

Three layers:

* **fixtures** — each miniature repo under ``tests/analysis_fixtures/``
  plants exactly one violation; the matching rule (and only that rule)
  must fire, and the ``clean`` fixture must pass every rule.
* **baseline mechanics** — justified suppressions hide a finding, empty
  justifications are a config error (exit 2), stale keys are reported.
* **the repo itself** — ``run_analysis`` over the real repo with the
  shipped ``baseline.json`` must come back clean, and the CLI must exit 1
  when a violation is injected into a scratch tree (the contract the CI
  lint job relies on).
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from repro.analysis import run_analysis
from repro.analysis.engine import load_baseline

FIXTURES = Path(__file__).resolve().parent / "analysis_fixtures"
REPO_ROOT = Path(__file__).resolve().parents[1]

#: fixture dir -> the one rule its planted violation must trigger.
CASES = {
    "r1": "R1",
    "r2": "R2",
    "r3": "R3",
    "r4": "R4",
    "r5": "R5",
    "r5_cadence": "R5",
    "r5_calibration": "R5",
    "r5_policy": "R5",
    "r5_provenance": "R5",
    "r5_scenarios": "R5",
    "r5_telemetry": "R5",
    "r6": "R6",
    "r7": "R7",
    "r7_spans": "R7",
}


class TestFixtures:
    def test_clean_fixture_has_no_findings(self):
        report = run_analysis(FIXTURES / "clean")
        assert report.ok
        assert report.findings == []

    @pytest.mark.parametrize("case", sorted(CASES))
    def test_planted_violation_fires_exactly_its_rule(self, case):
        report = run_analysis(FIXTURES / case)
        assert report.unsuppressed, f"fixture {case}: expected a finding"
        fired = {f.rule for f in report.unsuppressed}
        assert fired == {CASES[case]}, (
            f"fixture {case}: expected only {CASES[case]}, got "
            f"{sorted(fired)}: "
            + "; ".join(f.render() for f in report.unsuppressed)
        )

    @pytest.mark.parametrize("case", sorted(CASES))
    def test_finding_keys_are_line_free(self, case):
        for f in run_analysis(FIXTURES / case).unsuppressed:
            assert f":{f.line}" not in f.key or f.line == 0, (
                f"{f.key}: suppression keys must survive line shifts"
            )


class TestBaseline:
    def _r2_key(self) -> str:
        (finding,) = run_analysis(FIXTURES / "r2").unsuppressed
        return finding.key

    def test_justified_suppression_hides_finding(self, tmp_path):
        bl = tmp_path / "baseline.json"
        bl.write_text(json.dumps({
            "version": 1,
            "suppressions": [
                {"key": self._r2_key(),
                 "justification": "fixture: accepted for the test"},
            ],
        }))
        report = run_analysis(FIXTURES / "r2", baseline_path=bl)
        assert report.ok
        assert len(report.suppressed) == 1
        assert report.unsuppressed == []

    def test_empty_justification_is_config_error(self, tmp_path):
        bl = tmp_path / "baseline.json"
        bl.write_text(json.dumps({
            "version": 1,
            "suppressions": [{"key": self._r2_key(), "justification": ""}],
        }))
        report = run_analysis(FIXTURES / "r2", baseline_path=bl)
        assert report.errors
        assert not report.ok

    def test_stale_suppression_is_reported(self, tmp_path):
        bl = tmp_path / "baseline.json"
        bl.write_text(json.dumps({
            "version": 1,
            "suppressions": [
                {"key": "R2:nonexistent.py:whatever",
                 "justification": "left over from a deleted module"},
            ],
        }))
        report = run_analysis(FIXTURES / "clean", baseline_path=bl)
        assert report.stale_suppressions == ["R2:nonexistent.py:whatever"]
        assert report.ok  # stale entries warn, they don't fail the run

    def test_malformed_baseline_is_config_error(self, tmp_path):
        bl = tmp_path / "baseline.json"
        bl.write_text("{not json")
        _, errors = load_baseline(bl)
        assert errors


class TestRealRepo:
    def test_repo_lints_clean_with_shipped_baseline(self):
        report = run_analysis(REPO_ROOT)
        assert report.ok, "repo must lint clean:\n" + "\n".join(
            f.render() for f in report.unsuppressed
        ) + "\n".join(report.errors)

    def test_shipped_baseline_entries_are_justified(self):
        suppressions, errors = load_baseline(REPO_ROOT / "baseline.json")
        assert errors == []
        assert all(j.strip() for j in suppressions.values())


def _run_cli(root: Path, *extra: str) -> subprocess.CompletedProcess:
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO_ROOT / "src")
    return subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--root", str(root), *extra],
        capture_output=True, text=True, env=env,
    )


class TestCLI:
    def test_exit_zero_on_clean_tree(self):
        proc = _run_cli(FIXTURES / "clean")
        assert proc.returncode == 0, proc.stdout + proc.stderr

    def test_exit_one_on_injected_violation(self, tmp_path):
        # Scratch copy of the clean tree with an R2 violation injected —
        # exactly what the CI lint job must catch.
        scratch = tmp_path / "scratch"
        shutil.copytree(FIXTURES / "clean", scratch)
        bad = scratch / "src" / "repro" / "core" / "leak.py"
        bad.write_text(
            "import time\n\n\ndef now():\n    return time.time()\n"
        )
        proc = _run_cli(scratch, "--json")
        assert proc.returncode == 1, proc.stdout + proc.stderr
        payload = json.loads(proc.stdout)
        assert payload["ok"] is False
        assert {f["rule"] for f in payload["findings"]} == {"R2"}

    def test_exit_two_on_unjustified_baseline(self, tmp_path):
        scratch = tmp_path / "scratch"
        shutil.copytree(FIXTURES / "r2", scratch)
        (scratch / "baseline.json").write_text(json.dumps({
            "version": 1,
            "suppressions": [{"key": "R2:x", "justification": ""}],
        }))
        proc = _run_cli(scratch)
        assert proc.returncode == 2, proc.stdout + proc.stderr

    def test_single_rule_selection(self):
        # r2 fixture analyzed under R1 only: nothing to report.
        proc = _run_cli(FIXTURES / "r2", "--rule", "R1")
        assert proc.returncode == 0, proc.stdout + proc.stderr
