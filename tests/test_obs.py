"""Observability layer (``repro.obs``): telemetry goldens, the
cost-attribution ledger, span profiler, kernel stats, scenario replay,
the CLI, and bench provenance.

The load-bearing guarantee is bit-identity: ``telemetry=None`` (the
default) must reproduce the pre-telemetry planner exactly — the scan
only emits its extra ledger outputs when telemetry is on, so the
disabled program is the same compiled program.  The goldens below cover
every registry policy and every spot/migration/convertible band
combination the planner exposes, held to the python-loop replay and to
facts no toolchain moves; ``telemetry=True`` must then reproduce
the same totals bitwise (extra scan outputs, same billing math), and the
ledger it materializes must reconcile with the report's weekly costs to
f32 machine precision.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.capacity import simulator as sim
from repro.core import api
from repro.core import planner as pl
from repro.core import replan
from repro.data import scenarios as sc
from repro.data import traces
from repro.core import demand as dmnd
from repro.obs import (
    CalibrationCube,
    CostLedger,
    KernelStats,
    SpanRecorder,
    TelemetryConfig,
    ledger_from_report,
    resolve_telemetry,
    sweep_kernel_stats,
)
from repro.obs.__main__ import main as obs_cli

REPO_ROOT = Path(__file__).resolve().parents[1]

#: cadence="weekly" is the explicit disabled spelling of the breach
#: cadence — the goldens below prove it stays bit-identical.
ROLLING = dict(cadence_weeks=2, start_weeks=6, horizon_weeks=4,
               compare=False, cadence="weekly")

#: Relative gap allowed between the scan replay and the python-loop
#: replay (``backend="loop"``): they differ only in the float32 summation
#: order of the prefix normal equations.
LOOP_RTOL = 2e-4

#: policy:s<spot>m<migration>c<convertible> -> decision weeks of the
#: golden replay (weeks 6..19, cadence 2): every week for the forecast-free
#: hedges and hindsight, once for one-shot, every other week for the
#: paper's policy.  Totals are not pinned — the toolchain and the CPU move
#: them — but held to the loop replay.
GOLDENS = {
    "deterministic_hedge:s0m0c0": 14,
    "hindsight:s0m0c0": 14,
    "one_shot:s0m0c0": 1,
    "one_shot:s0m0c1": 1,
    "one_shot:s0m1c0": 1,
    "one_shot:s0m1c1": 1,
    "one_shot:s1m0c0": 1,
    "one_shot:s1m0c1": 1,
    "one_shot:s1m1c0": 1,
    "one_shot:s1m1c1": 1,
    "randomized_hedge:s0m0c0": 14,
    "rolling_portfolio:s0m0c0": 7,
    "rolling_portfolio:s0m0c1": 7,
    "rolling_portfolio:s0m1c0": 7,
    "rolling_portfolio:s0m1c1": 7,
    "rolling_portfolio:s1m0c0": 7,
    "rolling_portfolio:s1m0c1": 7,
    "rolling_portfolio:s1m1c0": 7,
    "rolling_portfolio:s1m1c1": 7,
}

_POOLS_CACHE: dict[bool, object] = {}


def _pools(migration_fleet: bool):
    """The golden fleets: migration fleets need an even pool count."""
    if migration_fleet not in _POOLS_CACHE:
        _POOLS_CACHE[migration_fleet] = (
            traces.synthetic_pool_set(num_pools=4, num_hours=24 * 7 * 20,
                                      migration=True)
            if migration_fleet
            else traces.synthetic_pool_set(num_pools=3,
                                           num_hours=24 * 7 * 20)
        )
    return _POOLS_CACHE[migration_fleet]


def _run_case(policy, s, m, c, **extra):
    pools = _pools(bool(m or c))
    return replan.replan_fleet_pools(
        pools, policy=policy, spot=bool(s), migration=bool(m),
        convertible=bool(c), **ROLLING, **extra,
    )


class TestTelemetryNoneGolden:
    """telemetry=None keeps every policy x band path the plain program:
    the disabled spellings agree bit for bit, nothing telemetry-shaped is
    materialized, and the totals hold to the loop replay."""

    @pytest.mark.parametrize("case", sorted(GOLDENS))
    def test_default_path_matches_pre_telemetry_golden(self, case):
        policy, bands = case.split(":")
        s, m, c = int(bands[1]), int(bands[3]), int(bands[5])
        rep = _run_case(policy, s, m, c, telemetry=None)
        off = _run_case(policy, s, m, c, telemetry=False)
        assert off.total_cost == rep.total_cost
        np.testing.assert_array_equal(
            np.asarray(off.targets), np.asarray(rep.targets)
        )
        np.testing.assert_array_equal(rep.weeks, np.arange(6, 20))
        assert int(np.asarray(rep.decision_mask).sum()) == GOLDENS[case]
        assert np.asarray(rep.targets).shape[:2] == (14, 4 if m or c else 3)
        loop = _run_case(policy, s, m, c, telemetry=None, backend="loop")
        np.testing.assert_allclose(
            rep.total_cost, loop.total_cost, rtol=LOOP_RTOL
        )
        np.testing.assert_allclose(
            float(np.asarray(rep.targets).sum()),
            float(np.asarray(loop.targets).sum()), rtol=LOOP_RTOL,
        )
        # The disabled path must carry no telemetry artifacts at all.
        assert rep.ledger is None
        assert rep.committed_by_sku is None
        assert rep.kernel_stats is None
        assert rep.calibration is None
        assert rep.decision_log is None
        assert rep.fractile_levels is None
        assert rep.breach_band_lo is None and rep.breach_band_hi is None
        assert rep.cadence == "weekly"

    def test_calibration_provenance_off_is_bitwise_identical(self):
        """The ledger-only telemetry spelling — calibration=False,
        provenance=False — must match the goldens' telemetry=None path
        bitwise; the new instruments only exist when asked for."""
        off = _run_case("rolling_portfolio", 1, 1, 1, telemetry=None)
        on = _run_case(
            "rolling_portfolio", 1, 1, 1,
            telemetry=TelemetryConfig(calibration=False, provenance=False),
        )
        assert on.total_cost == off.total_cost
        np.testing.assert_array_equal(
            np.asarray(on.weekly_cost), np.asarray(off.weekly_cost)
        )
        assert on.calibration is None and on.decision_log is None

    def test_telemetry_on_is_bitwise_identical(self):
        off = _run_case("rolling_portfolio", 1, 1, 1, telemetry=None)
        on = _run_case("rolling_portfolio", 1, 1, 1, telemetry=True)
        assert on.total_cost == off.total_cost  # bitwise, not approx
        np.testing.assert_array_equal(
            np.asarray(on.targets), np.asarray(off.targets)
        )
        np.testing.assert_array_equal(
            np.asarray(on.weekly_cost), np.asarray(off.weekly_cost)
        )
        assert on.ledger is not None and off.ledger is None


@pytest.fixture(scope="module")
def rep_full():
    """All-bands telemetry-enabled report on the drifting migration
    fleet — the acceptance configuration."""
    return _run_case("rolling_portfolio", 1, 1, 1, telemetry=True)


class TestCostLedger:
    def test_reconciles_with_report_weekly_costs(self, rep_full):
        res = rep_full.ledger.reconcile(rep_full)
        assert res["ok"], res
        assert res["max_rel"] <= 1e-5
        np.testing.assert_allclose(
            res["total_ledger"], rep_full.total_cost, rtol=1e-6
        )

    def test_sources_cover_every_band(self, rep_full):
        led = rep_full.ledger
        srcs = set(led.sources)
        assert "on_demand" in srcs
        assert {"spot_market", "spot_requeue", "spot_fallback"} <= srcs
        assert any(s.startswith("commit:") for s in srcs)
        assert any(s.startswith("convertible:") for s in srcs)
        assert any(e.startswith("cloud:") for e in led.entities)

    def test_attribute_slices_sum_to_total(self, rep_full):
        led = rep_full.ledger
        total = led.attribute()
        np.testing.assert_allclose(total, led.total, rtol=1e-12)
        by_week = sum(
            led.attribute(week=int(w)) for w in led.weeks
        )
        np.testing.assert_allclose(by_week, total, rtol=1e-9)
        by_entity = sum(led.attribute(pool=e) for e in led.entities)
        np.testing.assert_allclose(by_entity, total, rtol=1e-9)
        np.testing.assert_allclose(
            sum(led.by_source().values()), total, rtol=1e-9
        )

    def test_attribute_unknown_selectors_raise(self, rep_full):
        led = rep_full.ledger
        with pytest.raises(KeyError):
            led.attribute(pool="not/a/pool")
        with pytest.raises(KeyError):
            led.attribute(source="not_a_source")
        with pytest.raises(KeyError):
            led.attribute(week=10**6)

    def test_unit_economics_shape(self, rep_full):
        econ = rep_full.ledger.unit_economics()
        np.testing.assert_allclose(
            econ["total_cost"], rep_full.ledger.total, rtol=1e-12
        )
        assert 0.0 <= econ["idle_fraction"] <= 1.0
        assert 0.0 < econ["utilization_mean"] <= 1.0
        assert econ["cost_per_used_chip_hour"] > 0.0
        parts = (econ["committed_cost"] + econ["convertible_cost"]
                 + econ["on_demand_cost"] + econ["spot_cost"])
        np.testing.assert_allclose(parts, econ["total_cost"], rtol=1e-9)

    def test_jsonl_roundtrip_is_exact(self, rep_full, tmp_path):
        led = rep_full.ledger
        path = str(tmp_path / "ledger.jsonl")
        led.to_jsonl(path)
        back = CostLedger.from_jsonl(path)
        assert back.entities == led.entities
        assert back.sources == led.sources
        np.testing.assert_array_equal(back.cost, led.cost)
        np.testing.assert_array_equal(back.volume, led.volume)
        np.testing.assert_array_equal(back.used_hours, led.used_hours)
        assert led.diff(back).max_abs_delta == 0.0

    def test_diff_pinpoints_a_perturbed_cell(self, rep_full, tmp_path):
        import dataclasses

        led = rep_full.ledger
        cost2 = led.cost.copy()
        ei = 0
        mi = led.sources.index("on_demand")
        cost2[:, ei, mi] += 100.0
        other = dataclasses.replace(led, cost=cost2)
        diff = other.diff(led)
        n_weeks = len(led.weeks)
        np.testing.assert_allclose(diff.total_delta, 100.0 * n_weeks)
        e, s, d = diff.top_movers(1)[0]
        assert (e, s) == (led.entities[ei], "on_demand")
        np.testing.assert_allclose(d, 100.0 * n_weeks)
        assert "on_demand" in diff.report()

    def test_ledger_requires_telemetry(self):
        rep = _run_case("rolling_portfolio", 0, 0, 0, telemetry=None)
        with pytest.raises(ValueError, match="telemetry"):
            ledger_from_report(rep)


class TestRequestSurfaces:
    def test_plan_request_threads_telemetry(self):
        pools = traces.synthetic_pool_set(num_pools=2, num_hours=24 * 7 * 12)
        req = api.PlanRequest(
            pools=pools, mode="rolling", telemetry=True,
            rolling=api.RollingConfig(cadence_weeks=2, start_weeks=4,
                                      compare=False),
            horizon_weeks=4,
        )
        rep = api.plan(req)
        assert rep.ledger is not None
        assert rep.ledger.reconcile(rep)["ok"]

    def test_one_shot_telemetry_is_a_construction_error(self):
        pools = traces.synthetic_pool_set(num_pools=2, num_hours=24 * 7 * 12)
        with pytest.raises(ValueError, match="rolling"):
            api.PlanRequest(pools=pools, mode="one_shot", telemetry=True)
        with pytest.raises(TypeError, match="rolling"):
            pl.plan_fleet_pools(pools, mode="one_shot", telemetry=True)

    def test_resolve_telemetry_spellings(self):
        assert resolve_telemetry(None) is None
        assert resolve_telemetry(False) is None
        cfg = resolve_telemetry(True)
        assert isinstance(cfg, TelemetryConfig) and cfg.ledger
        same = TelemetryConfig(ledger=True, kernel_stats=False)
        assert resolve_telemetry(same) is same
        assert resolve_telemetry(
            TelemetryConfig(ledger=False, kernel_stats=False)
        ) is None
        with pytest.raises(TypeError):
            resolve_telemetry(1.5)


class TestSpans:
    def _fake_clock(self):
        state = {"t": 0.0}

        def clock():
            state["t"] += 1.0
            return state["t"]

        return clock

    def test_nesting_and_durations(self):
        rec = SpanRecorder(clock=self._fake_clock())
        with rec.span("outer", phase="execute"):
            with rec.span("inner"):
                pass
        outer, inner = rec.spans
        assert (outer.name, outer.depth, outer.parent) == ("outer", 0, -1)
        assert (inner.name, inner.depth, inner.parent) == ("inner", 1, 0)
        # clock ticks: outer@1, inner@2, inner ends@3, outer ends@4
        assert inner.duration_s == 1.0
        assert outer.duration_s == 3.0
        assert rec.total_s == 3.0  # roots only, no double-count

    def test_summary_and_by_phase(self):
        rec = SpanRecorder(clock=self._fake_clock())
        with rec.span("a", phase="execute"):
            with rec.span("b", phase="host"):
                pass
        summ = rec.summary()
        assert summ["a"]["count"] == 1 and summ["b"]["count"] == 1
        phases = rec.by_phase()
        # a's self time excludes b
        assert phases["execute"] == 2.0 and phases["host"] == 1.0
        assert phases["compile"] == 0.0
        assert "a" in rec.report() and "total execute" in rec.report()

    def test_unknown_phase_rejected(self):
        rec = SpanRecorder()
        with pytest.raises(ValueError, match="phase"):
            with rec.span("x", phase="gpu"):
                pass

    def test_module_span_noops_on_none(self):
        from repro.obs import span

        with span(None, "anything") as s:
            assert s is None

    def test_to_json(self, tmp_path):
        rec = SpanRecorder(clock=self._fake_clock())
        with rec.span("a"):
            pass
        path = tmp_path / "spans.json"
        rec.to_json(str(path))
        payload = json.loads(path.read_text())
        assert payload["spans"][0]["name"] == "a"
        assert set(payload["by_phase"]) == {"compile", "execute", "host"}


class TestKernelStats:
    def test_stats_respect_budgets(self):
        # The planner's own shape: g is the candidate grid (num_grid).
        st = sweep_kernel_stats(12, 128, 24 * 365)
        assert isinstance(st, KernelStats)
        assert st.vmem_temp_bytes <= st.vmem_budget
        assert st.hbm_passes <= st.pass_budget
        assert st.flops == 4 * 12 * 128 * 24 * 365
        assert 0.0 < st.vmem_utilization <= 1.0
        assert st.padding_waste >= 0.0
        d = st.to_dict()
        assert d["kernel"] == "commitment_sweep"
        assert d["block"] == list(st.block)

    def test_grid_solver_report_carries_stats(self):
        pools = traces.synthetic_pool_set(num_pools=2, num_hours=24 * 7 * 12)
        rep = replan.replan_fleet_pools(
            pools, cadence_weeks=2, start_weeks=4, horizon_weeks=4,
            compare=False, solver="grid", telemetry=True,
        )
        assert rep.kernel_stats is not None
        assert rep.kernel_stats.hbm_passes >= 1
        assert rep.ledger.meta["kernel_stats"]["kernel"] == \
            "commitment_sweep"


class TestTelemetryOverhead:
    def test_ledger_overhead_within_budget(self, monkeypatch):
        """Telemetry adds small per-week scan outputs, not solver work: the
        base outputs keep their shapes, no extra output is larger than
        the largest base output, the pull's ``d2h_bytes`` grows by exactly
        the extra outputs, and the lowered scan's flops grow by under 1 %.
        (Its wall-clock cost is read on the chip, in PERF.md.)"""
        import jax

        from repro.obs import recording

        pools = traces.synthetic_pool_set(num_pools=2, num_hours=24 * 7 * 10)
        kw = dict(cadence_weeks=2, start_weeks=4, horizon_weeks=4,
                  compare=False)
        real_scan = jax.lax.scan

        def scan_of(**extra):
            """(output shapes, flops, d2h_bytes) of the replay's scan."""
            calls = []

            def spy(f, init, xs=None, *a, **k):
                leaves = jax.tree.leaves((init, xs))
                if not any(isinstance(x, jax.core.Tracer) for x in leaves):
                    calls.append((f, init, xs, a, k))
                return real_scan(f, init, xs, *a, **k)

            monkeypatch.setattr(jax.lax, "scan", spy)
            rec = SpanRecorder()
            with recording(rec):
                replan.replan_fleet_pools(pools, **kw, **extra)
            monkeypatch.setattr(jax.lax, "scan", real_scan)
            (f, init, xs, a, k), = calls

            def whole(c, x):
                return real_scan(f, c, x, *a, **k)

            outs = jax.eval_shape(whole, init, xs)[1]
            flops = jax.jit(whole).lower(init, xs).cost_analysis()["flops"]
            (pull,) = [sp for sp in rec.spans if sp.name == "replan/pull"]
            return outs, flops, pull.counts["d2h_bytes"]

        base, base_flops, base_d2h = scan_of(telemetry=None)
        largest = max(v.size * v.dtype.itemsize for v in base.values())
        for tele in (True, TelemetryConfig(calibration=True,
                                           provenance=True)):
            outs, flops, d2h = scan_of(telemetry=tele)
            for key, v in base.items():
                assert (outs[key].shape, outs[key].dtype) == \
                    (v.shape, v.dtype), key
            extra = {k: v for k, v in outs.items() if k not in base}
            assert extra, "telemetry on must add scan outputs"
            for key, v in extra.items():
                assert v.size * v.dtype.itemsize <= largest, key
            assert d2h - base_d2h == sum(
                v.size * v.dtype.itemsize for v in extra.values()
            )
            assert base_flops <= flops <= 1.01 * base_flops, (
                f"telemetry {tele!r} adds {flops / base_flops - 1:.2%} "
                "flops to the scan"
            )


#: The planner's host stages, in the order a plan opens them.
STAGES = (
    "replan", "replan/scenarios", "replan/place_rows", "replan/prepare",
    "replan/scan", "replan/pull", "replan/post", "replan/post/books",
    "replan/post/totals", "replan/post/report", "replan/post/baselines",
)


class TestStages:
    """``replan_fleet_pools`` marks its host stages with
    ``repro.obs.spans.stage``: profiler annotations on the device trace's
    clock, with their byte counters as event stats, recorded too by an
    active ``SpanRecorder``."""

    N, WEEKS, START = 2, 12, 4

    @pytest.fixture(scope="class")
    def pools(self):
        return traces.synthetic_pool_set(num_pools=2,
                                         num_hours=24 * 7 * self.WEEKS)

    def _plan(self, pools, compare):
        return replan.replan_fleet_pools(
            pools, cadence_weeks=2, start_weeks=self.START,
            horizon_weeks=4, compare=compare,
            scenarios=sc.ScenarioConfig(n_scenarios=self.N,
                                        family="growth"),
        )

    @staticmethod
    def _stage_events(log_dir):
        """(name, start_ns, end_ns, stats) of every stage in the trace,
        in the order they opened."""
        import glob

        import jax

        (path,) = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
        data = jax.profiler.ProfileData.from_file(path)
        out = [
            (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
             dict(ev.stats))
            for plane in data.planes if plane.name == "/host:CPU"
            for line in plane.lines for ev in line.events
            if ev.name == "replan" or ev.name.startswith("replan/")
        ]
        return sorted(out, key=lambda e: (e[1], -e[2]))

    @staticmethod
    def _pulled_bytes(rep):
        """Bytes of the scan outputs a plain replay pulls, read off its
        report (no spot, migration, convertible or telemetry)."""
        return sum(np.asarray(a).nbytes for a in (
            rep.targets, rep.increments, rep.active, rep.committed_cost,
            rep.on_demand_cost, rep.utilization, rep.decision_mask,
        ))

    def test_stages_on_the_profiler_trace(self, pools, tmp_path,
                                          monkeypatch):
        import jax

        from repro.launch import mesh as mesh_mod

        placed = []
        shard_rows = mesh_mod.shard_rows

        def spy(x, *a, **k):
            placed.append(x.nbytes)
            return shard_rows(x, *a, **k)

        monkeypatch.setattr(mesh_mod, "shard_rows", spy)
        self._plan(pools, compare=False)   # compile outside the trace
        jax.profiler.start_trace(str(tmp_path))
        try:
            plain = self._plan(pools, compare=False)
            full = self._plan(pools, compare=True)
        finally:
            jax.profiler.stop_trace()
        events = self._stage_events(tmp_path)
        roots = [i for i, e in enumerate(events) if e[0] == "replan"]
        assert len(roots) == 2
        realized = pools.num_pools * self.WEEKS * 168 * 4
        for lo, hi, rep, names in (
            (roots[0], roots[1], plain, STAGES[:-1]),
            (roots[1], len(events), full, STAGES),
        ):
            plan = events[lo:hi]
            # Each stage once, in table order, nested under ``replan``
            # (and the post stages under ``replan/post``).
            assert tuple(e[0] for e in plan) == names
            by_name = {e[0]: e for e in plan}
            for name, start, end, _ in plan[1:]:
                parent = by_name["replan/post" if name.startswith(
                    "replan/post/") else "replan"]
                assert parent[1] <= start <= end <= parent[2], name
            for a, b in zip(plan[2:6], plan[3:7]):
                assert a[2] <= b[1], (a[0], b[0])   # siblings in sequence
            assert by_name["replan/place_rows"][3] == {
                "h2d_bytes": realized + placed[-1]}
            assert by_name["replan/pull"][3] == {
                "d2h_bytes": self._pulled_bytes(rep)}
        assert placed[-1] == self.N * realized
        eval_bytes = (self.N * pools.num_pools
                      * (self.WEEKS - self.START) * 168 * 4)
        assert by_name["replan/post/baselines"][3] == {
            "d2h_bytes": eval_bytes}

    def test_recorder_gets_the_same_stages_and_counts(self, pools):
        from repro.obs import recording

        rec = SpanRecorder()
        with recording(rec):
            rep = self._plan(pools, compare=True)
        assert tuple(sp.name for sp in rec.spans) == STAGES
        counts = {sp.name: sp.counts for sp in rec.spans if sp.counts}
        realized = pools.num_pools * self.WEEKS * 168 * 4
        assert counts == {
            "replan/place_rows": {"h2d_bytes": (1 + self.N) * realized},
            "replan/pull": {"d2h_bytes": self._pulled_bytes(rep)},
            "replan/post/baselines": {
                "d2h_bytes": self.N * pools.num_pools
                * (self.WEEKS - self.START) * 168 * 4},
        }
        assert rec.spans[0].parent == -1
        assert all(sp.parent >= 0 for sp in rec.spans[1:])
        assert rec.summary()["replan/pull"]["counts"] == counts["replan/pull"]

    def test_api_plan_brackets_the_replay(self, pools):
        """``api.plan`` wraps the replay in ``api/plan``, so what runs as
        the replay returns (its locals freed) is inside a stage too."""
        from repro.obs import recording

        rec = SpanRecorder()
        with recording(rec):
            api.plan(api.PlanRequest(
                pools=pools, mode="rolling", horizon_weeks=4,
                scenarios=sc.ScenarioConfig(n_scenarios=self.N,
                                            family="growth"),
                rolling=api.RollingConfig(cadence_weeks=2,
                                          start_weeks=self.START,
                                          compare=False),
            ))
        assert [sp.name for sp in rec.spans] == ["api/plan", *STAGES[:-1]]
        assert [sp.depth for sp in rec.spans[:3]] == [0, 1, 2]

    def test_idle_stage_reads_no_clock(self, pools, monkeypatch):
        """With neither a profiler nor an active recorder, a stage reads
        no clock and makes no ``Span``: a recorder that is not (or no
        longer) active is never called."""
        from repro.obs import recording
        from repro.obs import spans as obs_spans

        def boom():
            raise AssertionError("a stage read the clock")

        idle = SpanRecorder(clock=boom)
        done = SpanRecorder()
        with recording(done):
            pass
        monkeypatch.setattr(obs_spans, "Span", None)
        self._plan(pools, compare=False)
        assert idle.spans == [] and done.spans == []

    def test_recorder_leaves_the_report_bit_identical(self, pools):
        from repro.obs import recording

        rep = _run_case("rolling_portfolio", 1, 1, 1, telemetry=None)
        with recording(SpanRecorder()):
            rec = _run_case("rolling_portfolio", 1, 1, 1, telemetry=None)
        assert rec.total_cost == rep.total_cost
        for name in ("targets", "increments", "active", "committed_cost",
                     "on_demand_cost", "utilization", "decision_mask",
                     "spot_cost", "conv_targets", "conv_alloc"):
            np.testing.assert_array_equal(
                np.asarray(getattr(rec, name)),
                np.asarray(getattr(rep, name)), err_msg=name,
            )
        assert rec.ledger is None and rec.telemetry is None


class TestScenarioReplay:
    @pytest.fixture(scope="class")
    def pools(self):
        return traces.synthetic_pool_set(num_pools=2, num_hours=24 * 7 * 12)

    @pytest.fixture(scope="class")
    def batched(self, pools):
        # A perturbing family, so scenarios 1.. are genuinely different
        # demand futures (the int spelling's "realized" family replays
        # the same trace N times).
        return replan.replan_fleet_pools(
            pools, spot=True,
            scenarios=sc.ScenarioConfig(n_scenarios=3, family="growth"),
            cadence_weeks=2, start_weeks=4, horizon_weeks=4,
            compare=False,
        )

    def test_scenario0_matches_unbatched_replay(self, pools, batched):
        unbatched = replan.replan_fleet_pools(
            pools, spot=True, cadence_weeks=2, start_weeks=4,
            horizon_weeks=4, compare=False,
        )
        a = sim.replay_spot_plan(pools, batched, num_draws=8, seed=0,
                                 scenario=0)
        b = sim.replay_spot_plan(pools, unbatched, num_draws=8, seed=0)
        assert a.realized_cost == b.realized_cost
        np.testing.assert_array_equal(a.availability, b.availability)

    def test_nonzero_scenario_replays_its_own_future(self, pools, batched):
        rep1 = sim.replay_spot_plan(pools, batched, num_draws=8, seed=0,
                                    scenario=1)
        assert np.isfinite(rep1.realized_cost)
        np.testing.assert_allclose(
            rep1.planned_cost, float(batched.scenario_cost[1]), rtol=1e-6
        )
        rep0 = sim.replay_spot_plan(pools, batched, num_draws=8, seed=0,
                                    scenario=0)
        assert rep1.realized_cost != rep0.realized_cost

    def test_out_of_range_scenario_raises(self, pools, batched):
        with pytest.raises(ValueError, match="out of range"):
            sim.replay_spot_plan(pools, batched, scenario=3)
        unbatched = replan.replan_fleet_pools(
            pools, spot=True, cadence_weeks=2, start_weeks=4,
            horizon_weeks=4, compare=False,
        )
        with pytest.raises(ValueError, match="out of range"):
            sim.replay_spot_plan(pools, unbatched, scenario=1)


class TestObsCli:
    @pytest.fixture(scope="class")
    def ledger_paths(self, rep_full, tmp_path_factory):
        tmp = tmp_path_factory.mktemp("obs_cli")
        a = str(tmp / "a.jsonl")
        b = str(tmp / "b.jsonl")
        led = rep_full.ledger
        led.to_jsonl(a)
        import dataclasses

        bumped = dataclasses.replace(led, cost=led.cost + 1.0)
        bumped.to_jsonl(b)
        return a, b

    def test_report(self, ledger_paths, tmp_path, capsys):
        a, _ = ledger_paths
        out_json = str(tmp_path / "report.json")
        assert obs_cli(["report", a, "--json", out_json]) == 0
        assert "spend by source" in capsys.readouterr().out
        payload = json.loads(Path(out_json).read_text())
        assert "unit_economics" in payload and "by_source" in payload

    def test_diff_gate(self, ledger_paths, capsys):
        a, b = ledger_paths
        assert obs_cli(["diff", a, a]) == 0
        assert obs_cli(["diff", a, b]) == 0          # no gate: report only
        assert obs_cli(["diff", a, b, "--fail-above", "0.5"]) == 1
        assert "FAIL" in capsys.readouterr().err

    def test_top(self, ledger_paths, capsys):
        a, b = ledger_paths
        assert obs_cli(["top", a, "-n", "3"]) == 0
        assert obs_cli(["top", a, b, "--fail-above", "0.5"]) == 1
        out = capsys.readouterr().out
        assert "top 3 spend cells" in out


def _steady_fleet(family: str = "steady", num_seeds: int = 32,
                  num_weeks: int = 20):
    """N seeded single-pool paths of one family, flattened into an
    N-pool fleet — the coverage test's unit of statistical power."""
    arr = np.asarray(sc.scenario_paths(
        family, num_pools=1, num_weeks=num_weeks, num_seeds=num_seeds,
    )).reshape(num_seeds, -1)
    return dmnd.PoolSet(keys=sc.scenario_keys(num_seeds), demand=arr)


@pytest.fixture(scope="module")
def calib_cubes():
    """Calibration cubes for the steady and unpredictable families from
    identically configured replays."""
    tele = TelemetryConfig(calibration=True)
    cubes = {}
    for family in ("steady", "unpredictable"):
        rep = replan.replan_fleet_pools(
            _steady_fleet(family), cadence_weeks=1, start_weeks=8,
            horizon_weeks=4, compare=False, telemetry=tele,
        )
        cubes[family] = rep.calibration
    return cubes


class TestCalibration:
    def test_steady_coverage_within_3pp_of_nominal(self, calib_cubes):
        cube = calib_cubes["steady"]
        assert cube.max_coverage_drift <= 0.03, cube.report()

    def test_unpredictable_family_degrades_detectably(self, calib_cubes):
        steady = calib_cubes["steady"].max_coverage_drift
        rough = calib_cubes["unpredictable"].max_coverage_drift
        assert rough > 2.0 * steady, (
            f"unpredictable drift {rough:.4f} not detectably worse than "
            f"steady {steady:.4f}"
        )

    def test_cube_shape_and_summary(self, calib_cubes):
        cube = calib_cubes["steady"]
        s, n, p, q = cube.levels.shape
        assert (n, p) == (1, 32)
        assert cube.hits.shape == cube.pinball.shape == (s, n, p, q)
        assert cube.realized_mean.shape == (s, n, p)
        assert np.all((0.0 <= cube.hits) & (cube.hits <= 1.0))
        assert np.all(cube.pinball >= 0.0)
        assert np.all(np.diff(np.asarray(cube.fractiles)) > 0)
        summ = cube.summary()
        assert summ["weeks"] == s and summ["n_scenarios"] == 1
        assert summ["max_coverage_drift"] == cube.max_coverage_drift
        assert summ["interval_width"] > 0.0
        assert "coverage" in summ and len(summ["coverage"]) == q
        assert "fractile" in cube.report()

    def test_report_carries_levels_and_mask(self, calib_cubes):
        # fractile_levels ride the report next to the cube; the weekly
        # decision mask reflects the cadence grid.
        tele = TelemetryConfig(calibration=True)
        rep = replan.replan_fleet_pools(
            _steady_fleet(num_seeds=4), cadence_weeks=2, start_weeks=8,
            horizon_weeks=4, compare=False, telemetry=tele,
        )
        s = len(np.asarray(rep.calibration.weeks))
        assert np.asarray(rep.fractile_levels).shape == (s, 4, 5)
        mask = np.asarray(rep.decision_mask)
        assert mask.shape == (s,)
        np.testing.assert_array_equal(mask, (np.arange(s) % 2) == 0)
        assert rep.summary()["decision_weeks"] == int(mask.sum())

    def test_jsonl_roundtrip_is_exact(self, calib_cubes, tmp_path):
        cube = calib_cubes["steady"]
        path = str(tmp_path / "calib.jsonl")
        cube.to_jsonl(path)
        back = CalibrationCube.from_jsonl(path)
        assert back.entities == cube.entities
        assert back.fractiles == cube.fractiles
        np.testing.assert_array_equal(back.weeks, cube.weeks)
        np.testing.assert_array_equal(back.levels, cube.levels)
        np.testing.assert_array_equal(back.hits, cube.hits)
        np.testing.assert_array_equal(back.pinball, cube.pinball)
        np.testing.assert_array_equal(back.realized_mean,
                                      cube.realized_mean)
        np.testing.assert_array_equal(back.realized_peak,
                                      cube.realized_peak)
        assert back.diff(cube).max_abs_coverage_delta == 0.0

    def test_diff_compares_families(self, calib_cubes):
        diff = calib_cubes["unpredictable"].diff(calib_cubes["steady"])
        assert diff.max_abs_coverage_delta > 0.0
        assert diff.drift_a > diff.drift_b
        assert set(diff.coverage_delta) == set(
            float(q) for q in calib_cubes["steady"].fractiles
        )
        assert "d-coverage" in diff.report()
        payload = diff.to_dict()
        assert payload["max_abs_coverage_delta"] == \
            diff.max_abs_coverage_delta
        with pytest.raises(ValueError, match="fractile"):
            import dataclasses as dc

            other = dc.replace(
                calib_cubes["steady"], fractiles=(0.1, 0.5, 0.9),
                levels=calib_cubes["steady"].levels[..., :3],
                hits=calib_cubes["steady"].hits[..., :3],
                pinball=calib_cubes["steady"].pinball[..., :3],
            )
            calib_cubes["steady"].diff(other)

    def test_scenario_batched_cube_from_one_scan(self):
        pools = traces.synthetic_pool_set(num_pools=2,
                                          num_hours=24 * 7 * 12)
        rep = replan.replan_fleet_pools(
            pools,
            scenarios=sc.ScenarioConfig(n_scenarios=3, family="regime"),
            cadence_weeks=1, start_weeks=6, horizon_weeks=4,
            compare=False, telemetry=TelemetryConfig(calibration=True),
        )
        cube = rep.calibration
        assert cube.n_scenarios == 3
        per_scen = cube.scenario_coverage()
        assert per_scen.shape == (3, len(cube.fractiles))
        np.testing.assert_allclose(
            per_scen[0], cube.coverage(scenario=0), rtol=1e-12
        )
        np.testing.assert_allclose(
            per_scen.mean(axis=0), cube.coverage(), rtol=1e-12
        )
        # The regime scenarios perturb demand away from the realized
        # trace, so their coverage genuinely differs from scenario 0.
        assert np.abs(per_scen[1:] - per_scen[0]).max() > 0.0
        with pytest.raises(ValueError, match="out of range"):
            cube.coverage(scenario=3)

    def test_interval_width_unknown_pair_raises(self, calib_cubes):
        with pytest.raises(KeyError, match="not carried"):
            calib_cubes["steady"].interval_width(0.123, 0.456)

    def test_calibration_requires_forecasting_policy(self):
        pools = traces.synthetic_pool_set(num_pools=2,
                                          num_hours=24 * 7 * 12)
        with pytest.raises(ValueError, match="forecast"):
            replan.replan_fleet_pools(
                pools, policy="deterministic_hedge", cadence_weeks=1,
                start_weeks=6, horizon_weeks=4, compare=False,
                telemetry=TelemetryConfig(calibration=True),
            )

    def test_fractile_validation(self):
        with pytest.raises(ValueError, match="fractiles"):
            TelemetryConfig(fractiles=())
        with pytest.raises(ValueError, match="fractiles"):
            TelemetryConfig(fractiles=(0.5, 0.25))
        with pytest.raises(ValueError, match="fractiles"):
            TelemetryConfig(fractiles=(0.0, 0.5))


@pytest.fixture(scope="module")
def prov_rep():
    """All-bands replay with provenance telemetry on."""
    return _run_case(
        "rolling_portfolio", 1, 1, 1,
        telemetry=TelemetryConfig(provenance=True),
    )


class TestDecisionLog:
    def test_log_materializes_with_all_bands(self, prov_rep):
        log = prov_rep.decision_log
        assert log is not None
        assert len(log.entities) == 4
        assert log.conv_clouds is not None
        assert log.increments.shape == log.targets.shape
        assert set(np.unique(log.binding)) <= set(
            ("convertible", "spot_cap", "envelope", "carry")
        )

    def test_decision_weeks_follow_cadence(self, prov_rep):
        log = prov_rep.decision_log
        mask = np.asarray(prov_rep.decision_mask)
        np.testing.assert_array_equal(
            log.decision_weeks, log.weeks[mask]
        )
        # cadence_weeks=2: every other evaluated week decides.
        np.testing.assert_array_equal(log.is_decision, mask)
        # Non-decision weeks never buy and always carry.
        nondec = ~log.is_decision
        assert float(log.increments[nondec].sum()) == 0.0
        assert np.all(log.binding[nondec] == "carry")

    def test_holdings_reconstruct_active_stack(self, prov_rep):
        log = prov_rep.decision_log
        for week in (int(log.weeks[0]), int(log.weeks[-1])):
            si = int(np.flatnonzero(log.weeks == week)[0])
            held = log.holdings(week)
            for pi, pool in enumerate(log.entities):
                tranche_sum = sum(t["width"] for t in held[pool])
                np.testing.assert_allclose(
                    tranche_sum, log.active[si, pi].sum(), rtol=1e-6,
                    err_msg=f"week {week} pool {pool}",
                )
                for t in held[pool]:
                    assert t["bought_week"] <= week < t["expires_week"]
                    assert t["sku"] in log.skus

    def test_explain_answers_why(self, prov_rep):
        log = prov_rep.decision_log
        w = int(log.decision_weeks[0])
        rec = log.explain(w)
        assert rec["week"] == w and rec["is_decision"]
        pool = rec["pools"][log.entities[0]]
        assert set(pool) == {"binding", "bought", "rolled_off",
                             "target_top", "stack_top"}
        assert "clouds" in rec
        cloud = rec["clouds"][log.conv_clouds[0]]
        assert set(cloud) == {"bought", "rolled_off", "stack_top"}
        with pytest.raises(KeyError, match="not in log"):
            log.explain(10 ** 6)

    def test_summary_and_binding_counts(self, prov_rep):
        log = prov_rep.decision_log
        counts = log.binding_counts()
        assert sum(counts.values()) == log.binding.size
        summ = log.summary()
        assert summ["decision_weeks"] == int(log.is_decision.sum())
        assert summ["tranches_bought"] >= 1
        assert summ["binding_counts"] == counts
        assert "conv_width_bought" in summ
        assert summ["policy"] == "rolling_portfolio"

    def test_spot_free_replay_has_no_spot_cap(self):
        rep = _run_case(
            "rolling_portfolio", 0, 0, 0,
            telemetry=TelemetryConfig(provenance=True),
        )
        log = rep.decision_log
        assert log.conv_clouds is None
        counts = log.binding_counts()
        assert counts["spot_cap"] == 0 and counts["convertible"] == 0
        assert counts["envelope"] >= 1


class TestBreachCadence:
    @pytest.fixture(scope="class")
    def steady_pools(self):
        return sc.scenario_pool_set("steady", num_pools=4, num_weeks=52)

    @pytest.fixture(scope="class")
    def weekly_rep(self, steady_pools):
        return replan.replan_fleet_pools(
            steady_pools, cadence_weeks=1, start_weeks=24,
            horizon_weeks=4, compare=False,
        )

    @pytest.fixture(scope="class")
    def breach_rep(self, steady_pools):
        return replan.replan_fleet_pools(
            steady_pools, cadence_weeks=1, cadence="breach",
            start_weeks=24, horizon_weeks=4, compare=False,
        )

    def test_breach_skips_decisions_at_tiny_cost_delta(
        self, weekly_rep, breach_rep,
    ):
        """The acceptance criterion: >= 60% fewer decision weeks than the
        weekly cadence on a steady fleet, at <= 1% realized-cost delta."""
        n_weekly = int(np.asarray(weekly_rep.decision_mask).sum())
        n_breach = int(np.asarray(breach_rep.decision_mask).sum())
        assert n_breach <= 0.4 * n_weekly, (
            f"breach decided {n_breach}/{n_weekly} weeks"
        )
        cw = float(weekly_rep.total_cost)
        cb = float(breach_rep.total_cost)
        assert abs(cb - cw) / cw <= 0.01, (
            f"cost delta {abs(cb - cw) / cw:.4%} exceeds 1%"
        )

    def test_python_loop_oracle_reproduces_mask_bitwise(
        self, steady_pools, breach_rep,
    ):
        """The in-scan breach mask must equal a host-side python loop
        over the emitted bands bit-for-bit — integer hour counts against
        integer budgets, no float tolerance."""
        start = 24
        h = 168
        demand = np.asarray(steady_pools.demand).reshape(
            len(steady_pools.keys), -1, h
        )
        lo_all = np.asarray(breach_rep.breach_band_lo)
        hi_all = np.asarray(breach_rep.breach_band_hi)
        mask = np.asarray(breach_rep.decision_mask)
        q_lo, q_hi, tol = 0.05, 0.95, 4.0
        allow_above = int(tol * (1.0 - q_hi) * h)
        allow_below = int(tol * q_lo * h)
        want = np.zeros_like(mask)
        lo = np.zeros(demand.shape[0])
        hi = np.zeros(demand.shape[0])
        for i in range(mask.shape[0]):
            w = start + i
            d_prev = demand[:, w - 1]
            above = (d_prev > hi[:, None]).sum(-1)
            below = (d_prev < lo[:, None]).sum(-1)
            dec = bool(
                ((above > allow_above) | (below > allow_below)).any()
                or w == start
            )
            want[i] = dec
            if dec:
                lo, hi = lo_all[i], hi_all[i]
        np.testing.assert_array_equal(want, mask)

    def test_never_misses_a_breach_week(self, steady_pools, breach_rep):
        """Every week whose realized demand exited the held band beyond
        the hour budget IS a decision week (plus the mandatory start)."""
        h = 168
        demand = np.asarray(steady_pools.demand).reshape(
            len(steady_pools.keys), -1, h
        )
        lo_all = np.asarray(breach_rep.breach_band_lo)
        hi_all = np.asarray(breach_rep.breach_band_hi)
        mask = np.asarray(breach_rep.decision_mask)
        allow = int(4.0 * 0.05 * h)
        lo = np.zeros(demand.shape[0])
        hi = np.zeros(demand.shape[0])
        for i in range(mask.shape[0]):
            d_prev = demand[:, 24 + i - 1]
            breached = (
                ((d_prev > hi[:, None]).sum(-1) > allow)
                | ((d_prev < lo[:, None]).sum(-1) > allow)
            ).any()
            if breached or i == 0:
                assert mask[i], f"missed breach at step {i}"
            if mask[i]:
                lo, hi = lo_all[i], hi_all[i]

    def test_report_carries_cadence_and_bands(self, breach_rep):
        assert breach_rep.cadence == "breach"
        assert breach_rep.summary()["cadence"] == "breach"
        s = np.asarray(breach_rep.decision_mask).shape[0]
        assert np.asarray(breach_rep.breach_band_lo).shape == (s, 4)
        assert np.all(
            np.asarray(breach_rep.breach_band_hi)
            >= np.asarray(breach_rep.breach_band_lo)
        )

    def test_weekly_spelling_is_the_golden_path(self, steady_pools):
        """cadence='weekly' (explicit) is the same compiled program as
        the default — same costs bitwise."""
        a = replan.replan_fleet_pools(
            steady_pools, cadence_weeks=2, start_weeks=24,
            horizon_weeks=4, compare=False,
        )
        b = replan.replan_fleet_pools(
            steady_pools, cadence_weeks=2, start_weeks=24,
            horizon_weeks=4, compare=False, cadence="weekly",
        )
        assert a.total_cost == b.total_cost
        np.testing.assert_array_equal(
            np.asarray(a.weekly_cost), np.asarray(b.weekly_cost)
        )

    def test_scenario_batched_breach_masks_per_scenario(self):
        pools = traces.synthetic_pool_set(num_pools=2,
                                          num_hours=24 * 7 * 16)
        rep = replan.replan_fleet_pools(
            pools, cadence_weeks=1, cadence="breach", start_weeks=8,
            horizon_weeks=4, compare=False,
            scenarios=sc.ScenarioConfig(n_scenarios=3, family="regime"),
        )
        mask = np.asarray(rep.decision_mask)
        assert mask.ndim == 2 and mask.shape[1] == 3
        # Scenario 0 is the realized trace: its mask matches the
        # unbatched breach replay of the same pools.
        solo = replan.replan_fleet_pools(
            pools, cadence_weeks=1, cadence="breach", start_weeks=8,
            horizon_weeks=4, compare=False,
        )
        np.testing.assert_array_equal(
            mask[:, 0], np.asarray(solo.decision_mask)
        )
        # Regime scenarios shift demand, so at least one scenario's
        # replan schedule must differ from the realized one.
        assert np.any(mask[:, 1:] != mask[:, :1])

    def test_breach_validation_errors(self, steady_pools):
        with pytest.raises(ValueError, match="cadence"):
            replan.replan_fleet_pools(
                steady_pools, cadence_weeks=1, cadence="hourly",
                start_weeks=24, horizon_weeks=4, compare=False,
            )
        with pytest.raises(ValueError, match="cadence_weeks=1"):
            replan.replan_fleet_pools(
                steady_pools, cadence_weeks=2, cadence="breach",
                start_weeks=24, horizon_weeks=4, compare=False,
            )
        with pytest.raises(ValueError, match="forecast"):
            replan.replan_fleet_pools(
                steady_pools, cadence_weeks=1, cadence="breach",
                policy="deterministic_hedge", start_weeks=24,
                horizon_weeks=4, compare=False,
            )
        with pytest.raises(ValueError, match="cadence"):
            api.RollingConfig(cadence="hourly")
        with pytest.raises(ValueError, match="cadence_weeks=1"):
            api.RollingConfig(cadence="breach", cadence_weeks=2)
        with pytest.raises(ValueError, match="breach_band"):
            api.RollingConfig(breach_band=(0.9, 0.1))
        with pytest.raises(ValueError, match="breach_band"):
            api.RollingConfig(breach_band=(0.05, 0.5, 0.95))
        with pytest.raises(ValueError, match="breach_tolerance"):
            api.RollingConfig(breach_tolerance=0.0)


class TestLedgerScenarios:
    @pytest.fixture(scope="class")
    def batched_rep(self):
        pools = traces.synthetic_pool_set(num_pools=2,
                                          num_hours=24 * 7 * 12)
        return replan.replan_fleet_pools(
            pools, spot=True,
            scenarios=sc.ScenarioConfig(n_scenarios=3, family="growth"),
            cadence_weeks=2, start_weeks=4, horizon_weeks=4,
            compare=False, telemetry=True,
        )

    def test_default_ledger_is_scenario_zero(self, batched_rep):
        led = batched_rep.ledger
        assert led.meta["scenario"] == 0
        assert led.reconcile(batched_rep)["ok"]

    def test_nonzero_scenario_ledger_reconciles_its_column(
        self, batched_rep,
    ):
        led1 = ledger_from_report(batched_rep, scenario=1)
        assert led1.meta["scenario"] == 1
        res = led1.reconcile(batched_rep)          # k from meta
        assert res["ok"], res
        assert res["scenario"] == 1
        np.testing.assert_allclose(
            res["total_report"],
            float(np.asarray(batched_rep.scenario_cost)[1]),
            rtol=1e-6,
        )
        # A growth future genuinely re-prices the fleet.
        assert led1.total != batched_rep.ledger.total
        explicit = led1.reconcile(batched_rep, scenario=1)
        assert explicit["ok"]

    def test_cross_scenario_reconcile_mismatches(self, batched_rep):
        led1 = ledger_from_report(batched_rep, scenario=1)
        res = led1.reconcile(batched_rep, scenario=0)
        assert not res["ok"]

    def test_out_of_range_scenario_raises(self, batched_rep):
        with pytest.raises(ValueError, match="out of range"):
            ledger_from_report(batched_rep, scenario=3)
        with pytest.raises(ValueError, match="out of range"):
            batched_rep.ledger.reconcile(batched_rep, scenario=3)

    def test_unbatched_report_rejects_nonzero_scenario(self, rep_full):
        with pytest.raises(ValueError, match="out of range"):
            ledger_from_report(rep_full, scenario=1)


class TestLedgerEdgeCases:
    def test_unit_economics_idle_only_fleet_is_inf_free(self, rep_full):
        import dataclasses

        led = rep_full.ledger
        idle = dataclasses.replace(
            led,
            used_hours=np.zeros_like(led.used_hours),
            idle_hours=led.idle_hours + led.used_hours,
        )
        econ = idle.unit_economics()
        assert econ["idle_only"] is True
        assert econ["cost_per_used_chip_hour"] == 0.0
        for v in econ.values():
            assert np.isfinite(float(v))
        live = led.unit_economics()
        assert live["idle_only"] is False
        assert live["cost_per_used_chip_hour"] > 0.0

    def test_top_movers_empty_diff(self, rep_full):
        diff = rep_full.ledger.diff(rep_full.ledger)
        assert diff.max_abs_delta == 0.0
        assert diff.top_movers(10) == []
        assert isinstance(diff.report(), str)


class TestCalibCli:
    @pytest.fixture(scope="class")
    def cube_paths(self, calib_cubes, tmp_path_factory):
        tmp = tmp_path_factory.mktemp("calib_cli")
        a = str(tmp / "steady.jsonl")
        b = str(tmp / "rough.jsonl")
        calib_cubes["steady"].to_jsonl(a)
        calib_cubes["unpredictable"].to_jsonl(b)
        return a, b

    def test_report_and_gate(self, cube_paths, tmp_path, capsys):
        a, _ = cube_paths
        out_json = str(tmp_path / "calib.json")
        assert obs_cli(["calib", a, "--json", out_json]) == 0
        assert "coverage" in capsys.readouterr().out
        payload = json.loads(Path(out_json).read_text())
        assert "max_coverage_drift" in payload
        # Permissive gate passes, impossible gate fails with exit 1.
        assert obs_cli(["calib", a, "--fail-above", "0.5"]) == 0
        assert obs_cli(["calib", a, "--fail-above", "0.0"]) == 1
        assert "FAIL" in capsys.readouterr().err

    def test_diff_gate(self, cube_paths, capsys):
        a, b = cube_paths
        assert obs_cli(["calib", a, a]) == 0
        assert obs_cli(["calib", a, b, "--fail-above", "1.0"]) == 0
        assert obs_cli(["calib", a, b, "--fail-above", "0.0"]) == 1
        assert "FAIL" in capsys.readouterr().err


class TestBenchProvenance:
    def test_quick_bench_json_is_stamped(self, tmp_path, monkeypatch):
        # A cache directory named by the environment keeps main() from
        # turning the checkout's persistent cache on for this process.
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        if str(REPO_ROOT) not in sys.path:
            sys.path.insert(0, str(REPO_ROOT))
        from benchmarks import run as bench_run

        out = str(tmp_path / "BENCH.json")
        spans = str(tmp_path / "SPANS.json")
        bench_run.main([
            "--quick", "--json", out, "--spans", spans,
            "--filter", "commitment_sweep",
        ])
        payload = json.loads(Path(out).read_text())
        assert payload["schema_version"] == bench_run.BENCH_SCHEMA_VERSION
        assert payload["git_sha"] and payload["git_sha"] != ""
        assert payload["quick"] is True and payload["seed"] == 0
        for key in ("jax", "numpy", "backend", "python", "platform"):
            assert payload[key]
        assert payload["rows"] and not payload["failures"]
        assert payload["spans"]  # per-bench wall-clock breakdown
        assert "commitment_sweep" in payload["kernel_stats"]
        span_payload = json.loads(Path(spans).read_text())
        assert span_payload["spans"]

    def test_unknown_filter_exits_nonzero(self, tmp_path, monkeypatch):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        if str(REPO_ROOT) not in sys.path:
            sys.path.insert(0, str(REPO_ROOT))
        from benchmarks import run as bench_run

        with pytest.raises(SystemExit):
            bench_run.main(["--quick", "--filter", "no_such_bench"])
