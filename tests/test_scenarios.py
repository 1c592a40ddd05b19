"""Scenario-generator coverage: every tournament family exhibits its
defining property across seeds, every path is a pure function of
(family, seed), and the PoolSet wrapper feeds the planner surface."""

import numpy as np
import pytest

from repro.core.demand import HOURS_PER_WEEK
from repro.data import scenarios as sc

WK = HOURS_PER_WEEK
SEEDS = (0, 1, 2, 3)


def _weekly_means(path):
    """(P, W) weekly mean level of one (P, T) path."""
    p, t = path.shape
    return path.reshape(p, t // WK, WK).mean(-1)


def _cv(path):
    wm = _weekly_means(path)
    return float((wm.std(-1) / wm.mean(-1)).mean())


def _lag_autocorr(x, lag):
    a, b = x[..., :-lag], x[..., lag:]
    a = a - a.mean(-1, keepdims=True)
    b = b - b.mean(-1, keepdims=True)
    return float(
        ((a * b).mean(-1) / (a.std(-1) * b.std(-1) + 1e-12)).mean()
    )


class TestFamilyProperties:
    """One defining, seed-robust property per §2 taxonomy family."""

    @pytest.mark.parametrize("seed", SEEDS)
    def test_steady_low_weekly_variation(self, seed):
        path = sc.scenario_path("steady", num_weeks=24, seed=seed)
        assert _cv(path) < 0.15

    @pytest.mark.parametrize("seed", SEEDS)
    def test_burst_rare_large_exceedances(self, seed):
        burst = sc.scenario_path("burst", num_weeks=24, seed=seed)
        steady = sc.scenario_path("steady", num_weeks=24, seed=seed)

        def exceed(path):
            med = np.median(path, axis=-1, keepdims=True)
            return int((path > 1.8 * med).sum())

        # spikes are present but rare: well under 10% of hours
        assert exceed(burst) >= 6
        assert exceed(burst) < 0.1 * burst.size
        assert exceed(burst) > exceed(steady)

    @pytest.mark.parametrize("seed", SEEDS)
    def test_cyclic_strong_weekly_autocorrelation(self, seed):
        cyc = sc.scenario_path("cyclic", num_weeks=24, seed=seed)
        steady = sc.scenario_path("steady", num_weeks=24, seed=seed)
        ac_c = _lag_autocorr(cyc, WK)
        assert ac_c > 0.4
        assert ac_c > _lag_autocorr(steady, WK)

    @pytest.mark.parametrize("seed", SEEDS)
    def test_declining_trend(self, seed):
        path = sc.scenario_path("declining", num_weeks=24, seed=seed)
        steady = sc.scenario_path("steady", num_weeks=24, seed=seed)
        wm = _weekly_means(path).mean(0)
        sm = _weekly_means(steady).mean(0)
        assert wm[-8:].mean() < 0.7 * wm[:8].mean()
        assert sm[-8:].mean() > 0.9 * sm[:8].mean()

    @pytest.mark.parametrize("seed", SEEDS)
    def test_unpredictable_high_variation(self, seed):
        unp = sc.scenario_path("unpredictable", num_weeks=24, seed=seed)
        steady = sc.scenario_path("steady", num_weeks=24, seed=seed)
        assert _cv(unp) > 0.15
        assert _cv(unp) > _cv(steady)


class TestGeneratorContract:
    def test_shapes_and_dtype(self):
        path = sc.scenario_path("steady", num_pools=4, num_weeks=10, seed=3)
        assert path.shape == (4, 10 * WK)
        assert path.dtype == np.float32
        assert (path >= 0).all() and np.isfinite(path).all()

    def test_paths_stack_shape(self):
        paths = sc.scenario_paths(
            "burst", num_pools=2, num_weeks=8, num_seeds=5, base_seed=7
        )
        assert paths.shape == (5, 2, 8 * WK)

    @pytest.mark.parametrize("family", sc.FAMILIES)
    def test_reproducible_given_seed(self, family):
        a = sc.scenario_path(family, num_weeks=8, seed=11)
        b = sc.scenario_path(family, num_weeks=8, seed=11)
        np.testing.assert_array_equal(a, b)
        c = sc.scenario_path(family, num_weeks=8, seed=12)
        assert not np.array_equal(a, c)

    def test_paths_slices_match_single_calls(self):
        paths = sc.scenario_paths(
            "cyclic", num_weeks=8, num_seeds=3, base_seed=4
        )
        for s in range(3):
            np.testing.assert_array_equal(
                paths[s], sc.scenario_path("cyclic", num_weeks=8, seed=4 + s)
            )

    def test_families_are_distinct_paths(self):
        got = {
            f: sc.scenario_path(f, num_weeks=8, seed=0).tobytes()
            for f in sc.FAMILIES
        }
        assert len(set(got.values())) == len(sc.FAMILIES)

    def test_unknown_family_raises(self):
        with pytest.raises(ValueError, match="unknown family"):
            sc.scenario_path("spiky", num_weeks=8)
        with pytest.raises(ValueError, match="unknown family"):
            sc.scenario_pool_set("spiky")

    def test_pool_keys_cycle_clouds(self):
        keys = sc.scenario_keys(5)
        assert [k[0] for k in keys] == ["aws", "azure", "gcp", "aws", "azure"]
        assert len(set(keys)) == 5

    def test_pool_set_wraps_path(self):
        ps = sc.scenario_pool_set("steady", num_pools=3, num_weeks=8, seed=2)
        np.testing.assert_array_equal(
            ps.demand,
            sc.scenario_path("steady", num_pools=3, num_weeks=8, seed=2),
        )
        assert ps.keys == sc.scenario_keys(3)
        assert set(ps.configs) == set(ps.keys)


class TestScenarioBatch:
    """The perturbation families behind ``scenarios=`` on the rolling
    replay: scenario 0 is the realized trace verbatim, every batch is a
    pure function of (demand, config), and each family moves the paths
    the way its name says."""

    def _demand(self):
        rng = np.random.default_rng(7)
        return rng.gamma(2.0, 40.0, (3, 6 * WK)).astype(np.float32)

    def test_config_validation(self):
        with pytest.raises(ValueError, match="n_scenarios"):
            sc.ScenarioConfig(n_scenarios=0)
        with pytest.raises(ValueError, match="unknown scenario family"):
            sc.ScenarioConfig(family="chaotic")
        with pytest.raises(ValueError, match="chunk"):
            sc.ScenarioConfig(chunk=0)

    def test_resolve_spellings(self):
        assert sc.resolve_scenarios(None) is None
        cfg = sc.resolve_scenarios(5)
        assert cfg == sc.ScenarioConfig(n_scenarios=5)
        assert sc.resolve_scenarios(cfg) is cfg
        with pytest.raises(TypeError, match="bool"):
            sc.resolve_scenarios(True)
        with pytest.raises(TypeError):
            sc.resolve_scenarios("many")

    @pytest.mark.parametrize("family", sc.PERTURBATIONS)
    def test_scenario0_is_realized_verbatim(self, family):
        d = self._demand()
        batch = sc.scenario_batch(d, sc.ScenarioConfig(
            n_scenarios=3, family=family
        ))
        assert batch.shape == (3,) + d.shape
        np.testing.assert_array_equal(batch[0], d)

    @pytest.mark.parametrize("family", sc.PERTURBATIONS)
    def test_batch_is_deterministic(self, family):
        d = self._demand()
        cfg = sc.ScenarioConfig(n_scenarios=3, family=family, seed=2)
        np.testing.assert_array_equal(
            sc.scenario_batch(d, cfg), sc.scenario_batch(d, cfg)
        )

    def test_seed_moves_perturbed_scenarios(self):
        d = self._demand()
        a = sc.scenario_batch(
            d, sc.ScenarioConfig(n_scenarios=3, family="regime", seed=0)
        )
        b = sc.scenario_batch(
            d, sc.ScenarioConfig(n_scenarios=3, family="regime", seed=1)
        )
        np.testing.assert_array_equal(a[0], b[0])
        assert not np.array_equal(a[1:], b[1:])

    def test_realized_family_is_copies(self):
        d = self._demand()
        batch = sc.scenario_batch(d, sc.ScenarioConfig(n_scenarios=4))
        for s in range(4):
            np.testing.assert_array_equal(batch[s], d)

    def test_growth_is_exponential_ramp(self):
        d = self._demand()
        batch = sc.scenario_batch(d, sc.ScenarioConfig(
            n_scenarios=2, family="growth", seed=3
        ))
        ratio = batch[1] / np.maximum(d, 1e-9)
        # One multiplicative ramp per pool: log-ratio is linear in t.
        lr = np.log(ratio)
        slope = lr[:, -1] - lr[:, 0]
        t = np.arange(d.shape[-1]) / (d.shape[-1] - 1)
        np.testing.assert_allclose(
            lr, lr[:, :1] + slope[:, None] * t[None], atol=1e-4
        )

    def test_scale_is_single_multiplier_per_pool(self):
        d = self._demand()
        batch = sc.scenario_batch(d, sc.ScenarioConfig(
            n_scenarios=2, family="scale", seed=5
        ))
        ratio = batch[1] / np.maximum(d, 1e-9)
        np.testing.assert_allclose(
            ratio, ratio[:, :1].repeat(d.shape[-1], axis=1), rtol=1e-5
        )

    def test_bad_demand_shape(self):
        with pytest.raises(ValueError, match="P, T"):
            sc.scenario_batch(
                np.zeros(10, np.float32), sc.ScenarioConfig(n_scenarios=2)
            )


def _loop_draws(family, rng, t):
    """The generator calls of one (s, p) row as the per-row host loop made
    them, value by value, in call order."""
    if family == "burst":
        out = []
        for _ in range(max(1, t // (sc.BURST_EVERY_WEEKS * WK))):
            ln = int(rng.integers(*sc.BURST_LEN_HOURS))
            at = int(rng.integers(0, max(t - ln, 1)))
            out += [at, ln]
        return out
    if family == "regime":
        edges = np.sort(rng.integers(1, t, size=sc.REGIME_SEGMENTS - 1))
        mult = rng.uniform(*sc.REGIME_RANGE, size=sc.REGIME_SEGMENTS)
        return [*edges, *mult]
    if family == "growth":
        return [rng.uniform(*sc.GROWTH_RANGE)]
    if family == "scale":
        return [rng.lognormal(0.0, sc.SCALE_SIGMA)]
    return []


def _oracle_row(family, y, vals):
    """Float64 perturbation of one realized row from its loop draws."""
    y = np.asarray(y, np.float64)
    t = np.arange(y.shape[-1])
    if family == "burst":
        out = y.copy()
        for at, ln in zip(vals[0::2], vals[1::2]):
            out[at:at + ln] *= sc.BURST_FACTOR
        return out
    if family == "regime":
        k = sc.REGIME_SEGMENTS - 1
        edges, mult = np.asarray(vals[:k], int), np.asarray(vals[k:])
        return y * mult[np.searchsorted(edges, t, side="right")]
    if family == "growth":
        return y * np.exp(vals[0] * t / (52.0 * WK))
    if family == "scale":
        return y * vals[0]
    return y


class TestDeviceBatch:
    """``scenario_batch`` as host draws plus one device module: the draws
    are the per-(s, p) generator's calls exactly, the device rows are the
    float64 perturbation to float32 precision, and a slice of the batch is
    the same rows as the whole."""

    N, P, T, SEED = 4, 3, 5 * WK, 11

    def _demand(self):
        rng = np.random.default_rng(3)
        return rng.gamma(2.0, 40.0, (self.P, self.T)).astype(np.float32)

    def _cfg(self, family):
        return sc.ScenarioConfig(n_scenarios=self.N, family=family,
                                 seed=self.SEED)

    def _loop(self, family):
        """{(s, p): draws} of the per-row loop, scenarios 1 .. N - 1."""
        fam_idx = sc.PERTURBATIONS.index(family)
        return {
            (s, p): _loop_draws(family, np.random.default_rng(
                (1_000_003 * fam_idx, self.SEED, s, p)), self.T)
            for s in range(1, self.N) for p in range(self.P)
        }

    @pytest.mark.parametrize("family", sc.PERTURBATIONS)
    def test_host_draws_are_the_loop_draws(self, family):
        draws = sc.host_draws(self._cfg(family), self.P, self.T)
        for (s, p), vals in self._loop(family).items():
            got = [v for a in draws for v in np.ravel(a[s, p])]
            if family == "burst":   # (at..., ln...) -> (at, ln) pairs
                nb = len(got) // 2
                got = [v for pair in zip(got[:nb], got[nb:]) for v in pair]
            assert got == vals, (s, p)

    @pytest.mark.parametrize("family", sc.PERTURBATIONS)
    def test_device_rows_match_float64_oracle(self, family):
        d = self._demand()
        batch = np.asarray(sc.scenario_batch(d, self._cfg(family)))
        assert batch.dtype == np.float32
        for (s, p), vals in self._loop(family).items():
            np.testing.assert_allclose(
                batch[s, p], _oracle_row(family, d[p], vals), rtol=2e-6,
                err_msg=f"scenario {s} pool {p}",
            )

    @pytest.mark.parametrize("family", sc.PERTURBATIONS)
    @pytest.mark.parametrize("lo,hi", [(0, 2), (1, 3), (3, 4)])
    def test_slice_is_the_whole_batchs_rows(self, family, lo, hi):
        d = self._demand()
        cfg = self._cfg(family)
        part = np.asarray(sc.scenario_batch(d, cfg, lo, hi))
        np.testing.assert_array_equal(
            part, np.asarray(sc.scenario_batch(d, cfg))[lo:hi]
        )
        if lo == 0:
            np.testing.assert_array_equal(part[0], d)

    @pytest.mark.parametrize("family", sc.PERTURBATIONS)
    def test_placed_bytes_are_realized_rows_and_draws(self, family):
        cfg = self._cfg(family)
        draws = sc.host_draws(cfg, self.P, self.T, 1, 3)
        assert sc.placed_bytes(cfg, self.P, self.T, 1, 3) == (
            self._demand().nbytes + 4 * sum(a.size for a in draws)
        )

    @pytest.mark.parametrize("lo,hi", [(-1, 2), (2, 2), (0, 5)])
    def test_bad_slice(self, lo, hi):
        with pytest.raises(ValueError, match="outside"):
            sc.scenario_batch(self._demand(), self._cfg("growth"), lo, hi)
