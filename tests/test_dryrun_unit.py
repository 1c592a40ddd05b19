"""Unit tests for the dry-run machinery that don't need the 512-device mesh:
collective parsing, delta configs, rule resolution, sharding sanitization."""

import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.launch import hlo_analysis as ha
from repro.launch.cells import delta_configs, resolve_rules
from repro.models.config import SHAPES
from repro.models.params import Spec, sanitize_partition_spec
from repro.sharding.rules import RULESETS


class TestCollectiveParsing:
    HLO = """
  %ag = bf16[16,4096]{1,0} all-gather(bf16[1,4096]{1,0} %x), dims={0}
  %ar = f32[256,128]{1,0} all-reduce(f32[256,128]{1,0} %y), to_apply=%sum
  %rs = f32[2,128]{1,0} reduce-scatter(f32[32,128]{1,0} %z), dims={0}
  %a2a = bf16[8,64]{1,0} all-to-all(bf16[8,64]{1,0} %w), dims={0}
  %cp = bf16[4,4]{1,0} collective-permute(bf16[4,4]{1,0} %v), pairs={{0,1}}
  %other = f32[10]{0} add(f32[10]{0} %a, f32[10]{0} %b)
"""

    def test_kinds_and_bytes(self):
        st = ha.parse_collectives(self.HLO)
        assert st.count_by_kind["all-gather"] == 1
        assert st.count_by_kind["all-reduce"] == 1
        assert st.count_by_kind["reduce-scatter"] == 1
        assert st.count_by_kind["all-to-all"] == 1
        assert st.count_by_kind["collective-permute"] == 1
        # all-gather counts output bytes
        assert st.bytes_by_kind["all-gather"] == 16 * 4096 * 2
        # all-reduce counts 2x input
        assert st.bytes_by_kind["all-reduce"] == 2 * 256 * 128 * 4
        # reduce-scatter counts input
        assert st.bytes_by_kind["reduce-scatter"] == 32 * 128 * 4

    def test_async_pairs_counted_once(self):
        hlo = """
  %s = bf16[8,8]{1,0} all-gather-start(bf16[1,8]{1,0} %x), dims={0}
  %d = bf16[8,8]{1,0} all-gather-done(bf16[8,8]{1,0} %s)
"""
        st = ha.parse_collectives(hlo)
        assert st.count_by_kind["all-gather"] == 1


class TestDeltaConfigs:
    @pytest.mark.parametrize("arch", sorted(configs.ARCHS))
    def test_repeat_counts(self, arch):
        cfg = configs.get(arch)
        c1, c2, repeat = delta_configs(cfg)
        assert c1.unroll_layers and c2.unroll_layers
        if cfg.family == "hybrid":
            assert (c2.num_layers - c1.num_layers) == cfg.attn_layer_period
            assert repeat * cfg.attn_layer_period == cfg.num_layers
        elif cfg.family == "audio":
            assert repeat == cfg.num_layers
        else:
            assert c2.num_layers - c1.num_layers == 1
            assert repeat == cfg.num_layers - cfg.first_dense_layers

    def test_extrapolation_identity(self):
        """cost(L1) + (repeat-1)*(cost(L2)-cost(L1)) is exact for affine
        per-layer costs."""
        per_layer, base = 7.0, 100.0
        cfg = configs.get("stablelm-1.6b")
        c1, c2, repeat = delta_configs(cfg)
        cost = lambda n: base + per_layer * n  # noqa: E731
        total = cost(c1.num_layers) + (repeat - 1) * (
            cost(c2.num_layers) - cost(c1.num_layers)
        )
        assert total == base + per_layer * cfg.num_layers


class TestRules:
    def test_resolve_drops_missing_axes(self):
        mesh = jax.make_mesh((1,), ("data",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        rules = resolve_rules(dict(RULESETS["train"]), mesh, 256)
        assert rules["batch"] == ("data",)
        assert rules["heads"] is None  # "model" axis doesn't exist

    def test_batch_1_unsharded(self):
        class FakeMesh:
            shape = {"data": 16, "model": 16}
            axis_names = ("data", "model")

        rules = resolve_rules(dict(RULESETS["decode"]), FakeMesh(), 1)
        assert rules["batch"] is None  # 1 % 16 != 0 -> replicate batch

    def test_cells_for_counts(self):
        from repro.launch.cells import all_cells

        cells = all_cells()
        assert len(cells) == 32  # 10x3 + 2 long_500k
        assert ("rwkv6-3b", "long_500k") in cells
        assert ("jamba-v0.1-52b", "long_500k") in cells
        assert ("phi3-medium-14b", "long_500k") not in cells


class TestSanitize:
    def _mesh(self):
        # uses whatever devices exist; spec math only needs mesh.shape
        return jax.make_mesh((1,), ("model",),
                             axis_types=(jax.sharding.AxisType.Auto,))

    def test_even_dims_untouched(self):
        mesh = jax.make_mesh((1,), ("model",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        spec = Spec((32, 64), ("heads", None))
        ps = sanitize_partition_spec(spec, {"heads": "model"}, mesh)
        assert ps == P("model", None)

    def test_uneven_dim_spills(self):
        class FakeMesh:
            shape = {"model": 16}
            axis_names = ("model",)

        spec = Spec((40, 128), ("heads", "head_dim"))  # 40 % 16 != 0
        ps = sanitize_partition_spec(spec, {"heads": "model"}, FakeMesh())
        assert ps == P(None, "model")  # spilled to head_dim (128 % 16 == 0)

    def test_unplaceable_axis_dropped(self):
        class FakeMesh:
            shape = {"model": 16}
            axis_names = ("model",)

        spec = Spec((6, 7), ("heads", None))
        ps = sanitize_partition_spec(spec, {"heads": "model"}, FakeMesh())
        assert ps == P(None, None)


class TestAnalyticModels:
    def test_active_params_moe_discount(self):
        from repro.models.model import build

        cfg = configs.get("deepseek-v2-lite-16b")
        model = build(cfg)
        total = model.num_params()
        active = ha.active_params(cfg, model)
        assert active < 0.25 * total  # 6/64 routing + shared + dense

    def test_model_flops_formulas(self):
        from repro.models.model import build

        cfg = configs.get("stablelm-1.6b")
        model = build(cfg)
        train = ha.model_flops_for(cfg, model, SHAPES["train_4k"])
        prefill = ha.model_flops_for(cfg, model, SHAPES["prefill_32k"])
        decode = ha.model_flops_for(cfg, model, SHAPES["decode_32k"])
        n = ha.active_params(cfg, model)
        assert train == pytest.approx(6 * n * 256 * 4096)
        assert prefill == pytest.approx(2 * n * 32 * 32768)
        assert decode == pytest.approx(2 * n * 128)

    def test_roofline_dominance(self):
        r = ha.roofline_terms(
            flops=197e12, hbm_bytes=1e9, collective_bytes=1e9,
            model_flops=100e12,
        )
        assert r.dominant == "compute"
        assert r.compute_s == pytest.approx(1.0)
        r = ha.roofline_terms(
            flops=1e12, hbm_bytes=819e9 * 2, collective_bytes=0,
            model_flops=1e12,
        )
        assert r.dominant == "memory"
        assert r.memory_s == pytest.approx(2.0)
