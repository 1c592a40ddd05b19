"""The sweep's roofline arithmetic against the program's own accounting."""

import pytest

from lib import roofline


@pytest.mark.parametrize("shape", [(8192, 128, 1344), (96, 128, 1344),
                                   (8, 128, 512), (1000, 2000, 300)])
def test_matches_kernelstats(shape):
    from repro.obs.kernelstats import sweep_kernel_stats

    ks = sweep_kernel_stats(*shape)
    cost = roofline.sweep_cost(*shape)
    assert cost["flops"] == ks.flops
    assert cost["block"] == ks.block
    assert cost["padded"] == ks.padded
    assert cost["hbm_passes"] == ks.hbm_passes


def test_grid_sweep_shape():
    # 1,024 rows x 8 horizon prefixes, 128 levels, 8 weeks of hours.
    cost = roofline.sweep_cost(8192, 128, 1344)
    assert cost["flops"] == 4 * 8192 * 1344 * 128
    assert cost["hbm_passes"] == 1
    pp, gg, tt = cost["padded"]
    assert cost["bytes"] == 2 * pp * tt * 4 + 3 * pp * gg * 4


def test_share_is_bytes_bound_on_v5e():
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    cost = roofline.sweep_cost(8192, 128, 1344)
    least = cost["bytes"] / 819e9
    value, bound = roofline.share(cost, 10, 10 * least * 4, peaks)
    assert bound == "bytes"
    assert value == pytest.approx(25.0)
