"""Operations and HBM bytes of one launch of the Pallas commitment sweep,
from its shape.

Copied from the program's accounting (``obs.kernelstats`` and the block
plan of ``kernels.commitment_sweep.ops``) so the yardstick does not move
with the program: the FLOP count is the bench convention 4 P T G (compare
and accumulate of over and under per (row, hour, level)); the trace
(P_pad x T_pad demand and weights) streams once per candidate tile,
ceil(G_pad / bg) times, and the levels and both outputs once."""

from __future__ import annotations

VMEM_BUDGET = 4 * 1024 * 1024
HBM_PASS_BUDGET = 8


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def block_plan(p: int, g: int, t: int) -> tuple[int, int, int]:
    """(bp, bg, bt) the sweep launches with at a (P, G, T) shape."""
    bp = 8
    bg = max(128, 128 * -(-g // (128 * HBM_PASS_BUDGET)))
    bg = min(bg, _round_up(g, 128))
    bg_max = VMEM_BUDGET // (bp * 128 * 4) // 128 * 128
    bg = min(bg, max(bg_max, 128))
    bt = min(512, _round_up(t, 128))
    while bt > 128 and bp * bg * bt * 4 > VMEM_BUDGET:
        bt -= 128
    return bp, bg, bt


def sweep_cost(p: int, g: int, t: int) -> dict:
    """{flops, bytes, hbm_passes, block, padded} of one launch."""
    bp, bg, bt = block_plan(p, g, t)
    pp, gg, tt = _round_up(p, bp), _round_up(g, bg), _round_up(t, bt)
    passes = -(-gg // bg)
    return {
        "flops": 4 * p * t * g,
        "bytes": passes * 2 * pp * tt * 4 + 3 * pp * gg * 4,
        "hbm_passes": passes,
        "block": (bp, bg, bt),
        "padded": (pp, gg, tt),
    }


def share(cost: dict, launches: int, seconds: float, peaks: dict):
    """(percent of the roofline, bound) over ``launches`` launches that
    took ``seconds`` on the device.  The sweep's arithmetic is float32 on
    the vector units, for which a v5e publishes no peak; its operations
    are held to the published bf16 peak, an upper bound, so the bytes
    bound is the one that binds."""
    t_ops = cost["flops"] / peaks["bf16_flops_per_s"]
    t_bytes = cost["bytes"] / peaks["hbm_bytes_per_s"]
    bound = "bytes" if t_bytes >= t_ops else "operations"
    return 100.0 * launches * max(t_ops, t_bytes) / seconds, bound
