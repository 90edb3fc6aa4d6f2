"""Operations and bytes of the hop megakernel, from its shapes.

What one hop must read, whatever the implementation: for each of the
``B * W`` frontier nodes its ``m_out`` selected neighbour ids (int32), and
for each of those candidates its vector row at the table's itemsize. It
computes ``2 * d`` operations per candidate (a squared L2 distance). The
visited structure is not counted: it is an implementation choice. The count
is exact when no candidate was visited before and every query of the batch
is still searching; otherwise the hop needs less, so the roofline share
computed from it is an upper bound on the true share's numerator.
"""
from __future__ import annotations


def hop_bytes(B: int, W: int, m_out: int, d: int, itemsize: int) -> int:
    return B * W * m_out * (4 + d * itemsize)


def hop_flops(B: int, W: int, m_out: int, d: int) -> int:
    return B * W * m_out * 2 * d


def least_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of operations over
    peak operations per second and bytes over peak bandwidth."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
