"""The work the tolerance-stopped mapping LP needs, from its packed shapes.

The LP is solved by PDHG over a batch of B lanes padded to one shape
(n tasks, m node types, D dimensions, T' trimmed slots).  One iteration
of a lane applies the congestion operator forward and backward and
projects both iterates.  In the interval form of the operator, which is
O((n + T') m D), the least an iteration can move is one read and one
write of the primal iterate x (n, m) and the dual iterate y (T', m, D),
one read of the weights w (n, m, D) and of the task spans (2n int32).
Its arithmetic is a few operations per element of those arrays, far
below what the memory traffic allows, so the bound is the bytes.

These counts do not depend on which operator form the program runs
(dense products, prefix sums or a kernel): a faster form shows as less
device time for the same work.
"""

from __future__ import annotations


def bytes_per_iteration(n: int, m: int, D: int, Tp: int,
                        itemsize: int = 4) -> int:
    """Bytes one lane must move in one PDHG iteration."""
    x = n * m
    y = Tp * m * D
    w = n * m * D
    return itemsize * (2 * x + 2 * y + w) + 2 * 4 * n


def flops_per_iteration(n: int, m: int, D: int, Tp: int) -> int:
    """Floating-point operations of one lane's iteration in interval
    form: scale and scatter the weighted primal at starts and ends,
    prefix-sum over slots, the same backward, and both projections."""
    return 2 * (4 * n * m * D + 3 * Tp * m * D + 2 * n * m)


def lp_work(shape: tuple[int, int, int, int, int], lane_iters) -> tuple[int, int]:
    """(bytes, flops) an LP dispatch of packed ``shape`` (B, n, m, D, T')
    needs, given the iterations each lane ran."""
    B, n, m, D, Tp = shape
    iters = [int(i) for i in lane_iters]
    if len(iters) != B:
        raise ValueError(f"{len(iters)} lane iteration counts for B={B}")
    total = sum(iters)
    return (total * bytes_per_iteration(n, m, D, Tp),
            total * flops_per_iteration(n, m, D, Tp))


def roofline_pct(bytes_: float, flops: float, device_s: float,
                 peaks: dict) -> float | None:
    """Share, in %, of the device time that the least possible time for
    this work would take: max(bytes / HBM bandwidth, flops / peak) over
    the time the LP's programs ran.  None when nothing ran."""
    if device_s <= 0 or (bytes_ <= 0 and flops <= 0):
        return None
    least = max(bytes_ / peaks["hbm_bytes_per_s"],
                flops / peaks["flops_per_s"])
    return 100.0 * least / device_s
