"""Work of one fused Lloyd update over one party's points.

For ``n`` real points of ``d`` real features and ``k`` real centroids:
the distance cross term x.c (2nkd) and the per-cluster sums as a
one-hot product (2nkd), plus assembling the squared distances
(||x||^2 - 2x.c + ||c||^2: 3nk) and the argmin (nk).  The update reads
the points and the centroids once and writes each point's cluster and
distance and the (k, d) sums and k counts (f32 and i32, 4 bytes each).
"""
from __future__ import annotations

from typing import Tuple

F = 4          # bytes of one f32 / i32


def count(n: int, d: int, k: int) -> Tuple[int, int]:
    """(flops, bytes) of one update call."""
    flops = 4 * n * k * d + 4 * n * k
    nbytes = F * (n * d + k * d) + F * (2 * n + k * d + k)
    return flops, nbytes
