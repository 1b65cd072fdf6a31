"""Work of the block-diagonal bottom layer of the split model.

For ``rows`` real rows and parties of real widths ``widths``, each
party's bottom is a dense ``d_m -> out`` layer with bias and ReLU:
2 * rows * sum(d_m) * out flops.  It reads the rows' features, the
weights and biases once and writes the (parties, rows, out) activations
(all f32).  The forward only: the kernel computes no gradient.
"""
from __future__ import annotations

from typing import Sequence, Tuple

F = 4


def count(rows: int, widths: Sequence[int], out: int) -> Tuple[int, int]:
    """(flops, bytes) of the bottom pass over ``rows`` rows."""
    d = int(sum(widths))
    m = len(widths)
    flops = 2 * rows * d * out
    nbytes = F * (rows * d + d * out + m * out + rows * m * out)
    return flops, nbytes
