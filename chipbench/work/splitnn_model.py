"""Flops per row of the whole split MLP (paper §5.1), for ``train_mfu``
and ``pipeline_mfu``.

Forward: each party's bottom ``d_m -> out`` (2 * sum(d_m) * out), the
top ``M*out -> hidden`` (2 * M * out * hidden) and ``hidden -> n_out``
(2 * hidden * n_out).  A training step also needs each layer's weight
gradient (as much again) and the gradient of every layer's input except
the bottoms', whose input is the data (as much again for the top only).
Work the program does beyond that, such as the gradient of the data,
does not count.
"""
from __future__ import annotations

from typing import Sequence


def _layers(widths: Sequence[int], out: int, hidden: int, n_out: int):
    bottom = 2 * int(sum(widths)) * out
    top = 2 * len(widths) * out * hidden + 2 * hidden * n_out
    return bottom, top


def forward_flops(widths: Sequence[int], out: int, hidden: int,
                  n_out: int) -> int:
    bottom, top = _layers(widths, out, hidden, n_out)
    return bottom + top


def train_flops(widths: Sequence[int], out: int, hidden: int,
                n_out: int) -> int:
    bottom, top = _layers(widths, out, hidden, n_out)
    return 2 * bottom + 3 * top
