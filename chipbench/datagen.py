"""The benchmark's own copies of the deployment generators.

Copied from the program's ``repro.data.synthetic`` (``make_dataset``,
``make_id_universe``), ``repro.data.vertical.partition_features`` and
``benchmarks.common.dataset_partitions``, so that no later change to the
program can move the yardstick.  Everything is drawn from ``seed`` with
numpy; nothing here imports the program.
"""
from __future__ import annotations

import dataclasses
from typing import List, Sequence, Tuple

import numpy as np


@dataclasses.dataclass(frozen=True)
class Table:
    """A dataset signature: rows, features, classes (paper Table 1)."""
    n_instances: int
    n_features: int
    n_classes: int
    modes_per_class: int = 3
    margin: float = 2.2
    noise: float = 1.0


@dataclasses.dataclass
class Split:
    """One side (train or test) of a vertically split dataset."""
    features: List[np.ndarray]     # per party, (N, d_m) float32
    labels: np.ndarray             # (N,) int64

    @property
    def n(self) -> int:
        return int(self.labels.shape[0])


def make_table(spec: Table, seed: int) -> Tuple[np.ndarray, np.ndarray]:
    """Class-structured Gaussian mixture with the table's signature:
    (X (N, d) float32, y (N,) int64)."""
    rng = np.random.default_rng(seed)
    n, d = spec.n_instances, spec.n_features
    k = spec.n_classes * spec.modes_per_class
    centers = rng.normal(0, spec.margin, (k, d))
    mode_class = np.repeat(np.arange(spec.n_classes), spec.modes_per_class)
    assign = rng.integers(0, k, n)
    x = centers[assign] + rng.normal(0, spec.noise, (n, d))
    return x.astype(np.float32), mode_class[assign].astype(np.int64)


def party_widths(n_features: int, parties: int) -> List[int]:
    """Features split evenly, the first ``d % parties`` one wider."""
    sizes = [n_features // parties] * parties
    for i in range(n_features % parties):
        sizes[i] += 1
    return sizes


def vertical_split(x: np.ndarray, y: np.ndarray, parties: int) -> Split:
    cols = np.cumsum([0] + party_widths(x.shape[1], parties))
    return Split([x[:, a:b].copy() for a, b in zip(cols[:-1], cols[1:])],
                 y.copy())


def deployment(spec: Table, parties: int, train_share: float, seed: int
               ) -> Tuple[Split, Split]:
    """The paper's protocol: train/test split by a seeded permutation,
    features evenly over the parties, labels at the label owner."""
    x, y = make_table(spec, seed)
    n = spec.n_instances
    order = np.random.default_rng(seed + 1).permutation(n)
    n_tr = int(n * train_share)
    return (vertical_split(x[order[:n_tr]], y[order[:n_tr]], parties),
            vertical_split(x[order[n_tr:]], y[order[n_tr:]], parties))


def id_universe(parties: int, n_per_party, overlap: float, seed: int
                ) -> Tuple[List[np.ndarray], np.ndarray]:
    """Per-party id sets around a common core of ``overlap`` of the
    smallest set, each in its own shuffled order.  Returns (sets, core)."""
    rng = np.random.default_rng(seed)
    sizes: Sequence[int] = ([n_per_party] * parties
                            if isinstance(n_per_party, int) else n_per_party)
    n_core = int(round(min(sizes) * overlap))
    universe = rng.permutation(int(sum(sizes) * 2 + n_core))
    core = universe[:n_core]
    cursor = n_core
    sets = []
    for n in sizes:
        extra = universe[cursor:cursor + (n - n_core)]
        cursor += n - n_core
        sets.append(rng.permutation(np.concatenate([core, extra])))
    return sets, np.sort(core)
