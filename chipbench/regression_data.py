"""The benchmark's own copy of the regression deployment generator.

Copied from the program's ``repro.data.synthetic.make_dataset`` (its
regression branch, the YP signature of the paper's Table 1) with the two
numbers that branch fixes made parameters of the configuration: the
count of latent modes the rows are drawn around, and the target noise,
given as a multiple of the clean signal's standard deviation.  Drawn
from ``seed`` with numpy; nothing here imports the program.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import numpy as np

from chipbench import datagen


@dataclasses.dataclass(frozen=True)
class RegressionTable:
    """A regression dataset signature: rows, features (paper Table 1),
    the latent modes, their spread, and the target's noise."""
    n_instances: int
    n_features: int
    modes: int
    margin: float
    noise: float
    target_noise: float


def make_table(spec: RegressionTable, seed: int
               ) -> Tuple[np.ndarray, np.ndarray]:
    """Rows around ``modes`` Gaussian centres; the target a sparse linear
    function of the row plus noise of ``target_noise`` times the clean
    signal's standard deviation, scaled to mean 50 and standard deviation
    15 like YearPredictionMSD's years: (X (N, d) float32, y (N,) float32)."""
    rng = np.random.default_rng(seed)
    n, d = spec.n_instances, spec.n_features
    centers = rng.normal(0, spec.margin, (spec.modes, d))
    assign = rng.integers(0, spec.modes, n)
    x = centers[assign] + rng.normal(0, spec.noise, (n, d))
    w_true = rng.normal(0, 1, (d,)) * (rng.random(d) < 0.4)
    signal = x @ w_true
    y = signal + spec.target_noise * signal.std() * rng.normal(0, 1, n)
    y = 50 + 15 * (y - y.mean()) / (y.std() + 1e-9)
    return x.astype(np.float32), y.astype(np.float32)


def deployment(spec: RegressionTable, parties: int, train_share: float,
               seed: int) -> Tuple[datagen.Split, datagen.Split]:
    """The paper's protocol, as ``datagen.deployment``: train/test split by
    a seeded permutation, features evenly over the parties, the target at
    the label owner."""
    x, y = make_table(spec, seed)
    n = spec.n_instances
    order = np.random.default_rng(seed + 1).permutation(n)
    n_tr = int(n * train_share)
    return (datagen.vertical_split(x[order[:n_tr]], y[order[:n_tr]], parties),
            datagen.vertical_split(x[order[n_tr:]], y[order[n_tr:]], parties))
