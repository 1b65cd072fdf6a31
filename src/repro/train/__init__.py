from repro.train.optimizer import adam_init, adam_update
from repro.train.losses import (weighted_softmax_xent, weighted_mse,
                                weighted_binary_xent)
from repro.train.vfl import EngineStats, TrainReport, train_scan

__all__ = [
    "adam_init", "adam_update",
    "weighted_softmax_xent", "weighted_mse", "weighted_binary_xent",
    "EngineStats", "TrainReport", "train_scan",
]
