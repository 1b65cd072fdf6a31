"""Mesh factories for the device stages: the 1-D ``("data",)`` mesh of
the PSI/CSS batch paths and the 2-D ``(data, model)`` train mesh.

Functions (not module constants) so importing never touches jax device
state — a caller may set XLA_FLAGS before the first jax init.
"""
from __future__ import annotations

import jax


def make_data_mesh(n_devices: int | None = None, *, model: int = 1):
    """``("data",)`` mesh for the PSI/CSS batch-sharding paths
    (DESIGN.md §5) over the first ``n_devices`` local devices (all by
    default).  Works with real accelerators and with virtual CPU devices
    (``XLA_FLAGS=--xla_force_host_platform_device_count=8``), which is
    how CI exercises shard_map on every PR.

    ``model > 1`` extends the factory to the 2-D ``(data, model)`` train
    mesh (DESIGN.md §8): the device list folds into a
    ``(n_devices/model, model)`` grid — the ``data`` axis keeps the
    PR-4 batch-sharding role while ``model`` hosts the M-client bottom
    axis of the SplitNN scan engine.  PSI/CSS consume the same mesh
    unchanged (they shard over ``data`` and replicate over ``model``).
    """
    import numpy as np

    devices = jax.devices()
    if n_devices is not None:
        if n_devices > len(devices):
            raise ValueError(
                f"requested {n_devices} devices, have {len(devices)}")
        devices = devices[:n_devices]
    from jax.sharding import Mesh
    if model > 1:
        if len(devices) % model:
            raise ValueError(f"{len(devices)} devices do not fold into a "
                             f"(data, model={model}) grid")
        grid = np.asarray(devices).reshape(len(devices) // model, model)
        return Mesh(grid, ("data", "model"))
    return Mesh(np.asarray(devices), ("data",))


def make_train_mesh(data: int, model: int):
    """Explicit 2-D ``(data, model)`` train mesh over the first
    ``data * model`` local devices — the CI shape is ``(2, 4)`` on 8
    virtual CPU devices.  Equivalent to
    ``make_data_mesh(data * model, model=model)``."""
    return make_data_mesh(data * model, model=model)
