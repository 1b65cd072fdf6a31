"""Mesh helpers for the device stages: which axis a batch shards over,
``shard_map`` wrappers, batch-row padding, and the (data, model) layout
the VFL train engine reads from a mesh (DESIGN.md §5, §8).
"""
from __future__ import annotations

import inspect as _inspect
from typing import Optional

from jax.sharding import PartitionSpec as P

try:  # jax >= 0.6: graduated to the top-level namespace
    from jax import shard_map as _shard_map
except ImportError:
    from jax.experimental.shard_map import shard_map as _shard_map

# the replication-check kwarg was renamed check_rep -> check_vma in jax 0.6
_SHARD_MAP_NO_CHECK = {
    ("check_vma" if "check_vma" in _inspect.signature(_shard_map).parameters
     else "check_rep"): False}


def shard_axis_name(mesh) -> str:
    """The mesh axis the PSI/CSS batch paths shard over: ``data`` when the
    mesh has one, else the mesh's first axis (1-D sweep meshes)."""
    names = tuple(mesh.axis_names)
    return "data" if "data" in names else names[0]


def batch_shard_map(fn, mesh, axis: str):
    """shard_map ``fn`` (batched over every arg/out's LEADING dim) so the
    batch splits over one mesh axis — the leading dim must be a multiple
    of the axis size (see ``pad_batch_rows``).  Per-row compute is
    untouched: each device runs the identical per-row program on its
    rows, which is what keeps sharded results byte-identical to the
    single-device path (DESIGN.md §5)."""
    spec = P(axis)
    return _shard_map(fn, mesh=mesh, in_specs=spec, out_specs=spec,
                      **_SHARD_MAP_NO_CHECK)


def spec_shard_map(fn, mesh, in_specs, out_specs):
    """shard_map with explicit per-arg PartitionSpecs (replication check
    off, matching ``batch_shard_map``).  For paths that mix sharded and
    replicated arguments — e.g. the VFL train engine, whose ``(params,
    opt)`` carry is replicated while the per-step batch axis shards —
    where the all-leading-dims contract of ``batch_shard_map`` doesn't
    fit."""
    return _shard_map(fn, mesh=mesh, in_specs=in_specs,
                      out_specs=out_specs, **_SHARD_MAP_NO_CHECK)


def padded_rows(b: int, n_shards: int) -> int:
    """The leading-dim size ``pad_batch_rows`` pads a B-row batch to."""
    return b + (-b) % n_shards


def pad_batch_rows(arrays, n_shards: int):
    """Pad every array's leading dim (shared batch size B) to
    ``padded_rows(B, n_shards)`` by repeating row 0.  Returns
    (padded, B): callers truncate outputs back to B rows.  Row-0 filler
    keeps the padded rows shape- and dtype-representative so the
    per-row program is identical across shards (outputs for filler rows
    are discarded)."""
    import numpy as _np
    b = arrays[0].shape[0]
    pad = padded_rows(b, n_shards) - b
    if pad == 0:
        return list(arrays), b
    out = []
    for a in arrays:
        filler = _np.repeat(_np.asarray(a[:1]), pad, axis=0)
        out.append(_np.concatenate([_np.asarray(a), filler], axis=0))
    return out, b


def resolve_batch_mesh(mesh, shard_axis: Optional[str] = None):
    """(mesh, axis, n_shards) for the batch-sharding paths; ``mesh=None``
    or a 1-sized axis collapses to (None, None, 1) — the plain
    single-device dispatch path.  One definition so PSI and CSS always
    shard over the same axis of a shared mesh.  An explicit
    ``shard_axis`` that the mesh doesn't have raises rather than
    silently running unsharded."""
    if mesh is None:
        return None, None, 1
    if shard_axis is not None and shard_axis not in tuple(mesh.axis_names):
        raise ValueError(f"shard_axis {shard_axis!r} not in mesh axes "
                         f"{tuple(mesh.axis_names)}")
    axis = shard_axis or shard_axis_name(mesh)
    n = mesh_axis_size(mesh, axis)
    if n <= 1:
        return None, None, 1
    return mesh, axis, n


def resolve_train_mesh(mesh, shard_axis: Optional[str] = None):
    """(mesh, data_axis, n_data, model_axis, n_model) for the VFL train
    engine (DESIGN.md §8).

    Accepts the 1-D ``("data",)`` meshes of the PSI/CSS paths *and* 2-D
    ``(data, model)`` train meshes (``launch.mesh.make_train_mesh``):

    - ``data_axis`` shards the per-step batch columns (PR-4 semantics);
      ``shard_axis`` overrides its name, and a name the mesh doesn't
      have raises rather than silently running unsharded.
    - ``model_axis`` — the mesh's ``"model"`` axis when present (and not
      claimed as the data axis) — shards the M-client bottom axis:
      per-client weight blocks live on their own devices and the
      client→server activation send lowers to an all-gather over it.

    ``mesh=None`` or an all-1-sized mesh collapses to
    ``(None, None, 1, None, 1)`` — the plain single-device path — so the
    knob is safe to leave on everywhere.
    """
    if mesh is None:
        return None, None, 1, None, 1
    names = tuple(mesh.axis_names)
    if shard_axis is not None and shard_axis not in names:
        raise ValueError(f"shard_axis {shard_axis!r} not in mesh axes "
                         f"{names}")
    data_axis = shard_axis or shard_axis_name(mesh)
    model_axis = "model" if ("model" in names and "model" != data_axis) \
        else None
    n_data = mesh_axis_size(mesh, data_axis)
    n_model = mesh_axis_size(mesh, model_axis) if model_axis else 1
    if n_model <= 1:
        model_axis, n_model = None, 1
    if n_data <= 1 and n_model <= 1:
        return None, None, 1, None, 1
    return mesh, data_axis, n_data, model_axis, n_model


def mesh_axis_size(mesh, name: str) -> int:
    """Size of mesh axis ``name``.  Raises on a mesh whose axis sizes
    cannot be read, or that lacks the axis: answering 1 would quietly
    run a sharded path unsharded."""
    try:
        sizes = dict(mesh.shape)
    except (AttributeError, TypeError, ValueError) as e:
        raise TypeError(f"cannot read the axis sizes of mesh {mesh!r}") from e
    if name not in sizes:
        raise ValueError(f"mesh axis {name!r} not in {tuple(sizes)}")
    return int(sizes[name])
