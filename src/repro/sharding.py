"""Sharding rules: FSDP over ``data`` (d_model axis) + tensor/expert parallel
over ``model`` (heads / d_ff / experts / padded-vocab), batch over
``("pod","data")``.

Rules are *path-based* over the param pytree and *divisibility-checked*
against the actual mesh, so architectures with non-divisible head counts
(hymba 25H, whisper 20H, internvl 14H) automatically fall back to replicated
attention + sharded FFN, as documented in DESIGN.md §5.
"""
from __future__ import annotations

import re
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DP = ("pod", "data")  # batch axes (filtered to the mesh's actual axes)

# --------------------------------------------------------- sharding profile
# "2d"   (default): batch→(pod,data), tensor-parallel over model (heads/
#                   d_ff/experts/vocab) + FSDP over data.
# "fsdp" (§Perf iteration 3): NO tensor parallelism — the model axis joins
#        the batch axes and params shard FSDP-only over data. Wins for
#        small models where 16-way tensor parallelism makes matmul shards
#        too skinny (low arithmetic intensity) and per-layer collectives
#        dominate. Select with REPRO_SHARDING_PROFILE=fsdp. (MoE expert
#        parallelism requires the 2d profile.)
import os as _os

_PROFILE = _os.environ.get("REPRO_SHARDING_PROFILE", "2d")


def set_profile(name: str) -> None:
    global _PROFILE
    assert name in ("2d", "fsdp"), name
    _PROFILE = name


def profile() -> str:
    return _PROFILE


def batch_axes() -> Tuple[str, ...]:
    return ("pod", "data", "model") if _PROFILE == "fsdp" else ("pod",
                                                                "data")

# ------------------------------------------------------- active mesh context
# The launcher/dry-run register the mesh here so model code (e.g. the MoE
# expert-parallel shard_map path) can build explicit collectives. ``None``
# means single-host eager/smoke mode — models fall back to pure-jnp paths.

_ACTIVE_MESH = None


def set_active_mesh(mesh) -> None:
    global _ACTIVE_MESH
    _ACTIVE_MESH = mesh


def active_mesh():
    return _ACTIVE_MESH


class use_mesh:
    """Context manager: ``with use_mesh(mesh): ...`` activates a mesh for
    both GSPMD constraints (jax ``with mesh``) and the explicit shard_map
    paths."""

    def __init__(self, mesh):
        self.mesh = mesh

    def __enter__(self):
        set_active_mesh(self.mesh)
        self._ctx = self.mesh
        self._ctx.__enter__()
        return self.mesh

    def __exit__(self, *exc):
        set_active_mesh(None)
        return self._ctx.__exit__(*exc)


# ------------------------------------------------------------ generic helpers

try:  # jax >= 0.6: graduated to the top-level namespace
    from jax import shard_map as _shard_map
except ImportError:
    from jax.experimental.shard_map import shard_map as _shard_map

import inspect as _inspect

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


def _filter_entry(entry, axes):
    if entry is None:
        return None
    # the DP marker expands to the profile's batch axes
    if isinstance(entry, (tuple, list)) and set(entry) == {"pod", "data"}:
        entry = batch_axes()
    elif _PROFILE == "fsdp":
        # fsdp profile: the model axis belongs to the batch — drop it from
        # every non-batch (tensor-parallel) entry
        if entry == "model":
            return None
        if isinstance(entry, (tuple, list)):
            entry = tuple(a for a in entry if a != "model") or None
            if entry is None:
                return None
    if isinstance(entry, (tuple, list)):
        kept = tuple(a for a in entry if a in axes)
        return kept if kept else None
    return entry if entry in axes else None


def filter_spec(spec: P, mesh) -> P:
    axes = set(mesh.axis_names)
    return P(*[_filter_entry(e, axes) for e in spec])


def check_divisible(spec: P, shape, mesh) -> P:
    """Drop sharded axes whose dimension doesn't divide evenly."""
    sizes = dict(zip(mesh.axis_names,
                     mesh.devices.shape if isinstance(mesh, Mesh)
                     else mesh.axis_sizes))
    out = []
    for i, entry in enumerate(spec):
        if entry is None or i >= len(shape):
            out.append(entry)
            continue
        names = entry if isinstance(entry, tuple) else (entry,)
        total = 1
        for n in names:
            total *= sizes.get(n, 1)
        out.append(entry if shape[i] % total == 0 else None)
    return P(*out)


def shard_act(x, *entries):
    """Activation sharding constraint; no-op outside a mesh context.

    Uses the framework's registered active mesh (``use_mesh``) first — the
    legacy ``with mesh:`` context does NOT populate jax's abstract mesh in
    current JAX, so relying on it silently drops every constraint."""
    mesh = active_mesh()
    if mesh is None:
        try:
            m = jax.sharding.get_abstract_mesh()
            if m is not None and m.axis_names:
                mesh = m
        except Exception:
            return x
    if mesh is None or not getattr(mesh, "axis_names", ()):
        return x
    spec = filter_spec(P(*entries), mesh)
    spec = check_divisible(spec, x.shape, mesh)
    try:
        if isinstance(mesh, Mesh):
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, spec))
        return jax.lax.with_sharding_constraint(x, spec)
    except Exception:
        return x


def dp_spec(mesh) -> Tuple[str, ...]:
    return tuple(a for a in batch_axes() if a in mesh.axis_names)


def shard_attn_act(x, *, head_axis: int = 2, seq_axis: int = 1):
    """Attention activation constraint (B, S, H, Dh).

    Prefer sharding heads over ``model``; when the head count does not
    divide the model axis (hymba 25H, whisper 20H, internvl 14H on a
    16-way axis) fall back to CONTEXT PARALLELISM — shard the q sequence
    over ``model`` — instead of full replication (§Perf iteration 2:
    16× attention activation replication removed)."""
    mesh = active_mesh()
    if mesh is None or not getattr(mesh, "axis_names", ()):
        return x
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    msize = sizes.get("model", 1)
    dp = dp_spec(mesh)
    nd = x.ndim
    entries = [None] * nd
    entries[0] = dp if dp else None
    if _PROFILE != "fsdp":   # fsdp: model is already a batch axis
        if x.shape[head_axis] % msize == 0:
            entries[head_axis] = "model"
        elif x.shape[seq_axis] % msize == 0:
            entries[seq_axis] = "model"
    spec = check_divisible(P(*entries), x.shape, mesh)
    try:
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
    except Exception:
        return x


# ------------------------------------------------------------- param rules

_RULES = [
    # (regex over "/"-joined path, spec for the UNSTACKED param)
    (r"(^|/)embed$", P("model", "data")),
    (r"(^|/)lm_head$", P("data", "model")),
    (r"(^|/)(dec_)?pos_embed$", P(None, "model")),
    (r"(^|/)meta_tokens$", P(None, None)),
    (r"(^|/)vision_proj$", P("data", "model")),
    (r"attn.*/wq$", P("data", "model", None)),
    (r"attn.*/w[kv]$", P("data", "model", None)),
    (r"attn.*/wo$", P("model", "data")),
    (r"attn.*/b[qkv]$", P(None, None)),
    (r"(mlp|cross_mlp)/wi(_gate|_up)?$", P("data", "model")),
    (r"(mlp|cross_mlp)/wo$", P("model", "data")),
    (r"(mlp|cross_mlp)/bi$", P("model",)),
    (r"(mlp|cross_mlp)/bo$", P(None,)),
    (r"moe/router$", P("data", None)),
    (r"moe/wi(_gate|_up)$", P("model", "data", None)),
    (r"moe/wo$", P("model", None, "data")),
    (r"(mamba|ssm)/w[zx]$", P("data", "model")),
    (r"(mamba|ssm)/w[BC]$", P("data", None)),
    (r"(mamba|ssm)/wdt$", P("data", None)),
    (r"(mamba|ssm)/conv_x$", P(None, "model")),
    (r"(mamba|ssm)/out_proj$", P("model", "data")),
    (r"(mamba|ssm)/gate_norm/scale$", P("model",)),
]


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def spec_for_param(path_str: str, ndim: int) -> P:
    stacked = bool(re.search(r"(^|/)(layers|enc_layers|dec_layers)(/|$)", path_str))
    base_ndim = ndim - (1 if stacked else 0)
    spec = None
    for pat, s in _RULES:
        if re.search(pat, path_str):
            spec = s
            break
    if spec is None:
        spec = P(*([None] * base_ndim))
    entries = list(spec)
    # pad/truncate to the param's ndim
    while len(entries) < base_ndim:
        entries.append(None)
    entries = entries[:base_ndim]
    if stacked:
        entries = [None] + entries
    return P(*entries)


def param_shardings(params, mesh):
    """NamedSharding tree for a param pytree (divisibility-safe)."""
    def one(path, leaf):
        ps = _path_str(path)
        spec = spec_for_param(ps, jnp.ndim(leaf))
        spec = filter_spec(spec, mesh)
        spec = check_divisible(spec, jnp.shape(leaf), mesh)
        return NamedSharding(mesh, spec)
    return jax.tree_util.tree_map_with_path(one, params)


def param_specs_abstract(abstract_params, mesh):
    def one(path, leaf):
        ps = _path_str(path)
        spec = spec_for_param(ps, len(leaf.shape))
        spec = filter_spec(spec, mesh)
        spec = check_divisible(spec, leaf.shape, mesh)
        return NamedSharding(mesh, spec)
    return jax.tree_util.tree_map_with_path(one, abstract_params)


def replicated(mesh):
    return NamedSharding(mesh, P())


def batch_shardings(batch, mesh):
    """Shard the leading (batch) dim of every leaf over ("pod","data")."""
    dp = dp_spec(mesh)

    def one(leaf):
        spec = P(dp, *([None] * (jnp.ndim(leaf) - 1))) if jnp.ndim(leaf) else P()
        spec = check_divisible(spec, jnp.shape(leaf), mesh)
        return NamedSharding(mesh, spec)
    return jax.tree_util.tree_map(one, batch)
