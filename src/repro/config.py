"""Typed engine configuration (DESIGN.md §13).

Every capability added since the seed (mesh sharding, quantized comm,
gather fusion, tracing, PSI backends, ...) landed as another kw-only
knob on ``run_pipeline``/``train_splitnn``/the MPSI family — 17 kwargs
on the pipeline alone before this module existed.  The sprawl is now
fenced by two frozen dataclasses:

``EngineOptions``
    Knobs of the *compiled execution* layer — where programs run and in
    what shape: ``mesh``/``shard_axis`` (DESIGN.md §5/§8), the bottom
    kernel, the gather fusion, the batch tile, the
    activation wire dtype (§12), and the tracer (§10).

``AlignOptions``
    Knobs of the *alignment protocol* layer: PSI protocol flavor and
    backend, id overlap, the engine's sort mode and kernel impl, and an
    optional alignment-specific mesh (defaults to the engine mesh via
    ``with_engine_defaults``).

Both are frozen — and therefore hashable (``jax.sharding.Mesh`` hashes)
— so ``psi/engine._dispatch`` derives its executable-cache key directly
from the config object instead of a hand-flattened (impl, mesh, axis)
tuple, and ``lru_cache`` factories can key on whole option objects.

Legacy kwargs still work everywhere through ONE shim,
``_coerce_options``: every public entry point collects ``**legacy``,
routes each key to the options class that owns it (honouring renames
like ``backend=`` → ``psi_backend``),
warns ``DeprecationWarning`` once, and builds the same frozen object
the new path receives — so the two call styles are bitwise-identical by
construction (property-tested in tests/test_config.py).  Mixing a
config object with legacy kwargs that target the same object is a
``TypeError``.  New APIs (``repro.psi.delta.DeltaMPSI``) accept ONLY
the config objects.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Dict, Optional, Tuple

__all__ = ["EngineOptions", "AlignOptions", "ALIGN_ALIASES"]


@dataclasses.dataclass(frozen=True)
class EngineOptions:
    """Execution-layer options (training / serving / device placement).

    ``mesh``/``shard_axis`` shard every device stage through one knob
    (1-D ``("data",)`` or 2-D ``(data, model)`` meshes);
    ``bottom_impl`` ("ref"|"pallas")/``fuse_gather``/``block_b``
    configure the block-diagonal bottom pass of the one training engine
    (``repro.train.vfl.train_scan``); ``quant`` narrows the activation
    wire dtype ("int8"|"fp8"); ``trace`` turns on the obs layer (a
    ``repro.obs.Tracer`` or any truthy value).

    ``train_engine`` is not a field: the benchmark's tests still pass
    ``train_engine="scan"``, the only engine there is, so that value is
    accepted and dropped, and any other is a ``ValueError``."""
    mesh: Any = None
    shard_axis: Optional[str] = None
    train_engine: dataclasses.InitVar[str] = "scan"
    bottom_impl: str = "ref"
    fuse_gather: bool = True
    block_b: int = 512
    quant: Optional[str] = None
    trace: Any = None

    def __post_init__(self, train_engine: str) -> None:
        if train_engine != "scan":
            raise ValueError(f"train_engine must be 'scan', the only "
                             f"engine, not {train_engine!r}")


@dataclasses.dataclass(frozen=True)
class AlignOptions:
    """Alignment-protocol options shared by ``tpsi``/``mpsi``/
    ``run_psi``/``run_pipeline`` and the delta-PSI subsystem.

    ``protocol`` is the TPSI flavor ("rsa"|"oprf"); ``psi_backend``
    "host" (per-element protocol sessions) or "device" (batched
    ``repro.psi.engine`` dispatches); ``overlap`` the synthetic common
    id fraction (paper §5.3); ``sort`` the engine's tag-sort mode
    (None = platform default, "host"|"device"); ``impl`` the kernel
    implementation ("pallas"|"ref"); ``mesh``/``shard_axis`` an
    alignment-stage mesh (``None`` inherits the engine mesh through
    ``with_engine_defaults``)."""
    protocol: str = "rsa"
    psi_backend: str = "host"
    overlap: float = 0.7
    sort: Optional[str] = None
    impl: str = "pallas"
    mesh: Any = None
    shard_axis: Optional[str] = None

    def with_engine_defaults(self, engine: EngineOptions) -> "AlignOptions":
        """Inherit the engine mesh when no alignment mesh was given —
        what the legacy single-``mesh=`` kwarg did implicitly."""
        if self.mesh is None and engine.mesh is not None:
            return dataclasses.replace(self, mesh=engine.mesh,
                                       shard_axis=self.shard_axis
                                       or engine.shard_axis)
        return self


# legacy kwarg name -> options field (identity names resolve implicitly)
ALIGN_ALIASES: Dict[str, str] = {"backend": "psi_backend",
                                 "engine_impl": "impl"}


def _coerce_options(caller: str, legacy: Dict[str, Any],
                    *specs: Tuple[str, type, Any, Dict[str, str]]
                    ) -> Tuple[Any, ...]:
    """THE deprecation shim: resolve (options object | legacy kwargs)
    into frozen config objects — one implementation for every entry
    point, so the two call styles cannot drift.

    ``specs`` is ``(param_name, options_cls, provided_value, aliases)``
    per accepted config object, in routing-priority order (a legacy key
    lands on the FIRST class that has its field — e.g. ``mesh=`` on
    ``run_pipeline`` routes to ``EngineOptions`` and reaches alignment
    via ``with_engine_defaults``, exactly like the old single knob).

    Unknown keys raise ``TypeError`` (same contract as a real
    signature); any legacy key warns ``DeprecationWarning`` once; a
    legacy key plus a provided object for the same class is a
    ``TypeError`` (ambiguous intent).
    """
    buckets: list = [{} for _ in specs]
    if legacy:
        unknown = []
        for key, val in legacy.items():
            for bucket, (_, cls, _, aliases) in zip(buckets, specs):
                field = aliases.get(key, key)
                if field in cls.__dataclass_fields__:  # type: ignore[attr-defined]
                    bucket[field] = val
                    break
            else:
                unknown.append(key)
        if unknown:
            raise TypeError(
                f"{caller}() got unexpected keyword argument(s) "
                f"{sorted(unknown)}")
        repl = " / ".join(f"{name}={cls.__name__}(...)"
                          for name, cls, _, _ in specs)
        warnings.warn(
            f"{caller}(): keyword(s) {sorted(legacy)} are deprecated; "
            f"pass {repl} (repro.config)", DeprecationWarning,
            stacklevel=3)
    out = []
    for bucket, (name, cls, given, _) in zip(buckets, specs):
        if bucket and given is not None:
            raise TypeError(
                f"{caller}(): pass either {name}={cls.__name__}(...) or "
                f"legacy kwarg(s) {sorted(bucket)}, not both")
        out.append(given if given is not None else cls(**bucket))
    return tuple(out)
