"""Typed engine-config API (DESIGN.md §13, repro.config).

The contract under test: the legacy kwargs style and the
options-object style are the SAME call — every legacy key routes
through ``_coerce_options`` into the identical frozen dataclass the
new path receives, so training/pipeline outputs are bitwise-equal,
with a ``DeprecationWarning`` as the only observable difference.
"""
import dataclasses

import numpy as np
import pytest

from conftest import make_cls_partition
from repro.config import (ALIGN_ALIASES, AlignOptions, EngineOptions,
                          _coerce_options)
from repro.core import SplitNNConfig, run_pipeline
from repro.core.mpsi import tree_mpsi
from repro.core.splitnn import train_splitnn


@pytest.fixture(scope="module")
def part():
    return make_cls_partition(n=220, d=6, seed=3)


@pytest.fixture(scope="module")
def parts():
    full = make_cls_partition(n=300, d=8, seed=4)
    rows = np.random.default_rng(2).permutation(300)
    return full.take(rows[:220]), full.take(rows[220:])


def _cfg(model):
    return SplitNNConfig(model=model, n_classes=2, lr=0.05,
                         batch_size=64, max_epochs=6)


def _leaves(params):
    import jax
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(params)]


def assert_reports_bitwise_equal(a, b):
    assert a.losses == b.losses
    assert a.epochs == b.epochs and a.steps == b.steps
    assert a.comm_bytes == b.comm_bytes
    la, lb = _leaves(a.params), _leaves(b.params)
    assert len(la) == len(lb)
    for xa, xb in zip(la, lb):
        assert xa.dtype == xb.dtype and xa.tobytes() == xb.tobytes()


# ------------------------------------------------------------ dataclasses


def test_options_frozen_and_hashable():
    opts = EngineOptions(fuse_gather=False, block_b=256)
    with pytest.raises(dataclasses.FrozenInstanceError):
        opts.block_b = 128
    assert hash(opts) == hash(EngineOptions(fuse_gather=False,
                                            block_b=256))
    assert hash(AlignOptions()) == hash(AlignOptions())


def test_align_inherits_engine_mesh():
    eng = EngineOptions(mesh="fake-mesh", shard_axis="data")
    align = AlignOptions().with_engine_defaults(eng)
    assert align.mesh == "fake-mesh" and align.shard_axis == "data"
    pinned = AlignOptions(mesh="own").with_engine_defaults(eng)
    assert pinned.mesh == "own"


def test_alias_tables():
    assert ALIGN_ALIASES["backend"] == "psi_backend"
    assert ALIGN_ALIASES["engine_impl"] == "impl"


# ------------------------------------------------------- coercion shim


def test_coerce_unknown_kwarg_raises():
    with pytest.raises(TypeError, match="unexpected"):
        _coerce_options("f", {"bogus_knob": 1},
                        ("options", EngineOptions, None, {}))


def test_coerce_mixing_object_and_legacy_raises():
    with pytest.raises(TypeError):
        _coerce_options("f", {"block_b": 64},
                        ("options", EngineOptions, EngineOptions(), {}))


def test_coerce_warns_and_builds_equal_object():
    with pytest.warns(DeprecationWarning, match="options"):
        (opts,) = _coerce_options(
            "f", {"fuse_gather": False, "block_b": 64},
            ("options", EngineOptions, None, {}))
    assert opts == EngineOptions(fuse_gather=False, block_b=64)


def test_coerce_routes_keys_across_specs():
    with pytest.warns(DeprecationWarning):
        eng, align = _coerce_options(
            "f", {"block_b": 128, "protocol": "oprf"},
            ("options", EngineOptions, None, {}),
            ("align", AlignOptions, None, ALIGN_ALIASES))
    assert eng.block_b == 128 and align.protocol == "oprf"


# --------------------------------------------- bitwise parity: training


@pytest.mark.parametrize("model", ["lr", "mlp"])
@pytest.mark.parametrize("fuse", [True, False])
def test_train_splitnn_kwargs_vs_options_bitwise(part, model, fuse):
    cfg = _cfg(model)
    with pytest.warns(DeprecationWarning):
        legacy = train_splitnn(part, cfg, fuse_gather=fuse)
    new = train_splitnn(part, cfg, options=EngineOptions(fuse_gather=fuse))
    assert_reports_bitwise_equal(legacy, new)


def test_train_splitnn_rejects_unknown_engine_and_bottom(part):
    """One engine: "scan" is the only ``train_engine`` accepted, and a
    bottom other than "ref"|"pallas" is refused before any program is
    built."""
    assert EngineOptions(train_engine="scan") == EngineOptions()
    with pytest.raises(ValueError, match="train_engine"):
        EngineOptions(train_engine="loop")
    with pytest.raises(ValueError, match="bottom_impl"):
        train_splitnn(part, _cfg("lr"),
                      options=EngineOptions(bottom_impl="nope"))


# --------------------------------------------- bitwise parity: pipeline


def test_run_pipeline_kwargs_vs_options_bitwise(parts):
    tr, te = parts
    cfg = _cfg("lr")
    with pytest.warns(DeprecationWarning):
        legacy = run_pipeline(tr, te, cfg, variant="treecss",
                              clusters_per_client=6, seed=0,
                              protocol="rsa", block_b=256)
    new = run_pipeline(tr, te, cfg, variant="treecss",
                       clusters_per_client=6, seed=0,
                       options=EngineOptions(block_b=256),
                       align=AlignOptions(protocol="rsa"))
    assert legacy.metric == new.metric
    assert legacy.n_train == new.n_train
    assert np.array_equal(legacy.mpsi.intersection,
                          new.mpsi.intersection)
    assert legacy.mpsi.total_bytes == new.mpsi.total_bytes
    assert_reports_bitwise_equal(legacy.train, new.train)


# --------------------------------------------- shared MPSI signature


def test_mpsi_options_signature_parity():
    rng = np.random.default_rng(7)
    sets = [rng.choice(5000, size=800, replace=False).astype(np.int64)
            for _ in range(3)]
    with pytest.warns(DeprecationWarning):
        legacy = tree_mpsi(sets, protocol="oprf")
    new = tree_mpsi(sets, options=AlignOptions(protocol="oprf"))
    assert np.array_equal(legacy.intersection, new.intersection)
    assert legacy.total_bytes == new.total_bytes
    assert legacy.total_messages == new.total_messages


def test_run_psi_front_door():
    from repro.psi import run_psi
    rng = np.random.default_rng(8)
    sets = [rng.choice(3000, size=500, replace=False).astype(np.int64)
            for _ in range(3)]
    stats = run_psi(sets, topology="tree",
                    options=AlignOptions(protocol="rsa"))
    expect = tree_mpsi(sets, options=AlignOptions(protocol="rsa"))
    assert np.array_equal(stats.intersection, expect.intersection)
    with pytest.raises(ValueError, match="topology"):
        run_psi(sets, topology="ring")
