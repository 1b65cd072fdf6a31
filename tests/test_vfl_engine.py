"""Scan-based VFL train engine (repro.train.vfl, DESIGN.md §7):
parity with a plain per-step reference, the one-host-sync-per-epoch
contract, remainder-batch training, and weight semantics."""
import numpy as np
import pytest

from _vfl_reference import train_reference
from conftest import make_cls_partition
from repro.config import EngineOptions
from repro.core.splitnn import (SplitNNConfig, activation_bytes_per_sample,
                                evaluate, train_splitnn)


def _flat(params):
    import jax
    return np.concatenate([np.asarray(x).ravel()
                           for x in jax.tree_util.tree_leaves(params)])


# ---------------------------------------------- parity with the reference

MODELS = {"lr2": ("lr", 2), "lr3": ("lr", 3), "mlp4": ("mlp", 4),
          "linreg": ("linreg", 0)}
BOTTOMS = {"ref": ("ref", True), "pallas_fused": ("pallas", True),
           "pallas_dense": ("pallas", False)}
# HI's 11/11/10, YP's 30/30/30, one party, and ragged widths whose
# d_max padding pack_slab has to get right
WIDTHS = {"hi": (11, 11, 10), "yp": (30, 30, 30), "one": (1,),
          "ragged": (3, 5, 2, 9, 4)}


def _parties(widths, n_classes, n, seed):
    """Rows of one gaussian-mixture table split into parties of the given
    widths."""
    from repro.data.synthetic import DatasetSpec, make_dataset
    from repro.data.vertical import VerticalPartition

    x, y = make_dataset(DatasetSpec("t", n, sum(widths), n_classes),
                        seed=seed)
    cols = np.cumsum((0,) + widths)
    slices = [slice(int(a), int(b)) for a, b in zip(cols[:-1], cols[1:])]
    return VerticalPartition([x[:, sl].copy() for sl in slices], y, slices)


def _coreset_weights(n, seed):
    """Non-uniform weights with about a fifth of them zero, as a coreset
    gives (zero for rows no centroid kept)."""
    rng = np.random.default_rng(seed)
    w = rng.uniform(0.2, 4.0, n).astype(np.float32)
    w[rng.random(n) < 0.2] = 0.0
    return w


def _check_against_reference(widths, model, n_classes, bottom_impl, fuse,
                             n, weighted):
    tr = _parties(widths, n_classes, n, seed=1)
    te = _parties(widths, n_classes, 150, seed=2)
    cfg = SplitNNConfig(model=model, n_classes=n_classes, lr=0.02,
                        batch_size=64, max_epochs=5)
    w = _coreset_weights(n, seed=3) if weighted else None
    rep = train_splitnn(tr, cfg, sample_weights=w,
                        options=EngineOptions(bottom_impl=bottom_impl,
                                              fuse_gather=fuse))
    ref = train_reference(tr, cfg, sample_weights=w)
    assert np.allclose(rep.losses, ref.losses, rtol=1e-4, atol=1e-6)
    assert (rep.epochs, rep.steps, rep.comm_bytes) == (
        ref.epochs, ref.steps, ref.comm_bytes)
    assert abs(evaluate(rep.params, cfg, te)
               - evaluate(ref.params, cfg, te)) <= 0.02


@pytest.mark.parametrize("weighted", [False, True],
                         ids=["unweighted", "weighted"])
@pytest.mark.parametrize("widths", list(WIDTHS.values()), ids=list(WIDTHS))
@pytest.mark.parametrize("bottom", list(BOTTOMS), ids=list(BOTTOMS))
@pytest.mark.parametrize("model", list(MODELS), ids=list(MODELS))
def test_scan_matches_reference(model, bottom, widths, weighted):
    """The one engine against the plain per-step f32 reference
    (``tests/_vfl_reference.py``) on the same schedule, over the axes
    the engine's packing, padding and fusion depend on: the model head,
    the bottom path, the party widths and the sample weights.  230 rows
    at batch 64 leave a 38-row remainder the engine pads and masks;
    zero padding is exact, so only GEMM reassociation separates them."""
    _check_against_reference(widths, *MODELS[model], *BOTTOMS[bottom],
                             n=230, weighted=weighted)


@pytest.mark.parametrize("model", ["lr2", "linreg"])
@pytest.mark.parametrize("bottom", ["ref", "pallas_fused"])
@pytest.mark.parametrize("n", [40, 64, 193])
def test_scan_matches_reference_rows(n, bottom, model):
    """Row counts at the batch's edges: fewer rows than a batch, exactly
    one batch, and a remainder of one row."""
    _check_against_reference((11, 11, 10), *MODELS[model], *BOTTOMS[bottom],
                             n=n, weighted=False)


@pytest.mark.parametrize("bottom_impl", ["ref", "pallas"])
@pytest.mark.parametrize("n", [192, 230])          # divisible + remainder
def test_fuse_gather_is_bitwise(n, bottom_impl):
    """Scalar-prefetching the schedule indices into the bottom pass
    (DESIGN.md §8) is a pure data-movement change: losses and trained
    params must be BITWISE-equal to the explicit slab[:, idx, :] gather,
    on full and remainder batches, for both bottom impls."""
    tr = make_cls_partition(n=n, d=11, seed=8)
    cfg = SplitNNConfig(model="lr", n_classes=2, lr=0.05, batch_size=64,
                        max_epochs=5)
    fused = train_splitnn(tr, cfg, bottom_impl=bottom_impl)
    plain = train_splitnn(tr, cfg, bottom_impl=bottom_impl,
                          fuse_gather=False)
    assert fused.engine_stats.fused_gather
    assert not plain.engine_stats.fused_gather
    assert fused.losses == plain.losses
    assert np.array_equal(_flat(fused.params), _flat(plain.params))


def test_fuse_gather_mlp_bitwise():
    """Same contract through the MLP top model (bottom biases in the
    slab carry, ReLU mask through the shared custom_vjp backward)."""
    tr = make_cls_partition(n=200, d=12, classes=4, seed=9)
    cfg = SplitNNConfig(model="mlp", n_classes=4, lr=0.01, batch_size=64,
                        max_epochs=4)
    fused = train_splitnn(tr, cfg, bottom_impl="pallas")
    plain = train_splitnn(tr, cfg, bottom_impl="pallas", fuse_gather=False)
    assert fused.losses == plain.losses
    assert np.array_equal(_flat(fused.params), _flat(plain.params))


# ------------------------------------------------------- dispatch contract

def test_scan_one_dispatch_and_sync_per_epoch():
    """The engine's measured counts: it dispatches and syncs exactly once
    per epoch, however many steps the epoch takes."""
    tr = make_cls_partition(n=300, d=9, seed=2)
    cfg = SplitNNConfig(model="lr", n_classes=2, lr=0.05, batch_size=64,
                        max_epochs=7)
    scan = train_splitnn(tr, cfg)
    st = scan.engine_stats
    assert st.dispatches == scan.epochs
    assert st.host_syncs == scan.epochs
    assert scan.steps > scan.epochs          # the contrast being claimed


# -------------------------------------------------------- remainder batch

def test_remainder_rows_trained():
    """n=70, bs=64: the seed loop (range(0, n-bs+1, bs)) trained 64 of 70
    rows per epoch.  The engine must train all n rows and count the
    actual rows in comm_bytes."""
    tr = make_cls_partition(n=70, d=8, seed=3)
    cfg = SplitNNConfig(model="lr", n_classes=2, lr=0.05, batch_size=64,
                        max_epochs=4)
    rep = train_splitnn(tr, cfg)
    per = activation_bytes_per_sample(cfg, tr.n_clients)
    assert rep.steps == rep.epochs * 2       # 64-row + 6-row batches
    assert rep.comm_bytes == rep.epochs * 70 * per


def test_remainder_mask_excludes_pad_rows():
    """Poisoning row 0 (the scan schedule's pad target) with huge
    features must not leak into training through the padded slots: with
    row 0's weight at 0 the result must match training without row 0 at
    all (identical schedule up to the same-order permutation)."""
    tr = make_cls_partition(n=65, d=8, seed=4)
    tr.client_features[0][0] *= 1e6          # poison the pad target row
    w = np.ones(65, np.float32)
    w[0] = 0.0
    cfg = SplitNNConfig(model="lr", n_classes=2, lr=0.05, batch_size=64,
                        max_epochs=3)
    rep = train_splitnn(tr, cfg, sample_weights=w)
    assert np.all(np.isfinite(rep.losses))
    assert np.all(np.isfinite(_flat(rep.params)))


# --------------------------------------------------------------- weights

def test_sample_weights_none_equals_ones():
    tr = make_cls_partition(n=300, d=8, seed=3)
    cfg = SplitNNConfig(model="lr", n_classes=2, lr=0.05, batch_size=50,
                        max_epochs=6)
    r_none = train_splitnn(tr, cfg, sample_weights=None)
    r_ones = train_splitnn(tr, cfg,
                           sample_weights=np.ones(tr.n_samples, np.float32))
    assert np.array_equal(_flat(r_none.params), _flat(r_ones.params))
    assert r_none.losses == r_ones.losses


def test_scan_convergence_criterion_stops_early():
    tr = make_cls_partition(n=200, d=6, seed=5, margin=6.0)
    cfg = SplitNNConfig(model="lr", n_classes=2, lr=0.1, batch_size=50,
                        max_epochs=200, convergence_eps=1e-3)
    rep = train_splitnn(tr, cfg)
    assert rep.epochs < 200
    assert rep.engine_stats.host_syncs == rep.epochs
