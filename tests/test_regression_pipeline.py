"""The regression path (the paper's YP with LinearReg) against plain
references on the CPU at small sizes: the scan engine's split linear
regression training, the coreset's quantile-binned (CT, label) groups,
the test MSE, a tiny YP-shaped ``run_pipeline`` end to end, and the
spans of the selection step and of the training set-up."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import AlignOptions, EngineOptions
from repro.core.coreset import (ClientClustering, cluster_coreset,
                                select_coreset)
from repro.core.splitnn import SplitNNConfig, evaluate, train_splitnn
from repro.data.synthetic import DATASETS, make_dataset
from repro.data.vertical import VerticalPartition, partition_features
from repro.obs import Tracer, use_tracer


def reg_partition(n=300, widths=(4, 3, 5), seed=0):
    """Rows of uneven party widths and a noisy linear target near 50."""
    rng = np.random.default_rng(seed)
    x = rng.normal(0, 1.5, (n, sum(widths))).astype(np.float32)
    y = (50 + x @ rng.normal(0, 2, sum(widths))
         + rng.normal(0, 3, n)).astype(np.float32)
    return partition_features(x, y, len(widths),
                              proportions=[w / sum(widths) for w in widths])


# ------------------------------------------------- the plain reference

def ref_init(seed, widths):
    """The program's linreg init: per party N(0, 1/d) x 0.1 from
    PRNGKey(seed) split M+2 ways, one bias at 0."""
    ks = jax.random.split(jax.random.PRNGKey(seed), len(widths) + 2)
    return {"w": [jax.random.normal(ks[i], (d, 1), jnp.float32)
                  * (d ** -0.5) * 0.1 for i, d in enumerate(widths)],
            "b": jnp.zeros((1,), jnp.float32)}


def ref_forward(p, xs):
    return sum(x @ w for x, w in zip(xs, p["w"]))[:, 0] + p["b"][0]


def ref_train(xs, y, w, *, seed, epochs, batch, lr):
    """Mini-batch Adam on the Eq. (2) weighted squared error, in float32
    at the highest matrix-product precision: each epoch a permutation
    from ``default_rng(seed)``, the last batch short, the epoch's loss
    the mean of its steps' losses."""
    def loss_fn(p, xb, yb, wb):
        return jnp.sum(wb * jnp.square(ref_forward(p, xb) - yb)) / \
            jnp.sum(wb)

    grad = jax.jit(jax.value_and_grad(loss_fn))
    p = ref_init(seed, [x.shape[1] for x in xs])
    m = jax.tree_util.tree_map(jnp.zeros_like, p)
    v = jax.tree_util.tree_map(jnp.zeros_like, p)
    rng = np.random.default_rng(seed)
    n, t, losses = y.shape[0], 0, []
    with jax.default_matmul_precision("highest"):
        for _ in range(epochs):
            order = rng.permutation(n)
            step_losses = []
            for s in range(0, n, batch):
                ib = order[s:s + batch]
                loss, g = grad(p, [x[ib] for x in xs], y[ib], w[ib])
                t += 1
                m = jax.tree_util.tree_map(
                    lambda a, b: 0.9 * a + 0.1 * b, m, g)
                v = jax.tree_util.tree_map(
                    lambda a, b: 0.999 * a + 0.001 * b * b, v, g)
                p = jax.tree_util.tree_map(
                    lambda a, m1, v1: a - lr * (m1 / (1 - 0.9 ** t)) / (
                        jnp.sqrt(v1 / (1 - 0.999 ** t)) + 1e-8), p, m, v)
                step_losses.append(float(loss))
            losses.append(float(np.mean(step_losses)))
    return p, losses


# ------------------------------------------------------------ training

@pytest.mark.parametrize("bottom_impl", ["ref", "pallas"])
def test_scan_linreg_training_matches_the_plain_reference(bottom_impl):
    part = reg_partition()
    w = np.random.default_rng(1).uniform(0.1, 2.0, part.n_samples
                                         ).astype(np.float32)
    cfg = SplitNNConfig(model="linreg", n_classes=0, lr=0.05,
                        batch_size=64, max_epochs=6, convergence_eps=0.0,
                        seed=7)
    with jax.default_matmul_precision("highest"):
        rep = train_splitnn(part, cfg, sample_weights=w,
                            options=EngineOptions(bottom_impl=bottom_impl))
    p, losses = ref_train(part.client_features, part.labels, w, seed=7,
                          epochs=6, batch=64, lr=0.05)
    # 300 rows = 4 full batches and one of 44: both train every row.
    # The losses (~2500, falling) are sums reassociated differently
    # (three party partials summed in another order, the step mean on
    # device): a few float32 ulps of each term, 6e-7 relative seen, so
    # 1e-5; one bfloat16 pass would be ~1e-3 off.
    np.testing.assert_allclose(rep.losses, losses, rtol=1e-5)
    # after 30 Adam steps of size ~lr the weights sit ~1.1 from their
    # start; per-step ulp drift compounds through Adam's division by
    # sqrt(v): 1e-5 absolute seen, so 1e-4
    for got, want in zip(rep.params["bottoms"], p["w"]):
        np.testing.assert_allclose(got["w"], want, atol=1e-4)
    np.testing.assert_allclose(rep.params["top"]["b"], p["b"], atol=1e-4)


def test_evaluate_mse_matches_the_reference_forward():
    train = reg_partition(seed=2)
    test = reg_partition(n=1100, seed=3)
    cfg = SplitNNConfig(model="linreg", n_classes=0, lr=0.05,
                        batch_size=64, max_epochs=3, seed=0)
    params = train_splitnn(train, cfg).params
    p = {"w": [b["w"] for b in params["bottoms"]], "b": params["top"]["b"]}
    with jax.default_matmul_precision("highest"):
        pred = np.asarray(ref_forward(p, [jnp.asarray(x) for x in
                                          test.client_features]))
        want = float(np.mean((pred.astype(np.float64) - test.labels) ** 2))
        for impl in ("ref", "pallas"):
            # 1100 rows: two full 512-row scoring blocks and a padded one
            got = evaluate(params, cfg, test, bottom_impl=impl)
            # the program's mean is a float32 sum of 1100 squared errors
            # (~1e3 each): ~1e-6 relative
            assert got == pytest.approx(want, rel=1e-5)


# ------------------------------------------------------------- coreset

def loop_select(local, labels, bins=16):
    """Steps 4-5 one row at a time: the label's quantile bin (how many of
    the 15 inner quantiles lie below it), the (CT, bin) group, the row of
    least summed distance (the first on a tie), weighted by the sum of
    its parties' rank weights."""
    cuts = np.quantile(labels, np.arange(1, bins) / bins)
    best = {}
    for i in range(labels.shape[0]):
        b = sum(1 for c in cuts if c < labels[i])
        key = tuple(int(c.assign[i]) for c in local) + (b,)
        ed = np.float32(0)
        for c in local:
            ed = ed + np.sqrt(np.maximum(c.sq_dist[i], np.float32(0)))
        if key not in best or ed < best[key][1]:
            best[key] = (i, ed)
    idx = np.sort([i for i, _ in best.values()])
    w = np.zeros(idx.shape[0], np.float32)
    for c in local:
        w = w + c.weight[idx]
    return idx, w, len(best)


@pytest.mark.parametrize("ties", [False, True])
def test_select_coreset_bins_float_labels_like_a_row_loop(ties):
    rng = np.random.default_rng(5)
    n, k = 2000, 4
    labels = rng.normal(50, 15, n).astype(np.float32)
    if ties:       # whole years: many labels equal a quantile cut
        labels = np.round(labels).astype(np.float32)
    local = [ClientClustering(rng.integers(0, k, n).astype(np.int32),
                              rng.uniform(0, 9, n).astype(np.float32),
                              rng.uniform(0, 1, n).astype(np.float32),
                              np.zeros((k, 2), np.float32))
             for _ in range(3)]
    idx, w, groups = select_coreset(local, labels)
    ridx, rw, rgroups = loop_select(local, labels)
    np.testing.assert_array_equal(idx, ridx)
    np.testing.assert_array_equal(w, rw)
    assert groups == rgroups


def test_coreset_bin_and_group_spans_nest_in_select():
    part = reg_partition(n=240)
    tracer = Tracer()
    with use_tracer(tracer):
        res = cluster_coreset(part, 3, seed=0)
    spans = tracer.finished()
    by = {s.name: s for s in spans}
    for name in ("coreset.bin", "coreset.group"):
        assert by[name].parent == by["coreset.select"].sid
    assert by["coreset.bin"].attrs == {"rows": 240, "bins": 16}
    assert by["coreset.group"].attrs == {
        "rows": 240, "groups": res.n_groups,
        "kept": int(res.indices.shape[0])}
    # a class label is not binned
    cls = VerticalPartition(part.client_features,
                            (part.labels > 50).astype(np.int64),
                            part.feature_slices)
    tracer = Tracer()
    with use_tracer(tracer):
        cluster_coreset(cls, 3, seed=0)
    names = [s.name for s in tracer.finished()]
    assert "coreset.group" in names and "coreset.bin" not in names


# ---------------------------------------------------- the training path

@pytest.mark.parametrize("impl,fuse,path", [
    ("pallas", True, "gather_fused"), ("pallas", False, "dense"),
    ("ref", True, "ref")])
def test_train_setup_names_the_bottom_path(impl, fuse, path):
    part = reg_partition(n=100)
    cfg = SplitNNConfig(model="linreg", n_classes=0, max_epochs=1)
    tracer = Tracer()
    with use_tracer(tracer):
        train_splitnn(part, cfg, options=EngineOptions(bottom_impl=impl,
                                                       fuse_gather=fuse))
    (setup,) = [s for s in tracer.finished() if s.name == "train.setup"]
    assert setup.attrs["bottom_path"] == path


def test_gather_path_falls_back_past_the_vmem_budget(monkeypatch):
    from repro.kernels.padding import GATHER_VMEM_BUDGET
    from repro.kernels.splitnn_bottom import ops

    monkeypatch.setattr(ops, "interpret", lambda: False)   # as on a TPU
    rows = GATHER_VMEM_BUDGET // (4 * 128)
    assert ops.gather_path("pallas", rows, 30) == "gather_fused"
    assert ops.gather_path("pallas", rows + 1, 30) == "gather_fallback"
    assert ops.gather_path("pallas", 4 * rows, 30, "int8") == \
        "int8_gather_fused"
    assert ops.gather_path("pallas", 4 * rows + 1, 30, "int8") == \
        "int8_gather_fallback"
    assert ops.gather_path("ref", 10 * rows, 30) == "ref"


# ------------------------------------------------------ the whole job

def test_tiny_yp_run_pipeline_end_to_end():
    from repro.core import run_pipeline

    spec = DATASETS["YP"]
    x, y = make_dataset(spec, seed=0, n_override=3000)
    assert x.shape == (3000, 90) and y.dtype == np.float32
    order = np.random.default_rng(1).permutation(3000)
    tr = partition_features(x[order[:2100]], y[order[:2100]], 3)
    te = partition_features(x[order[2100:]], y[order[2100:]], 3)
    assert [f.shape[1] for f in tr.client_features] == [30, 30, 30]
    cfg = SplitNNConfig(model="linreg", n_classes=0, max_epochs=4,
                        convergence_eps=0.0, seed=0)
    tracer = Tracer()
    rep = run_pipeline(
        tr, te, cfg, variant="treecss", clusters_per_client=12,
        kmeans_impl="pallas", seed=0,
        align=AlignOptions(protocol="oprf", psi_backend="device",
                           impl="pallas"),
        options=EngineOptions(bottom_impl="pallas", trace=tracer))
    # 70% of 2100 ids are common to the three parties
    assert rep.mpsi.intersection.shape[0] == 1470
    assert rep.n_train == rep.coreset.indices.shape[0] <= 1470
    assert rep.train.epochs == 4 and len(rep.train.losses) == 4
    assert rep.train.losses[-1] < rep.train.losses[0]
    assert rep.metric == pytest.approx(
        evaluate(rep.train.params, cfg, te, bottom_impl="pallas"))
    names = {s.name for s in tracer.finished()}
    assert {"coreset.bin", "coreset.group", "train.setup"} <= names
