"""Cluster-Coreset: weighting formula, CT grouping, selection invariants."""
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # container image has no hypothesis
    from _propcheck import given, settings, strategies as st

from conftest import make_cls_partition
from repro.core.coreset import (ClientClustering, _group_ids,
                                cluster_coreset, local_cluster_weights,
                                select_coreset)
from repro.obs import Tracer, use_tracer
from repro.train.vfl import clear_program_caches

TESTS = Path(__file__).resolve().parent


def test_local_weight_formula():
    """w_i = pos(ed_i, DeSort)/|S_c|: closest sample weight == 1,
    farthest == 1/|S_c|."""
    pts = np.array([[0.0], [0.1], [0.5], [3.0]], np.float32)
    cc = local_cluster_weights(pts, 1, seed=0)
    assert np.unique(cc.assign).size == 1
    order = np.argsort(cc.sq_dist)      # ascending distance
    n = len(pts)
    expected = {order[-1]: 1.0 / n, order[0]: 1.0}
    assert cc.weight[order[0]] == pytest.approx(1.0)
    assert cc.weight[order[-1]] == pytest.approx(1.0 / n)
    # strictly monotone: closer → larger weight
    w_sorted = cc.weight[order]
    assert np.all(np.diff(w_sorted) < 0)


def test_ct_grouping_and_min_distance_selection():
    """Two clients, hand-built clusterings: one sample per (CT, label)
    group, the one with minimal Σ_m ed."""
    assign1 = np.array([0, 0, 1, 1, 0], np.int32)
    assign2 = np.array([0, 0, 1, 1, 1], np.int32)
    ed1 = np.array([0.5, 0.1, 0.3, 0.2, 0.4], np.float32) ** 2
    ed2 = np.array([0.2, 0.3, 0.1, 0.4, 0.1], np.float32) ** 2
    w = np.ones(5, np.float32) * 0.5
    labels = np.array([0, 0, 1, 1, 0], np.int64)
    local = [
        ClientClustering(assign1, ed1, w, np.zeros((2, 1), np.float32)),
        ClientClustering(assign2, ed2, w, np.zeros((2, 1), np.float32)),
    ]
    idx, weights, n_groups = select_coreset(local, labels)
    # groups: CT(0,0)+y0 -> {0,1}; CT(1,1)+y1 -> {2,3}; CT(0,1)+y0 -> {4}
    assert n_groups == 3
    assert set(idx) == {1, 2, 4}     # min Σed in each group
    assert weights == pytest.approx([1.0, 1.0, 1.0])  # Σ_m w_i^m


def test_coreset_end_to_end_invariants():
    part = make_cls_partition(n=400, d=12, clients=3, seed=1)
    res = cluster_coreset(part, 6, seed=0)
    assert len(np.unique(res.indices)) == len(res.indices)
    assert res.indices.min() >= 0 and res.indices.max() < part.n_samples
    assert len(res.indices) < part.n_samples       # actually reduces
    assert np.all(res.weights > 0)
    assert res.comm_bytes > 0
    # every (CT, label) group is represented exactly once
    assert len(res.indices) == res.n_groups


def test_coreset_covers_all_labels():
    part = make_cls_partition(n=300, d=9, classes=4, clients=3, seed=2)
    res = cluster_coreset(part, 4, seed=0)
    assert set(part.labels[res.indices]) == set(part.labels)


def test_more_clusters_bigger_coreset():
    part = make_cls_partition(n=500, d=12, clients=3, seed=3)
    small = cluster_coreset(part, 2, seed=0)
    big = cluster_coreset(part, 12, seed=0)
    assert len(big.indices) >= len(small.indices)


def test_he_exchange_fidelity():
    part = make_cls_partition(n=120, d=6, clients=2, seed=4)
    res = cluster_coreset(part, 3, seed=0, use_he=True)
    assert res.he_seconds > 0
    assert res.comm_bytes > 120 * 2 * 24   # ciphertexts ≫ plaintext tuples


# --------------------------------------------------- ragged client batching

def test_ragged_clients_batch_and_match_sequential():
    """Unequal feature widths (11 features / 3 clients -> 4,4,3) now run
    the pad-and-mask batched path; selection, assignments and centroids
    must equal the sequential per-client loop bitwise (zero-padded
    columns are exact — see kmeans_fit), the final distances up to the
    reordered-sum bound."""
    from repro.core.coreset import clients_batchable

    part = make_cls_partition(n=320, d=11, clients=3, seed=6)
    shapes = {f.shape for f in part.client_features}
    assert len(shapes) > 1                      # genuinely ragged
    assert clients_batchable(part.client_features, clusters=5)
    batched = cluster_coreset(part, 5, seed=3)
    seq = cluster_coreset(part, 5, seed=3, batch_clients="never")
    assert batched.batched and not seq.batched
    assert np.array_equal(batched.indices, seq.indices)
    assert np.array_equal(batched.weights, seq.weights)
    d_max = max(f.shape[1] for f in part.client_features)
    eps = np.finfo(np.float32).eps
    for f, b, s in zip(part.client_features, batched.local, seq.local):
        assert np.array_equal(b.assign, s.assign)
        assert np.array_equal(b.centroids, s.centroids)
        # The final distance ‖x‖² − 2x·c + ‖c‖² is summed over the
        # zero-padded width in the batch and over the true width in the
        # loop.  The padding adds exact zeros, but XLA orders each
        # width's sums its own way, so a narrower client's distances
        # agree only up to the reordered-sum bound: d·eps times the sum
        # of term magnitudes, at most 4·d·eps·(‖x‖² + ‖c‖²).  That is
        # an absolute bound on the operand scale, since the small result
        # cancels (measured on XLA-CPU: 7.6e-6, 1/12 of the bound).
        x2 = np.sum(np.square(f.astype(np.float64)), axis=1)
        c2 = np.sum(np.square(s.centroids.astype(np.float64)), axis=1)
        bound = 4 * d_max * eps * (x2 + c2[s.assign])
        assert np.all(np.abs(b.sq_dist.astype(np.float64) - s.sq_dist)
                      <= bound)
        assert np.array_equal(b.weight, s.weight)
        assert b.centroids.shape == s.centroids.shape


def test_ragged_rows_batch_via_mask():
    """Clients with unequal SAMPLE counts (direct feature-list API) pad
    rows and mask them out of init sampling, counts, and the reseed
    argmax — per-client results match the sequential fits."""
    from repro.core.coreset import _batched_local_clusterings

    rng = np.random.default_rng(9)
    feats = [rng.normal(size=(n, d)).astype(np.float32)
             for n, d in [(120, 3), (87, 5), (140, 2)]]
    local, _, shards = _batched_local_clusterings(
        feats, 4, seed=2, iters=10, impl="ref")
    assert shards == 1
    for m, f in enumerate(feats):
        seq = local_cluster_weights(f, 4, seed=2 + 17 * m, iters=10)
        assert np.array_equal(local[m].assign, seq.assign)
        assert local[m].sq_dist.shape == seq.sq_dist.shape
        # row-padding changes XLA's gemm shape, so sq_dist may differ by
        # reassociation ulps; the clustering itself must be identical
        np.testing.assert_allclose(local[m].sq_dist, seq.sq_dist,
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(local[m].centroids, seq.centroids,
                                   rtol=1e-5, atol=1e-6)


def test_ragged_small_client_falls_back_to_sequential():
    """A client with fewer samples than the cluster count needs its own
    smaller k, which the static-k batched path cannot express."""
    from repro.core.coreset import clients_batchable

    feats = [np.zeros((40, 3), np.float32), np.zeros((4, 2), np.float32)]
    assert not clients_batchable(feats, clusters=8)
    assert clients_batchable(feats, clusters=4)


@settings(max_examples=10, deadline=None)
@given(st.integers(60, 200), st.integers(2, 8), st.integers(0, 50))
def test_property_selection_is_deterministic_partition(n, k, seed):
    part = make_cls_partition(n=n, d=8, clients=2, seed=seed)
    r1 = cluster_coreset(part, k, seed=seed)
    r2 = cluster_coreset(part, k, seed=seed)
    assert np.array_equal(r1.indices, r2.indices)
    assert np.allclose(r1.weights, r2.weights)
    # weights bounded by number of clients (each local weight ≤ 1)
    assert np.all(r1.weights <= part.n_clients + 1e-6)


def _traced_coreset(part, k, **kw):
    """``cluster_coreset`` under a fresh tracer: (result, the
    ``coreset.compile`` span's ``cache_hit``)."""
    tracer = Tracer()
    with use_tracer(tracer):
        res = cluster_coreset(part, k, **kw)
    (sp,) = tracer.by_name("coreset.compile")
    return res, sp.attrs["cache_hit"]


def test_second_call_reuses_the_compiled_fit():
    """The batched k-means program is compiled once per key: a second
    call on the same partition triggers no backend compile, its
    ``coreset.compile`` span reports the hit, and its selection is
    bitwise that of a call made after the caches were dropped."""
    from jax import monitoring

    part = make_cls_partition(n=300, d=12, clients=3, seed=5)
    clear_program_caches()
    compiles = []

    def on_duration(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(secs)

    monitoring.register_event_duration_secs_listener(on_duration)
    try:
        first, hit1 = _traced_coreset(part, 5, seed=2)
        n_first = len(compiles)
        second, hit2 = _traced_coreset(part, 5, seed=2)
        n_second = len(compiles) - n_first
    finally:
        monitoring.unregister_event_duration_listener(on_duration)
    assert n_first > 0 and n_second == 0
    assert (hit1, hit2) == (0, 1)
    clear_program_caches()
    fresh, hit3 = _traced_coreset(part, 5, seed=2)
    assert hit3 == 0
    for res in (first, second):
        assert res.batched
        assert np.array_equal(res.indices, fresh.indices)
        assert res.weights.tobytes() == fresh.weights.tobytes()


_MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import json
import numpy as np
from conftest import make_cls_partition
from repro.core.coreset import _fit_program, clear_fit_cache, cluster_coreset
from repro.launch.mesh import make_data_mesh

# two clients on two devices: the sharded batch needs no row padding, so
# both runs lower the same argument shapes
part = make_cls_partition(n=240, d=8, clients=2, seed=3)
clear_fit_cache()
base = cluster_coreset(part, 4, seed=1)
shrd = cluster_coreset(part, 4, seed=1, mesh=make_data_mesh())
same = (np.array_equal(base.indices, shrd.indices)
        and base.weights.tobytes() == shrd.weights.tobytes()
        and all(b.assign.tobytes() == s.assign.tobytes()
                and b.sq_dist.tobytes() == s.sq_dist.tobytes()
                and b.centroids.tobytes() == s.centroids.tobytes()
                for b, s in zip(base.local, shrd.local)))
print("RESULT" + json.dumps({"shards": [base.shards, shrd.shards],
                             "misses": _fit_program.cache_info().misses,
                             "same": same}))
"""


def _mesh_case():
    """A mesh and a no-mesh fit on the same shapes, in a subprocess with
    two virtual CPU devices (the device count is fixed at JAX start)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join(
        [str(TESTS.parent / "src"), str(TESTS)]))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", _MESH_SCRIPT], env=env,
                         cwd=TESTS.parent, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    line = [ln for ln in out.stdout.splitlines() if ln.startswith("RESULT")]
    r = json.loads(line[0][len("RESULT"):])
    assert r["shards"] == [1, 2]
    assert r["misses"] == 2         # no shared executable
    assert r["same"]                # and byte-identical results


# (first call, second call): make_cls_partition kwargs, k, kmeans_impl
_KEY_CASES = {
    "k": ((dict(d=12), 4, "ref"), (dict(d=12), 5, "ref")),
    "ragged": ((dict(d=12), 4, "ref"), (dict(d=11), 4, "ref")),
    "impl": ((dict(d=12), 4, "ref"), (dict(d=12), 4, "pallas")),
}


@pytest.mark.parametrize("case", [*_KEY_CASES, "mesh"])
def test_fit_cache_key_separates(case):
    """A different k, a ragged against a same-shape partition, the ref
    against the pallas impl, and a mesh against no mesh each compile
    their own program instead of reusing another key's."""
    if case == "mesh":
        _mesh_case()
        return
    from repro.core.coreset import _fit_program

    clear_program_caches()
    hits = []
    for part_kw, k, impl in _KEY_CASES[case]:
        part = make_cls_partition(n=200, clients=3, seed=7, **part_kw)
        res, hit = _traced_coreset(part, k, seed=0, kmeans_impl=impl)
        assert res.batched
        hits.append(hit)
    assert hits == [0, 0]
    assert _fit_program.cache_info().currsize == 2


@pytest.mark.parametrize("lo,hi,cols", [(0, 12, 4), (-3, 5, 2), (0, 7, 1),
                                        (-2**40, 2**40, 3)])
def test_group_ids_match_the_row_wise_unique(lo, hi, cols):
    """The mixed-radix code numbers groups as ``np.unique(axis=0)``
    does: negative digits, one column, and keys too wide for one int64
    (the last case, which takes the row-wise unique)."""
    keys = np.random.default_rng(cols).integers(lo, hi, (5000, cols))
    want = np.unique(keys, axis=0, return_inverse=True)[1].reshape(-1)
    assert np.array_equal(_group_ids(keys), want)
    assert _group_ids(keys[:0]).shape == (0,)
