"""shard_map parity: the sharded PSI / coreset paths must be
byte-identical to the single-device paths.

These tests exercise real multi-device shard_map, so they skip unless
the process sees >= 2 devices — CI provides them via
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (a dedicated
tier-1 job variant); run locally the same way.
"""
import jax
import numpy as np
import pytest

from conftest import make_cls_partition
from repro.core.coreset import cluster_coreset
from repro.core.mpsi import MPSI
from repro.core.treecss import run_pipeline
from repro.core.splitnn import SplitNNConfig
from repro.data.synthetic import make_id_universe
from repro.launch.mesh import make_data_mesh, make_train_mesh
from repro.psi import engine

needs_devices = pytest.mark.skipif(
    len(jax.devices()) < 2,
    reason="needs >=2 devices "
           "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")

needs_8_devices = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs >=8 devices for the 2x4 (data, model) mesh "
           "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")


@pytest.fixture(scope="module")
def mesh():
    return make_data_mesh()


@pytest.fixture(scope="module")
def mesh2d():
    return make_train_mesh(2, 4)


def _pair_batch(npairs, base_n, seed):
    rng = np.random.default_rng(seed)
    senders, receivers, seeds = [], [], []
    for i in range(npairs):
        a = np.unique(rng.integers(0, 2**55, base_n + 211 * i,
                                   dtype=np.int64))
        b = np.unique(rng.integers(0, 2**55, base_n, dtype=np.int64))
        b = np.unique(np.concatenate([a[:base_n // 3], b]))
        senders.append(a)
        receivers.append(b)
        seeds.append((int(rng.integers(0, 2**32)),
                      int(rng.integers(0, 2**32))))
    return senders, receivers, seeds


# ------------------------------------------------------------- PSI engine

@needs_devices
@pytest.mark.parametrize("sort", ["host", "device"])
@pytest.mark.parametrize("npairs", [5, 8])   # non-divisible + divisible
def test_oprf_round_sharded_byte_identical(mesh, sort, npairs):
    senders, receivers, seeds = _pair_batch(npairs, 1500, seed=npairs)
    base = engine.oprf_round(senders, receivers, seeds, impl="pallas",
                             sort=sort)
    shrd = engine.oprf_round(senders, receivers, seeds, impl="pallas",
                             sort=sort, mesh=mesh)
    assert shrd.shards == len(jax.devices())
    assert base.shards == 1
    assert len(shrd.intersections) == npairs
    for got, exp in zip(shrd.intersections, base.intersections):
        assert got.dtype == exp.dtype
        assert np.array_equal(got, exp)


@needs_devices
def test_match_round_sharded_byte_identical(mesh):
    senders, receivers, _ = _pair_batch(3, 900, seed=17)
    r_tags = [ids & engine.TAG_MASK for ids in receivers]
    s_tags = [ids & engine.TAG_MASK for ids in senders]
    base = engine.match_round(r_tags, receivers, s_tags, impl="pallas")
    shrd = engine.match_round(r_tags, receivers, s_tags, impl="pallas",
                              mesh=mesh)
    assert shrd.shards == len(jax.devices())
    for got, exp in zip(shrd.intersections, base.intersections):
        assert np.array_equal(got, exp)


@needs_devices
@pytest.mark.parametrize("protocol", ["rsa", "oprf"])
def test_tree_mpsi_sharded_matches_single_device(mesh, protocol):
    """Full Tree-MPSI on the device backend: intersection AND the
    modeled cost accounting must not change when rounds shard."""
    sets, core = make_id_universe(10, 600, 0.7, seed=23)
    base = MPSI["tree"](sets, protocol=protocol, backend="device",
                        use_he=False)
    shrd = MPSI["tree"](sets, protocol=protocol, backend="device",
                        use_he=False, mesh=mesh)
    assert np.array_equal(shrd.intersection, base.intersection)
    assert np.array_equal(shrd.intersection, core)
    assert shrd.total_bytes == base.total_bytes
    assert shrd.total_messages == base.total_messages
    assert shrd.rounds == base.rounds
    assert shrd.device_dispatches == base.device_dispatches


# ---------------------------------------------------------------- coreset

@needs_devices
def test_coreset_sharded_byte_identical(mesh):
    """Same-shape clients: the client batch shards over the mesh axis;
    indices and weights must be byte-identical."""
    part = make_cls_partition(n=420, d=12, clients=3, seed=4)
    base = cluster_coreset(part, 6, seed=1)
    shrd = cluster_coreset(part, 6, seed=1, mesh=mesh)
    assert base.batched and shrd.batched
    assert shrd.shards == len(jax.devices())
    assert np.array_equal(shrd.indices, base.indices)
    assert np.array_equal(shrd.weights, base.weights)   # f32 bit-equal
    for b, s in zip(base.local, shrd.local):
        assert np.array_equal(b.assign, s.assign)
        assert np.array_equal(b.sq_dist, s.sq_dist)
        assert np.array_equal(b.centroids, s.centroids)


@needs_devices
def test_coreset_sharded_ragged_byte_identical(mesh):
    """Ragged widths (11 features / 3 clients) through pad-and-mask AND
    the mesh shard at once."""
    part = make_cls_partition(n=330, d=11, clients=3, seed=8)
    assert len({f.shape for f in part.client_features}) > 1
    base = cluster_coreset(part, 5, seed=2)
    shrd = cluster_coreset(part, 5, seed=2, mesh=mesh)
    assert base.batched and shrd.batched
    assert np.array_equal(shrd.indices, base.indices)
    assert np.array_equal(shrd.weights, base.weights)


# ----------------------------------------------------------------- train

@needs_devices
@pytest.mark.parametrize("batch_size", [64, 60])   # divisible + padded
def test_train_sharded_matches_single_device(mesh, batch_size):
    """Scan-engine training with the per-step batch axis sharded over
    the mesh: per-device partial loss/grad sums are psum'd before the
    replicated Adam update, so results match single-device within
    reassociation ulps (DESIGN.md §7 — a documented float tolerance,
    unlike the byte-identical PSI/CSS paths)."""
    from repro.core.splitnn import SplitNNConfig as Cfg, evaluate, \
        train_splitnn

    tr = make_cls_partition(n=420, d=12, seed=6)
    te = make_cls_partition(n=200, d=12, seed=6)
    cfg = Cfg(model="lr", n_classes=2, lr=0.05, batch_size=batch_size,
              max_epochs=8)
    base = train_splitnn(tr, cfg)
    shrd = train_splitnn(tr, cfg, mesh=mesh)
    assert shrd.engine_stats.shards == len(jax.devices())
    assert base.engine_stats.shards == 1
    assert shrd.engine_stats.padded_batch % len(jax.devices()) == 0
    assert np.allclose(base.losses, shrd.losses, rtol=1e-4, atol=1e-6)
    assert shrd.steps == base.steps
    assert shrd.comm_bytes == base.comm_bytes   # modeled traffic invariant
    assert abs(evaluate(base.params, cfg, te)
               - evaluate(shrd.params, cfg, te)) <= 0.02
    # the sync contract survives sharding: still one per epoch
    assert shrd.engine_stats.host_syncs == shrd.epochs


@needs_devices
def test_train_sharded_mlp(mesh):
    from repro.core.splitnn import SplitNNConfig as Cfg, train_splitnn

    tr = make_cls_partition(n=256, d=12, classes=4, seed=7)
    cfg = Cfg(model="mlp", n_classes=4, lr=0.01, batch_size=64,
              max_epochs=5)
    base = train_splitnn(tr, cfg)
    shrd = train_splitnn(tr, cfg, mesh=mesh)
    assert shrd.engine_stats.shards == len(jax.devices())
    assert np.allclose(base.losses, shrd.losses, rtol=1e-4, atol=1e-6)


# ----------------------------------------------------------- 2-D train mesh


def _flat(params):
    return np.concatenate([np.asarray(x).ravel()
                           for x in jax.tree_util.tree_leaves(params)])


@needs_8_devices
@pytest.mark.parametrize("batch_size", [64, 60])   # divisible + padded
def test_train_2d_mesh_matches_single_device(mesh2d, batch_size):
    """Client-axis model parallelism (DESIGN.md §8): the M=3 bottom
    blocks shard over a 4-way model axis (one dummy client pads), the
    activation send lowers to an all-gather, and the result matches
    single-device AND the 1-D data-only mesh within reassociation ulps.
    The dispatch/sync contract survives the 2-D mapping: still exactly
    ONE of each per epoch."""
    from repro.core.splitnn import SplitNNConfig as Cfg, evaluate, \
        train_splitnn

    tr = make_cls_partition(n=420, d=12, seed=6)
    te = make_cls_partition(n=200, d=12, seed=6)
    cfg = Cfg(model="lr", n_classes=2, lr=0.05, batch_size=batch_size,
              max_epochs=8)
    base = train_splitnn(tr, cfg)
    m1d = train_splitnn(tr, cfg, mesh=make_data_mesh())
    m2d = train_splitnn(tr, cfg, mesh=mesh2d)
    st = m2d.engine_stats
    assert st.shards == 2 and st.model_shards == 4
    assert st.dispatches == m2d.epochs and st.host_syncs == m2d.epochs
    assert np.allclose(base.losses, m2d.losses, rtol=1e-4, atol=1e-6)
    assert np.allclose(m1d.losses, m2d.losses, rtol=1e-4, atol=1e-6)
    assert m2d.steps == base.steps
    assert m2d.comm_bytes == base.comm_bytes   # modeled traffic invariant
    assert abs(evaluate(base.params, cfg, te)
               - evaluate(m2d.params, cfg, te)) <= 0.02


@needs_8_devices
@pytest.mark.parametrize("bottom_impl", ["ref", "pallas"])
def test_train_2d_mesh_mlp(mesh2d, bottom_impl):
    """MLP on the 2-D mesh — the all-gather feeds the real (concat) top
    model — with both bottom impls, gather fusion on (the default)."""
    from repro.core.splitnn import SplitNNConfig as Cfg, train_splitnn

    tr = make_cls_partition(n=256, d=12, classes=4, seed=7)
    cfg = Cfg(model="mlp", n_classes=4, lr=0.01, batch_size=64,
              max_epochs=5)
    base = train_splitnn(tr, cfg)
    shrd = train_splitnn(tr, cfg, mesh=mesh2d, bottom_impl=bottom_impl)
    assert shrd.engine_stats.model_shards == 4
    assert shrd.engine_stats.fused_gather
    assert np.allclose(base.losses, shrd.losses, rtol=1e-4, atol=1e-6)


@needs_8_devices
def test_train_2d_gather_fused_bitwise(mesh2d):
    """On the SAME 2-D mesh, fusing the schedule gather into the bottom
    kernel changes no value: losses and trained params are bitwise-equal
    to the explicit slab[:, idx, :] path (full AND remainder batches)."""
    from repro.core.splitnn import SplitNNConfig as Cfg, train_splitnn

    for n in (256, 230):                       # divisible + remainder
        tr = make_cls_partition(n=n, d=11, seed=9)
        cfg = Cfg(model="lr", n_classes=2, lr=0.05, batch_size=64,
                  max_epochs=4)
        fused = train_splitnn(tr, cfg, mesh=mesh2d, bottom_impl="pallas")
        plain = train_splitnn(tr, cfg, mesh=mesh2d, bottom_impl="pallas",
                              fuse_gather=False)
        assert fused.engine_stats.fused_gather
        assert not plain.engine_stats.fused_gather
        assert fused.losses == plain.losses
        assert np.array_equal(_flat(fused.params), _flat(plain.params))


@needs_8_devices
def test_pipeline_2d_mesh_end_to_end(mesh2d):
    """One 2-D mesh knob through run_pipeline: PSI/CSS shard over data
    (byte-identical, model axis replicated), training shards over both
    axes (documented float tolerance)."""
    full = make_cls_partition(n=640, d=12, seed=3)
    rows = np.random.default_rng(2).permutation(640)
    tr, te = full.take(rows[:480]), full.take(rows[480:])
    cfg = SplitNNConfig(model="lr", n_classes=2, lr=0.05, batch_size=64,
                        max_epochs=15)
    base = run_pipeline(tr, te, cfg, variant="treecss",
                        clusters_per_client=4, seed=0)
    shrd = run_pipeline(tr, te, cfg, variant="treecss",
                        clusters_per_client=4, seed=0, mesh=mesh2d)
    assert np.array_equal(shrd.coreset.indices, base.coreset.indices)
    assert np.array_equal(shrd.coreset.weights, base.coreset.weights)
    assert shrd.train.engine_stats.shards == 2
    assert shrd.train.engine_stats.model_shards == 4
    assert shrd.train.epochs == base.train.epochs
    assert np.allclose(base.train.losses, shrd.train.losses,
                       rtol=1e-4, atol=1e-6)
    assert abs(shrd.metric - base.metric) <= 0.03


def test_resolve_train_mesh_shapes():
    """1-D meshes keep the PR-4 data-only semantics; 2-D meshes expose
    the model axis; 1-sized axes collapse; typos raise."""
    from repro.sharding import resolve_train_mesh

    assert resolve_train_mesh(None) == (None, None, 1, None, 1)
    m1 = make_data_mesh(1)
    assert resolve_train_mesh(m1) == (None, None, 1, None, 1)
    with pytest.raises(ValueError, match="shard_axis"):
        resolve_train_mesh(m1, "dat")
    if len(jax.devices()) >= 8:
        m2 = make_train_mesh(2, 4)
        mesh, da, nd, ma, nm = resolve_train_mesh(m2)
        assert (da, nd, ma, nm) == ("data", 2, "model", 4)
        m1d = make_data_mesh()
        mesh, da, nd, ma, nm = resolve_train_mesh(m1d)
        assert (da, nd, ma, nm) == ("data", len(jax.devices()), None, 1)


def test_mesh_axis_size_raises_on_unreadable_mesh():
    """An axis size it cannot read is an error, never a silent 1 that
    would run a sharded phase unsharded."""
    from repro.sharding import mesh_axis_size

    m1 = make_data_mesh(1)
    assert mesh_axis_size(m1, "data") == 1
    with pytest.raises(ValueError, match="model"):
        mesh_axis_size(m1, "model")
    with pytest.raises(TypeError, match="axis sizes"):
        mesh_axis_size(object(), "data")


# ------------------------------------------------------------- end to end

@needs_devices
def test_pipeline_mesh_trains_sharded(mesh):
    """One mesh knob now covers all three stages: with a trainable model
    the pipeline's train stage runs the sharded scan engine (align and
    coreset stay byte-identical; training matches within the documented
    float tolerance)."""
    full = make_cls_partition(n=640, d=12, seed=3)
    rows = np.random.default_rng(2).permutation(640)
    tr, te = full.take(rows[:480]), full.take(rows[480:])
    cfg = SplitNNConfig(model="lr", n_classes=2, lr=0.05, batch_size=64,
                        max_epochs=15)
    base = run_pipeline(tr, te, cfg, variant="treecss",
                        clusters_per_client=4, seed=0)
    shrd = run_pipeline(tr, te, cfg, variant="treecss",
                        clusters_per_client=4, seed=0, mesh=mesh)
    assert np.array_equal(shrd.coreset.indices, base.coreset.indices)
    assert np.array_equal(shrd.coreset.weights, base.coreset.weights)
    assert shrd.train.engine_stats.shards == len(jax.devices())
    assert shrd.train.epochs == base.train.epochs
    assert np.allclose(base.train.losses, shrd.train.losses,
                       rtol=1e-4, atol=1e-6)
    assert abs(shrd.metric - base.metric) <= 0.03


@needs_devices
def test_pipeline_mesh_knob_end_to_end(mesh):
    """run_pipeline(mesh=...) shards alignment (device PSI) and CSS;
    aligned set, coreset selection, and modeled costs match the
    single-device run byte-for-byte."""
    full = make_cls_partition(n=700, d=12, seed=0)
    rows = np.random.default_rng(1).permutation(700)
    tr, te = full.take(rows[:520]), full.take(rows[520:])
    cfg = SplitNNConfig(model="knn", n_classes=2)
    base = run_pipeline(tr, te, cfg, variant="treecss",
                        clusters_per_client=4, seed=0,
                        psi_backend="device")
    shrd = run_pipeline(tr, te, cfg, variant="treecss",
                        clusters_per_client=4, seed=0,
                        psi_backend="device", mesh=mesh)
    assert np.array_equal(shrd.mpsi.intersection, base.mpsi.intersection)
    assert shrd.mpsi.total_bytes == base.mpsi.total_bytes
    assert shrd.n_train == base.n_train
    assert np.array_equal(shrd.coreset.indices, base.coreset.indices)
    assert np.array_equal(shrd.coreset.weights, base.coreset.weights)
    assert shrd.coreset.shards == len(jax.devices())
    assert shrd.metric == base.metric


def test_unknown_shard_axis_raises():
    """A typo'd shard_axis must raise, not silently run unsharded."""
    from repro.sharding import resolve_batch_mesh

    mesh1 = make_data_mesh(1)
    with pytest.raises(ValueError, match="shard_axis"):
        resolve_batch_mesh(mesh1, "dat")
    part = make_cls_partition(n=120, d=9, clients=3, seed=0)
    with pytest.raises(ValueError, match="shard_axis"):
        cluster_coreset(part, 4, seed=0, mesh=mesh1, shard_axis="model")


def test_single_device_mesh_is_a_noop():
    """A 1-device mesh must take the plain dispatch path (shards == 1),
    so the knob is safe to leave on everywhere."""
    mesh1 = make_data_mesh(1)
    senders, receivers, seeds = _pair_batch(3, 400, seed=2)
    rnd = engine.oprf_round(senders, receivers, seeds, impl="pallas",
                            mesh=mesh1)
    assert rnd.shards == 1
    part = make_cls_partition(n=200, d=9, clients=3, seed=1)
    res = cluster_coreset(part, 4, seed=0, mesh=mesh1)
    assert res.shards == 1
    base = cluster_coreset(part, 4, seed=0)
    assert np.array_equal(res.indices, base.indices)
