"""Compile every main-path Pallas kernel for a TPU v5e, at the paper's HI
widths, without a chip: the TPU compiler is installed and compiles for
a described topology (JAX_PLATFORMS may stay "cpu").  What interpret
mode cannot see fails here: block shapes off the (8, 128) tiling,
primitives Mosaic has no lowering for, and more scoped VMEM than a
kernel may use.  Each test asserts the kernel reached the program as a
Mosaic ``tpu_custom_call``.

The topology is described inside a module fixture, never at import: one
process at a time may load the TPU library, and under pytest-xdist only
the worker that runs these tests should.  Where it cannot be described,
the fixture skips.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.kmeans_assign.kernel import kmeans_assign_pallas
from repro.kernels.kmeans_update.kernel import (kmeans_update_gather_pallas,
                                                kmeans_update_pallas)
from repro.kernels.padding import GATHER_VMEM_BUDGET
from repro.kernels.psi_prf.kernel import prf_tags_pallas
from repro.kernels.sorted_intersect.kernel import (SINGLE_PASS_MAX_P,
                                                   merge_pallas, merge_tiled)
from repro.kernels.splitnn_bottom.kernel import (
    splitnn_bottom_gather_pallas, splitnn_bottom_int8_gather_pallas,
    splitnn_bottom_int8_pallas, splitnn_bottom_pallas)

# HI (paper Table 1): 100K rows x 32 features over 3 parties, 70/30
# split -> 70K ids per party, ~49K aligned rows, <= 11 features per
# party (one 128-lane tile), 12 clusters per party (one 128 K tile)
M, N_ALIGNED, DP, KP, K = 3, 49_152, 128, 128, 12
P_HI = 1 << 17                       # next_pow2(70_000)
BLOCK_B, OP = 512, 128
GATHER_ROWS = GATHER_VMEM_BUDGET // (4 * DP)   # f32 slab at the budget
# YP (paper Table 1): 510K rows x 90 features over 3 parties, 70/30
# split -> 357K ids per party, 249,900 aligned rows (padded to the
# 1024-row k-means block), 30 features per party; linreg bottoms have
# one output (lane-padded to 128) and no ReLU; the coreset trained at
# batch 64 holds ~11,700 rows
YP_ALIGNED, YP_ROWS_PADDED, YP_CORESET, YP_BATCH = (249_900, 250_880,
                                                    11_698, 64)

f32, i32, i8, u32 = jnp.float32, jnp.int32, jnp.int8, jnp.uint32


@pytest.fixture(scope="module")
def chip():
    """A described v5e chip to compile for; the persistent compile cache
    is off while these tests run (an entry written here could not be
    read back without a chip)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                       # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def test_psi_prf(chip):
    _compile(chip, lambda hi, lo: prf_tags_pallas(
        hi, lo, block_n=2048, interpret=False), ((P_HI,), u32), ((P_HI,), u32))


def test_sorted_intersect_single_pass(chip):
    p = SINGLE_PASS_MAX_P
    _compile(chip, lambda *a: merge_pallas(*a, interpret=False),
             *[((p,), u32)] * 4)


def test_sorted_intersect_single_pass_vmapped_over_pairs(chip):
    """The PSI engine runs one round's pairs as a vmap of the merge."""
    _compile(chip, jax.vmap(lambda *a: merge_pallas(*a, interpret=False)),
             *[((3, P_HI), u32)] * 4)


def test_sorted_intersect_tiled(chip):
    text = _compile(chip, lambda *a: merge_tiled(*a, interpret=False),
                    *[((2 * SINGLE_PASS_MAX_P,), u32)] * 4)
    assert "sorted_intersect_cross" in text


def test_kmeans_update_dense(chip):
    _compile(chip, lambda p, c: kmeans_update_pallas(
        p, c, k_real=K, n_real=49_000, block_n=1024, interpret=False),
        ((N_ALIGNED, DP), f32), ((KP, DP), f32))


def test_kmeans_update_dense_vmapped_over_clients(chip):
    """The coreset stage fits all M clients as one vmap of kmeans_fit."""
    fn = jax.vmap(lambda p, c: kmeans_update_pallas(
        p, c, k_real=K, n_real=49_000, block_n=1024, interpret=False))
    _compile(chip, fn, ((M, N_ALIGNED, DP), f32), ((M, KP, DP), f32))


def test_kmeans_update_gather(chip):
    _compile(chip, lambda idx, p, c: kmeans_update_gather_pallas(
        idx, p, c, k_real=K, b_real=1000, block_n=1024, interpret=False),
        ((1024,), i32), ((GATHER_ROWS, DP), f32), ((KP, DP), f32))


def test_kmeans_assign(chip):
    _compile(chip, lambda p, c: kmeans_assign_pallas(
        p, c, k_real=K, block_n=1024, interpret=False),
        ((N_ALIGNED, DP), f32), ((KP, DP), f32))


@pytest.mark.parametrize("quant", [None, "int8"])
def test_splitnn_bottom_dense(chip, quant):
    x, w, b = (M, 4 * BLOCK_B, DP), (M, DP, OP), (M, 1, OP)
    if quant is None:
        _compile(chip, lambda x_, w_, b_: splitnn_bottom_pallas(
            x_, w_, b_, relu=True, block_b=BLOCK_B, interpret=False),
            (x, f32), (w, f32), (b, f32))
    else:
        _compile(chip, lambda x_, sx, w_, sw, b_: splitnn_bottom_int8_pallas(
            x_, sx, w_, sw, b_, relu=True, block_b=BLOCK_B, interpret=False),
            (x, i8), ((M, 1, 4 * BLOCK_B), f32), (w, i8), (b, f32), (b, f32))


@pytest.mark.parametrize("quant", [None, "int8"])
def test_splitnn_bottom_gather(chip, quant):
    """Slabs exactly at GATHER_VMEM_BUDGET, the largest the ops wrapper
    fuses: f32 rows, and 4x the rows for int8 (packed 4 to a word)."""
    w, b = (M, DP, OP), (M, 1, OP)
    idx = ((2 * BLOCK_B,), i32)
    if quant is None:
        _compile(chip, lambda i, x_, w_, b_: splitnn_bottom_gather_pallas(
            i, x_, w_, b_, relu=True, block_b=BLOCK_B, interpret=False),
            idx, ((M, GATHER_ROWS, DP), f32), (w, f32), (b, f32))
    else:
        _compile(chip, lambda i, x_, sx, w_, sw, b_:
                 splitnn_bottom_int8_gather_pallas(
                     i, x_, sx, w_, sw, b_, relu=True, block_b=BLOCK_B,
                     interpret=False),
                 idx, ((M, GATHER_ROWS, DP), i32),
                 ((M, 1, 2 * BLOCK_B), f32), (w, i8), (b, f32), (b, f32))


def test_psi_prf_vmapped_over_pairs(chip):
    fn = jax.vmap(lambda hi, lo: prf_tags_pallas(
        hi, lo, block_n=2048, interpret=False))
    _compile(chip, fn, ((3, P_HI), u32), ((3, P_HI), u32))


def test_kmeans_assign_vmapped_over_clients(chip):
    fn = jax.vmap(lambda p, c: kmeans_assign_pallas(
        p, c, k_real=K, block_n=1024, interpret=False))
    _compile(chip, fn, ((M, N_ALIGNED, DP), f32), ((M, KP, DP), f32))


def test_kmeans_update_dense_vmapped_over_clients_at_yp_size(chip):
    fn = jax.vmap(lambda p, c: kmeans_update_pallas(
        p, c, k_real=K, n_real=YP_ALIGNED, block_n=1024, interpret=False))
    _compile(chip, fn, ((M, YP_ROWS_PADDED, DP), f32), ((M, KP, DP), f32))


def test_splitnn_bottom_linear_at_the_scoring_shape(chip):
    """Scoring runs the bottom over one 512-row block at a time."""
    _compile(chip, lambda x_, w_, b_: splitnn_bottom_pallas(
        x_, w_, b_, relu=False, block_b=BLOCK_B, interpret=False),
        ((M, BLOCK_B, DP), f32), ((M, DP, OP), f32), ((M, 1, OP), f32))


def test_splitnn_bottom_linear_gather_on_a_yp_coreset_slab(chip):
    """Training gathers each batch of 64 from the whole coreset slab,
    whose row count is the slab's own (no multiple of 8)."""
    assert YP_CORESET * DP * 4 <= GATHER_VMEM_BUDGET
    _compile(chip, lambda i, x_, w_, b_: splitnn_bottom_gather_pallas(
        i, x_, w_, b_, relu=False, block_b=YP_BATCH, interpret=False),
        ((YP_BATCH,), i32), ((M, YP_CORESET, DP), f32), ((M, DP, OP), f32),
        ((M, 1, OP), f32))


def test_bottom_gradient_gathers_rows_without_copying_the_slab(chip):
    """The backward gathers each batch's rows from the whole coreset slab
    again for dW.  Gathered as (M, 1, d) slices, XLA relayouts the whole
    slab on every training step; gathered as rows it copies none."""
    from repro.kernels.splitnn_bottom.ops import splitnn_bottom

    def grad(w, b, x, i):
        return jax.grad(lambda w_, b_: jnp.sum(splitnn_bottom(
            x, w_, b_, False, "ref", YP_BATCH, i) ** 2),
            argnums=(0, 1))(w, b)

    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in (
        ((M, DP, 1), f32), ((M, 1), f32), ((M, YP_CORESET, DP), f32),
        ((YP_BATCH,), i32))]
    text = jax.jit(grad).lower(*args).compile().as_text()
    slab = f"f32[{M},{YP_CORESET},{DP}]"
    assert not [line for line in text.splitlines()
                if slab in line and " copy(" in line]


@pytest.mark.parametrize("model,n_classes,widths,n_test", [
    ("linreg", 0, (30, 30, 30), 153_000),      # YP's 30% test rows
    ("mlp", 2, (11, 11, 10), 30_000)])         # HI's
def test_whole_partition_scoring_program(chip, monkeypatch, model, n_classes,
                                         widths, n_test):
    """Scoring maps the 512-row block forward over the whole test
    partition in one program: the bottom kernel sits in the device loop
    as a Mosaic call.  The ops wrapper asks the backend whether to
    interpret, and the backend here is the CPU, so it is told not to."""
    from repro.core import splitnn as models
    from repro.core.splitnn import SplitNNConfig
    from repro.kernels.splitnn_bottom import ops
    from repro.train.vfl import _score_blocks_fn, pack_slab_params

    monkeypatch.setattr(ops, "interpret", lambda: False)
    cfg = SplitNNConfig(model=model, n_classes=n_classes)
    packed = jax.eval_shape(lambda: pack_slab_params(
        models.init_splitnn(cfg, list(widths)), max(widths)))
    packed = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
        packed)
    xs = [jax.ShapeDtypeStruct((n_test, d), f32, sharding=chip)
          for d in widths]
    # a fresh jit, so no trace made for the CPU elsewhere is reused
    fn = _score_blocks_fn.__wrapped__(cfg, M, "pallas", BLOCK_B, None)
    text = fn.lower(packed, *xs).compile().as_text()
    assert "tpu_custom_call" in text
