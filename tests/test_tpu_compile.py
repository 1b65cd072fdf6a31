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
