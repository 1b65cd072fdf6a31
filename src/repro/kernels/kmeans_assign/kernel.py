"""Pallas TPU kernel: fused K-Means distance + argmin (assign) step.

TPU-native design (vs the CUDA tiling a GPU paper would use):
  · the (BN, d) point tile and the full (K, d) centroid block live in VMEM;
    the -2·P·Cᵀ term runs on the MXU as a single (BN,d)×(d,K) matmul,
  · ‖c‖² is fused in-kernel and the argmin reduction happens in VREGs
    before anything is written back — HBM traffic is N·d reads + 2·N writes,
  · BN and d are padded to multiples of 128 (MXU lane alignment) by ops.py;
    padded centroid rows are masked with +inf via an iota predicate.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

MASK_LARGE = 3.4e38  # python float: +inf stand-in for masked centroid columns


def _assign_kernel(k_real: int, points_ref, cents_ref, assign_ref, dist_ref):
    p = points_ref[...]                       # (BN, d)
    c = cents_ref[...]                        # (Kp, d)
    p2 = jnp.sum(p * p, axis=1, keepdims=True)            # (BN,1)
    c2 = jnp.sum(c * c, axis=1)[None]                     # (1,Kp)
    # MXU matmul: (BN,d) x (d,Kp)
    cross = jax.lax.dot_general(p, c, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
    d2 = p2 - 2.0 * cross + c2                            # (BN,Kp)
    col = jax.lax.broadcasted_iota(jnp.int32, d2.shape, 1)
    # clamp BEFORE the argmin (matching the ref oracle): cancellation can
    # leave tiny negatives whose ordering would otherwise flip ties
    d2 = jnp.where(col < k_real, jnp.maximum(d2, 0.0), MASK_LARGE)
    # per-row results as one (1, BN) lane row: a 2-D block stays on the
    # (8, 128) tiling rule when the call is vmapped (the batch dim is
    # squeezed in front of it)
    assign_ref[...] = jnp.argmin(d2, axis=1).astype(jnp.int32)[None]
    dist_ref[...] = jnp.min(d2, axis=1)[None]


def kmeans_assign_pallas(points: jnp.ndarray, centroids: jnp.ndarray, *,
                         k_real: int, block_n: int = 1024,
                         interpret: bool):
    """points (Np, dp) f32 (padded), centroids (Kp, dp) f32 (padded).

    Np % block_n == 0; dp % 128 == 0; Kp % 128 == 0. Returns
    (assign (Np,) int32, sq_dist (Np,) f32) — caller slices off padding.
    """
    n, d = points.shape
    kp = centroids.shape[0]
    assert n % block_n == 0 and d % 128 == 0 and kp % 128 == 0, (n, d, kp)
    grid = (n // block_n,)
    kernel = functools.partial(_assign_kernel, k_real)
    assign, dist = pl.pallas_call(
        kernel,
        name="kmeans_assign",
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i: (i, 0)),   # point tile
            pl.BlockSpec((kp, d), lambda i: (0, 0)),        # all centroids
        ],
        out_specs=[
            pl.BlockSpec((1, block_n), lambda i: (0, i)),
            pl.BlockSpec((1, block_n), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, n), jnp.int32),
            jax.ShapeDtypeStruct((1, n), jnp.float32),
        ],
        interpret=interpret,
    )(points, centroids)
    return assign[0], dist[0]
