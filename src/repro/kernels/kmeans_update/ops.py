"""jit'd public wrapper for the fused kmeans_update Pallas kernel.

Pads via the shared k-means kernel layout (``repro.kernels.padding``),
invokes the fused assign+accumulate kernel, slices padding off. Padded
point rows are masked out of the per-cluster sums/counts inside the
kernel, so the sliced outputs are exact.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels.kmeans_update.kernel import (kmeans_update_gather_pallas,
                                                kmeans_update_pallas)
from repro.kernels.padding import (GATHER_VMEM_BUDGET, interpret,
                                   pad_gather_idx, pad_points_centroids,
                                   record_path, round_up)


@functools.partial(jax.jit, static_argnames=("block_n",))
def kmeans_update(points: jnp.ndarray, centroids: jnp.ndarray, *,
                  block_n: int = 1024, idx=None
                  ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray,
                             jnp.ndarray]:
    """points (N,d), centroids (K,d) ->
    (assign (N,) i32, sq_dist (N,) f32, sums (K,d) f32, counts (K,) f32).

    With ``idx`` (B,) i32 the update runs over the gathered minibatch
    ``points[idx]`` WITHOUT materializing it: the indices scalar-prefetch
    into the fused kernel (DESIGN.md §8), and the outputs — per-row over
    the B gathered rows, sums/counts over the batch — are bitwise-equal
    to gathering first.
    """
    n, d = points.shape
    k = centroids.shape[0]
    if idx is None:
        p, c, bn = pad_points_centroids(points, centroids, block_n)
        record_path("kmeans_update", "dense")
        assign, dist, sums, counts = kmeans_update_pallas(
            p, c, k_real=k, n_real=n, block_n=bn,
            interpret=interpret())
        return assign[:n], dist[:n], sums[:k, :d], counts[0, :k]
    dp = round_up(d, 128)
    if not interpret() and n * dp * 4 > GATHER_VMEM_BUDGET:
        # the full point set cannot sit resident in VMEM on real TPU:
        # fall back to gather-then-dense (bitwise-identical values)
        pts = points[idx]
        p, c, bn = pad_points_centroids(pts, centroids, block_n)
        b = idx.shape[0]
        record_path("kmeans_update", "gather_fallback")
        assign, dist, sums, counts = kmeans_update_pallas(
            p, c, k_real=k, n_real=b, block_n=bn,
            interpret=interpret())
        return assign[:b], dist[:b], sums[:k, :d], counts[0, :k]
    b = idx.shape[0]
    # d/o-only padding: the gather grid tiles idx, not the point rows,
    # so an already-128-aligned f32 point set passes through untouched
    # (kmeans_minibatch_fit pre-pads once outside its scan)
    p = points.astype(jnp.float32)
    if d < dp:
        p = jnp.pad(p, ((0, 0), (0, dp - d)))
    kp = round_up(k, 128)
    c = jnp.zeros((kp, dp), jnp.float32).at[:k, :d].set(
        centroids.astype(jnp.float32))
    # same block rule the dense path applies to a B-row batch, so fused
    # and unfused tilings (and therefore outputs) coincide bitwise
    idx_p, bn, _ = pad_gather_idx(idx, block_n, align=128)
    record_path("kmeans_update", "gather_fused")
    assign, dist, sums, counts = kmeans_update_gather_pallas(
        idx_p, p, c, k_real=k, b_real=b, block_n=bn,
        interpret=interpret())
    return assign[:b], dist[:b], sums[:k, :d], counts[0, :k]
