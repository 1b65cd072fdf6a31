"""jit'd public wrapper for the kmeans_assign Pallas kernel.

Pads via the shared k-means kernel layout (``repro.kernels.padding``),
invokes the kernel, slices padding off.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels.kmeans_assign.kernel import kmeans_assign_pallas
from repro.kernels.padding import (interpret, pad_points_centroids,
                                   record_path)


@functools.partial(jax.jit, static_argnames=("block_n",))
def kmeans_assign(points: jnp.ndarray, centroids: jnp.ndarray, *,
                  block_n: int = 1024) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """points (N,d), centroids (K,d) -> (assign (N,) i32, sq_dist (N,) f32)."""
    n, d = points.shape
    k = centroids.shape[0]
    p, c, bn = pad_points_centroids(points, centroids, block_n)
    record_path("kmeans_assign", "pallas")
    assign, dist = kmeans_assign_pallas(p, c, k_real=k, block_n=bn,
                                        interpret=interpret())
    return assign[:n], dist[:n]
