"""Pallas TPU kernel: fused K-Means Lloyd update — distance + argmin +
per-cluster sum/count accumulation in ONE pass over the points.

The seed pipeline ran assign as a kernel but then materialized an (N, K)
one-hot in HBM and paid a second full read of the points for
``one_hot.T @ points``. Here the (BN, d) point tile never leaves VMEM
between the assign and the accumulate:

  · d² = ‖p‖² − 2·P·Cᵀ + ‖c‖² on the MXU, argmin in VREGs (as in
    ``kmeans_assign``),
  · the tile's one-hot is rebuilt in VREGs from the argmin via an iota
    compare — it is never written anywhere,
  · tile partial sums (Kp, d) come from a second MXU matmul
    one_hotᵀ·P against the SAME resident point tile; counts are a VPU
    row-reduction,
  · the (Kp, d) sums and (1, Kp) counts outputs map every grid step to
    block (0, 0): the TPU grid is sequential, so Pallas keeps the block
    resident in VMEM across steps (revisiting) and we accumulate with
    ``+=`` after a first-step zero-init.

HBM traffic per Lloyd iteration drops from N·d reads (assign) + N·K +
N·d reads (one-hot update) to a single N·d read + O(K·d) write.

Padding contract (enforced by ops.py): Np % block_n == 0, dp % 128 == 0,
Kp % 128 == 0. Padded centroid columns are masked to +inf before the
argmin; padded point rows (row index ≥ n_real) are masked OUT of the
one-hot so they contribute to no cluster's sum/count.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

MASK_LARGE = 3.4e38  # python float: +inf stand-in for masked centroid columns


def _tile_update(p, c, k_real: int, row):
    """Shared assign + accumulate math for one resident (BN, d) tile.

    ``row`` is the tile's global row-index column (used only to mask
    padded rows OUT of the one-hot); returns (assign, dist, tile_sums,
    tile_counts).  One definition so the dense and gather-fused kernels
    cannot diverge in tie-breaks or accumulation order.
    """
    p2 = jnp.sum(p * p, axis=1, keepdims=True)            # (BN,1)
    c2 = jnp.sum(c * c, axis=1)[None]                     # (1,Kp)
    # MXU matmul #1: (BN,d) x (d,Kp)
    cross = jax.lax.dot_general(p, c, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
    d2 = p2 - 2.0 * cross + c2                            # (BN,Kp)
    col = jax.lax.broadcasted_iota(jnp.int32, d2.shape, 1)
    # clamp BEFORE the argmin (matching the ref oracle): cancellation can
    # leave tiny negatives whose ordering would otherwise flip ties
    d2 = jnp.where(col < k_real, jnp.maximum(d2, 0.0), MASK_LARGE)
    assign = jnp.argmin(d2, axis=1).astype(jnp.int32)     # (BN,)
    dist = jnp.min(d2, axis=1)

    # one-hot rebuilt in VREGs; padded rows masked out of the accumulation
    one_hot = jnp.where((col == assign[:, None]) & (row[:, None] >= 0),
                        1.0, 0.0).astype(jnp.float32)     # (BN,Kp)
    # MXU matmul #2 against the SAME resident tile: (Kp,BN) x (BN,d)
    tile_sums = jax.lax.dot_general(one_hot, p, (((0,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)
    tile_counts = jnp.sum(one_hot, axis=0)[None]          # (1,Kp)
    return assign, dist, tile_sums, tile_counts


def _update_kernel(k_real: int, n_real: int, block_n: int,
                   points_ref, cents_ref,
                   assign_ref, dist_ref, sums_ref, counts_ref):
    i = pl.program_id(0)
    p = points_ref[...]                       # (BN, d)   resident tile
    c = cents_ref[...]                        # (Kp, d)
    row = i * block_n + jax.lax.broadcasted_iota(jnp.int32, (p.shape[0],), 0)
    valid_row = jnp.where(row < n_real, row, -1)
    assign, dist, tile_sums, tile_counts = _tile_update(p, c, k_real,
                                                        valid_row)
    assign_ref[...] = assign[None]            # (1, BN) lane rows, as in
    dist_ref[...] = dist[None]                # kmeans_assign

    @pl.when(i == 0)
    def _():
        sums_ref[...] = jnp.zeros_like(sums_ref)
        counts_ref[...] = jnp.zeros_like(counts_ref)

    sums_ref[...] += tile_sums
    counts_ref[...] += tile_counts


def kmeans_update_pallas(points: jnp.ndarray, centroids: jnp.ndarray, *,
                         k_real: int, n_real: int, block_n: int = 1024,
                         interpret: bool):
    """points (Np, dp) f32 (padded), centroids (Kp, dp) f32 (padded).

    Np % block_n == 0; dp % 128 == 0; Kp % 128 == 0. Returns
    (assign (Np,) i32, sq_dist (Np,) f32, sums (Kp, dp) f32,
    counts (1, Kp) f32) — caller slices off padding.
    """
    n, d = points.shape
    kp = centroids.shape[0]
    assert n % block_n == 0 and d % 128 == 0 and kp % 128 == 0, (n, d, kp)
    grid = (n // block_n,)
    kernel = functools.partial(_update_kernel, k_real, n_real, block_n)
    assign, dist, sums, counts = pl.pallas_call(
        kernel,
        name="kmeans_update",
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i: (i, 0)),   # point tile
            pl.BlockSpec((kp, d), lambda i: (0, 0)),        # all centroids
        ],
        out_specs=[
            pl.BlockSpec((1, block_n), lambda i: (0, i)),
            pl.BlockSpec((1, block_n), lambda i: (0, i)),
            pl.BlockSpec((kp, d), lambda i: (0, 0)),        # revisited accum
            pl.BlockSpec((1, kp), lambda i: (0, 0)),        # revisited accum
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, n), jnp.int32),
            jax.ShapeDtypeStruct((1, n), jnp.float32),
            jax.ShapeDtypeStruct((kp, d), jnp.float32),
            jax.ShapeDtypeStruct((1, kp), jnp.float32),
        ],
        interpret=interpret,
    )(points, centroids)
    return assign[0], dist[0], sums, counts


# ------------------------------------------------- scalar-prefetch gather


def _update_gather_kernel(k_real: int, b_real: int, block_n: int,
                          idx_ref, points_ref, cents_ref,
                          assign_ref, dist_ref, sums_ref, counts_ref,
                          buf_ref):
    i = pl.program_id(0)

    def gather_row(r, c):
        j = idx_ref[i * block_n + r]              # prefetched batch index
        buf_ref[pl.ds(r, 1), :] = points_ref[pl.ds(j, 1), :]
        return c

    # rows land in a VMEM scratch tile, one (1, dp) store each
    jax.lax.fori_loop(0, block_n, gather_row, 0)
    p = buf_ref[...]                              # (BN, dp) gathered tile
    c = cents_ref[...]                            # (Kp, dp)
    row = i * block_n + jax.lax.broadcasted_iota(jnp.int32, (block_n,), 0)
    valid_row = jnp.where(row < b_real, row, -1)  # mask idx-padding slots
    assign, dist, tile_sums, tile_counts = _tile_update(p, c, k_real,
                                                        valid_row)
    assign_ref[...] = assign[None]            # (1, BN) lane rows, as in
    dist_ref[...] = dist[None]                # kmeans_assign

    @pl.when(i == 0)
    def _():
        sums_ref[...] = jnp.zeros_like(sums_ref)
        counts_ref[...] = jnp.zeros_like(counts_ref)

    sums_ref[...] += tile_sums
    counts_ref[...] += tile_counts


def kmeans_update_gather_pallas(idx: jnp.ndarray, points: jnp.ndarray,
                                centroids: jnp.ndarray, *, k_real: int,
                                b_real: int, block_n: int = 1024,
                                interpret: bool):
    """Gather-fused Lloyd update for the mini-batch path: the
    ``points[idx]`` minibatch gather moves INTO the kernel via scalar
    prefetch, so the gathered batch never round-trips through HBM
    before the assign+accumulate pass.

    ``idx`` (Bp,) i32 (Bp % block_n == 0; padding slots point at row 0
    per ``padding.pad_gather_idx`` and are masked out of sums/counts by
    ``b_real``), ``points`` (Np, dp) f32 — the FULL point set is the
    resident block, read from HBM once per call — ``centroids``
    (Kp, dp) f32.  Returns (assign (Bp,) i32, sq_dist (Bp,) f32,
    sums (Kp, dp) f32, counts (1, Kp) f32) over the gathered rows,
    bitwise-equal to gathering first and running the dense kernel.
    """
    np_, dp = points.shape
    kp = centroids.shape[0]
    bp = idx.shape[0]
    assert bp % block_n == 0 and dp % 128 == 0 and kp % 128 == 0, \
        (bp, dp, kp, block_n)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bp // block_n,),
        in_specs=[
            pl.BlockSpec((np_, dp), lambda i, idx_ref: (0, 0)),
            pl.BlockSpec((kp, dp), lambda i, idx_ref: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_n), lambda i, idx_ref: (0, i)),
            pl.BlockSpec((1, block_n), lambda i, idx_ref: (0, i)),
            pl.BlockSpec((kp, dp), lambda i, idx_ref: (0, 0)),  # revisited
            pl.BlockSpec((1, kp), lambda i, idx_ref: (0, 0)),   # revisited
        ],
        scratch_shapes=[pltpu.VMEM((block_n, dp), jnp.float32)],
    )
    kernel = functools.partial(_update_gather_kernel, k_real, b_real,
                               block_n)
    assign, dist, sums, counts = pl.pallas_call(
        kernel,
        name="kmeans_update_gather",
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((1, bp), jnp.int32),
            jax.ShapeDtypeStruct((1, bp), jnp.float32),
            jax.ShapeDtypeStruct((kp, dp), jnp.float32),
            jax.ShapeDtypeStruct((1, kp), jnp.float32),
        ],
        interpret=interpret,
    )(jnp.asarray(idx, jnp.int32), points, centroids)
    return assign[0], dist[0], sums, counts
