"""Shared padding/layout contract for the Pallas kernels.

The k-means kernels (``kmeans_assign``, ``kmeans_update``) tile points
over an N grid and keep all centroids resident: N pads to the block
size, d and K pad to 128 (MXU lane alignment).  The ``splitnn_bottom``
kernel tiles the batch over a B grid with each client's weight block
resident: B pads to the block size, d and o pad to 128.  One definition
here so the contracts — and the interpret-mode switch — cannot silently
diverge between kernels.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.obs.metrics import MetricsRegistry

# Which path each ops wrapper took, counted once per traced program
# (jit re-uses a traced program without re-counting) as
# ``<kernel>.<path>``, e.g. ``splitnn_bottom.gather_fallback``: the
# budget-based fallbacks below report here rather than staying silent.
KERNEL_PATHS = MetricsRegistry()


def record_path(kernel: str, path: str) -> None:
    KERNEL_PATHS.counter(f"{kernel}.{path}").inc()


def interpret() -> bool:
    """Whether the ops wrappers run their Pallas kernels in interpret
    mode: exactly when JAX's default backend is the CPU, which has no
    Mosaic.  Called at trace time, so a TPU process always compiles the
    kernels with Mosaic and can never fall back to the emulator."""
    return jax.default_backend() == "cpu"


# Resident-block budget for the scalar-prefetch gather kernels on real
# TPU: a full (N, d_pad) slab past it cannot sit in VMEM, so the ops
# wrappers fall back to gather-then-dense (bitwise-identical values).
# Interpret mode has no VMEM — CPU runs always exercise the fused
# kernels.
GATHER_VMEM_BUDGET = 12 * 2**20


def round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def pad_points_centroids(points: jnp.ndarray, centroids: jnp.ndarray,
                         block_n: int
                         ) -> Tuple[jnp.ndarray, jnp.ndarray, int]:
    """Zero-pad (N,d) points / (K,d) centroids to the kernel layout.

    Returns (points (Np,dp) f32, centroids (Kp,dp) f32, bn) with
    Np % bn == 0 and dp, Kp multiples of 128, where bn is block_n
    shrunk to the padded N for small inputs.
    """
    n, d = points.shape
    k = centroids.shape[0]
    bn = min(block_n, round_up(n, 128))
    np_, dp, kp = round_up(n, bn), round_up(d, 128), round_up(k, 128)
    p = jnp.zeros((np_, dp), jnp.float32).at[:n, :d].set(
        points.astype(jnp.float32))
    c = jnp.zeros((kp, dp), jnp.float32).at[:k, :d].set(
        centroids.astype(jnp.float32))
    return p, c, bn


def pad_gather_idx(idx: jnp.ndarray, block: int,
                   align: int = 8) -> Tuple[jnp.ndarray, int, int]:
    """Pad a (B,) i32 gather-index vector to the scalar-prefetch kernel
    layout shared by ``splitnn_bottom`` and ``kmeans_update``.

    Returns (idx (Bp,) i32, bb, B) with Bp % bb == 0, where bb is
    ``block`` shrunk to the padded B for small batches (the same rule
    the dense batch pads use, so fused and unfused tilings coincide).
    Padding slots point at row 0 — a real, in-bounds row — which keeps
    every gathered tile shape- and dtype-representative; the padded
    positions are sliced off (per-row outputs) or masked out of every
    accumulation (per-cluster sums/counts) downstream, exactly like the
    zero-padded rows of the dense contract.
    """
    b = int(idx.shape[0])
    bb = min(block, round_up(b, align))
    bp = round_up(b, bb)
    idx = jnp.asarray(idx, jnp.int32)
    if bp > b:
        idx = jnp.concatenate([idx, jnp.zeros((bp - b,), jnp.int32)])
    return idx, bb, b


def pad_bottom_blocks_gather(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray
                             ) -> Tuple[jnp.ndarray, jnp.ndarray,
                                        jnp.ndarray]:
    """d/o-only padding for the ``splitnn_bottom`` gather kernel.

    The gather grid tiles the idx vector, not the slab rows, so the full
    (M, N, d) slab needs NO row padding — only d aligned to 128 (and w/b
    padded as in ``pad_bottom_blocks``).  An already-aligned f32 slab
    passes through untouched, which is how the train engine avoids
    re-copying the loop-invariant slab on every scan step: it pre-pads d
    once outside the scan (``train.vfl``), and this helper becomes a
    no-op on x.
    """
    m, n, d = x.shape
    dw, o = w.shape[1], w.shape[2]
    dp, op = round_up(dw, 128), round_up(o, 128)
    assert round_up(d, 128) == dp, (d, dw)
    x = x.astype(jnp.float32)
    if d < dp:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, dp - d)))
    wp = jnp.zeros((m, dp, op), jnp.float32).at[:, :dw, :o].set(
        w.astype(jnp.float32))
    bp = jnp.zeros((m, 1, op), jnp.float32).at[:, 0, :o].set(
        b.astype(jnp.float32))
    return x, wp, bp


def pad_bottom_blocks(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray,
                      block_b: int
                      ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, int]:
    """Zero-pad the (M, B, d) batch slab / (M, d, o) client weight stack /
    (M, o) biases to the ``splitnn_bottom`` kernel layout.

    Returns (x (M, Bp, dp) f32, w (M, dp, op) f32, b (M, 1, op) f32, bb)
    with Bp % bb == 0 and dp, op multiples of 128, where bb is block_b
    shrunk to the padded B for small batches.  Zero padding is exact:
    padded d columns multiply zero features, padded o columns read back
    sliced off, padded B rows are discarded by the caller.  ``x`` may
    arrive pre-padded wider than ``w`` (the train engine aligns the
    slab's d once, outside its scan) — the zero columns land on zero
    weight rows either way.
    """
    m, n, d = x.shape
    dw, o = w.shape[1], w.shape[2]
    bb = min(block_b, round_up(n, 8))
    bp, dp, op = round_up(n, bb), round_up(max(d, dw), 128), round_up(o, 128)
    xp = jnp.zeros((m, bp, dp), jnp.float32).at[:, :n, :d].set(
        x.astype(jnp.float32))
    wp = jnp.zeros((m, dp, op), jnp.float32).at[:, :dw, :o].set(
        w.astype(jnp.float32))
    bb_pad = jnp.zeros((m, 1, op), jnp.float32).at[:, 0, :o].set(
        b.astype(jnp.float32))
    return xp, wp, bb_pad, bb
