"""Pallas TPU kernel: fused block-diagonal SplitNN bottom layer.

All M clients' bottom models are independent GEMMs over disjoint feature
slices — a block-diagonal matmul.  The legacy forward ran them as an
M-long Python loop of small ``x_m @ w_m`` dispatches; here the whole
padded (M, B, d_max) slab runs in ONE pallas_call:

  · grid (M, B/bb): step (m, i) loads client m's (bb, dp) batch tile and
    its full (dp, op) weight block into VMEM,
  · one MXU matmul per step, + bias + optional ReLU in VREGs,
  · the weight block's index map ignores i, so the TPU's sequential grid
    keeps w[m] resident in VMEM across all of client m's batch tiles
    (revisiting) — each weight block is read from HBM once per call, not
    once per tile.

Padding contract (``padding.pad_bottom_blocks``, enforced by ops.py):
Bp % bb == 0, dp % 128 == 0, op % 128 == 0; zero-padded d columns
multiply zero features (exact), padded B rows / o columns are sliced off
by the caller.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _bottom_kernel(relu: bool, x_ref, w_ref, b_ref, out_ref):
    x = x_ref[0]                              # (bb, dp) batch tile
    w = w_ref[0]                              # (dp, op) resident weights
    a = jax.lax.dot_general(x, w, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    a = a + b_ref[0]                          # (1, op) broadcasts
    out_ref[0] = jnp.maximum(a, 0.0) if relu else a


def splitnn_bottom_pallas(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray, *,
                          relu: bool, block_b: int = 512,
                          interpret: bool) -> jnp.ndarray:
    """x (M, Bp, dp) f32, w (M, dp, op) f32, b (M, 1, op) f32 (padded).

    Bp % block_b == 0; dp % 128 == 0; op % 128 == 0.  Returns
    (M, Bp, op) f32 — caller slices off padding.
    """
    m, bp, dp = x.shape
    op = w.shape[2]
    assert bp % block_b == 0 and dp % 128 == 0 and op % 128 == 0, \
        (m, bp, dp, op, block_b)
    grid = (m, bp // block_b)
    kernel = functools.partial(_bottom_kernel, relu)
    return pl.pallas_call(
        kernel,
        name="splitnn_bottom",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_b, dp), lambda m, i: (m, i, 0)),
            pl.BlockSpec((1, dp, op), lambda m, i: (m, 0, 0)),  # resident
            pl.BlockSpec((1, 1, op), lambda m, i: (m, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_b, op), lambda m, i: (m, i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, bp, op), jnp.float32),
        interpret=interpret,
    )(x, w, b)


# ----------------------------------------------------- int8 dense variant


def _bottom_int8_kernel(relu: bool, xq_ref, sx_ref, wq_ref, sw_ref, b_ref,
                        out_ref):
    xq = xq_ref[0]                            # (bb, dp) int8 batch tile
    wq = wq_ref[0]                            # (dp, op) resident int8 weights
    acc = jax.lax.dot_general(xq, wq, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)  # MXU i8 path
    # rank-1 f32 epilogue: per-row scale x per-column scale, then bias
    scale = sx_ref[0].reshape(-1, 1) * sw_ref[0]        # (bb, 1) x (1, op)
    a = acc.astype(jnp.float32) * scale + b_ref[0]
    out_ref[0] = jnp.maximum(a, 0.0) if relu else a


def splitnn_bottom_int8_pallas(xq: jnp.ndarray, sx: jnp.ndarray,
                               wq: jnp.ndarray, sw: jnp.ndarray,
                               b: jnp.ndarray, *, relu: bool,
                               block_b: int = 512,
                               interpret: bool) -> jnp.ndarray:
    """int8 twin of :func:`splitnn_bottom_pallas` (DESIGN.md §12).

    xq (M, Bp, dp) i8, sx (M, 1, Bp) f32 per-row dequant scales (lane
    axis = batch, tiled (1, 1, bb) alongside the batch grid), wq
    (M, dp, op) i8, sw (M, 1, op) f32 per-column scales, b (M, 1, op)
    f32.  Same grid/residency scheme as the f32 kernel; the matmul
    accumulates i8 x i8 -> i32 on the MXU's native int path and the
    f32 scale/bias epilogue runs in VREGs.  Returns (M, Bp, op) f32.
    """
    m, bp, dp = xq.shape
    op = wq.shape[2]
    assert bp % block_b == 0 and dp % 128 == 0 and op % 128 == 0, \
        (m, bp, dp, op, block_b)
    grid = (m, bp // block_b)
    kernel = functools.partial(_bottom_int8_kernel, relu)
    return pl.pallas_call(
        kernel,
        name="splitnn_bottom_int8",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_b, dp), lambda m, i: (m, i, 0)),
            pl.BlockSpec((1, 1, block_b), lambda m, i: (m, 0, i)),
            pl.BlockSpec((1, dp, op), lambda m, i: (m, 0, 0)),  # resident
            pl.BlockSpec((1, 1, op), lambda m, i: (m, 0, 0)),
            pl.BlockSpec((1, 1, op), lambda m, i: (m, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_b, op), lambda m, i: (m, i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, bp, op), jnp.float32),
        interpret=interpret,
    )(xq, sx, wq, sw, b)


# ------------------------------------------------- scalar-prefetch gather


def _slab_spec(np_: int, dp: int):
    """Client m's full slab as one block.  Its index changes only with
    the outer client axis, so one buffer suffices: double-buffering it
    would halve the slab the scoped VMEM admits (``GATHER_VMEM_BUDGET``)
    to hide one DMA per client."""
    return pl.BlockSpec((1, np_, dp), lambda m, i, idx_ref: (m, 0, 0),
                        pipeline_mode=pl.Buffered(1))


def _gather_rows(idx_ref, x_ref, buf_ref, block_b: int):
    """Copy the batch tile's scheduled slab rows into the VMEM scratch
    tile, one (1, dp) row store each (prefetched indices, grid axis 1 =
    batch tile)."""
    i = pl.program_id(1)

    def gather_row(r, c):
        j = idx_ref[i * block_b + r]              # prefetched schedule slot
        buf_ref[pl.ds(r, 1), :] = x_ref[0, pl.ds(j, 1), :]
        return c

    jax.lax.fori_loop(0, block_b, gather_row, 0)


def pack_int8_rows(xq: jnp.ndarray) -> jnp.ndarray:
    """(M, N, dp) i8 -> (M, ceil(N/4), dp) i32, rows 4q..4q+3 as bytes
    0..3 of word row q.  Mosaic tiles i8 as (32, 128), so a single i8
    row at a dynamic offset cannot be loaded; a 32-bit word row can, at
    the same 1 byte per element of VMEM."""
    m, n, dp = xq.shape
    n4 = -(-n // 4) * 4
    u = jax.lax.bitcast_convert_type(xq, jnp.uint8).astype(jnp.uint32)
    u = jnp.pad(u, ((0, 0), (0, n4 - n), (0, 0))).reshape(m, n4 // 4, 4, dp)
    shifts = (8 * jnp.arange(4, dtype=jnp.uint32))[None, None, :, None]
    word = jnp.bitwise_or.reduce(u << shifts, axis=2)
    return jax.lax.bitcast_convert_type(word, jnp.int32)


def _gather_packed_rows(idx_ref, x_ref, buf_ref, block_b: int):
    """``_gather_rows`` over a ``pack_int8_rows`` slab: load word row
    j // 4, sign-extend byte j % 4 into the i32 scratch tile."""
    i = pl.program_id(1)

    def gather_row(r, c):
        j = idx_ref[i * block_b + r]
        word = x_ref[0, pl.ds(j >> 2, 1), :]      # (1, dp) i32, 4 rows
        buf_ref[pl.ds(r, 1), :] = (word << (24 - 8 * (j & 3))) >> 24
        return c

    jax.lax.fori_loop(0, block_b, gather_row, 0)


def _bottom_gather_kernel(relu: bool, block_b: int, idx_ref,
                          x_ref, w_ref, b_ref, out_ref, buf_ref):
    _gather_rows(idx_ref, x_ref, buf_ref, block_b)
    x = buf_ref[...]                              # (bb, dp) gathered tile
    w = w_ref[0]                                  # (dp, op) resident weights
    a = jax.lax.dot_general(x, w, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    a = a + b_ref[0]
    out_ref[0] = jnp.maximum(a, 0.0) if relu else a


def splitnn_bottom_gather_pallas(idx: jnp.ndarray, x: jnp.ndarray,
                                 w: jnp.ndarray, b: jnp.ndarray, *,
                                 relu: bool, block_b: int = 512,
                                 interpret: bool) -> jnp.ndarray:
    """Gather-fused forward: the per-step ``slab[:, idx, :]`` minibatch
    gather moves INTO the kernel via scalar prefetch
    (``pltpu.PrefetchScalarGridSpec``), so the gathered batch never
    round-trips through HBM between the schedule lookup and the matmul.

    ``idx`` (Bp,) i32 schedule indices (prefetched, available before the
    body runs), ``x`` (M, Np, dp) f32 — client m's FULL feature slab is
    the resident block (index map ignores the batch index, so the
    sequential grid reads it from HBM once per client, like the weight
    block), ``w`` (M, dp, op), ``b`` (M, 1, op).  Bp % block_b == 0;
    dp % 128 == 0; op % 128 == 0; every idx value < Np (padding slots
    point at row 0 per ``padding.pad_gather_idx``).  Returns
    (M, Bp, op) f32 — caller slices off the idx padding.

    VMEM bound: the resident slab block is Np·dp·4 bytes per client
    (ops.py falls back to the dense path past the budget on real TPU;
    values are bitwise-identical either way).
    """
    m, np_, dp = x.shape
    op = w.shape[2]
    bp = idx.shape[0]
    assert bp % block_b == 0 and dp % 128 == 0 and op % 128 == 0, \
        (m, bp, dp, op, block_b)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(m, bp // block_b),
        in_specs=[
            _slab_spec(np_, dp),
            pl.BlockSpec((1, dp, op), lambda m, i, idx_ref: (m, 0, 0)),
            pl.BlockSpec((1, 1, op), lambda m, i, idx_ref: (m, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_b, op),
                               lambda m, i, idx_ref: (m, i, 0)),
        scratch_shapes=[pltpu.VMEM((block_b, dp), jnp.float32)],
    )
    kernel = functools.partial(_bottom_gather_kernel, relu, block_b)
    return pl.pallas_call(
        kernel,
        name="splitnn_bottom_gather",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, bp, op), jnp.float32),
        interpret=interpret,
    )(jnp.asarray(idx, jnp.int32), x, w, b)


def _bottom_int8_gather_kernel(relu: bool, block_b: int, idx_ref,
                               xq_ref, sx_ref, wq_ref, sw_ref, b_ref,
                               out_ref, buf_ref):
    _gather_packed_rows(idx_ref, xq_ref, buf_ref, block_b)
    xq = buf_ref[...].astype(jnp.int8)            # (bb, dp) int8 tile
    acc = jax.lax.dot_general(xq, wq_ref[0], (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    scale = sx_ref[0].reshape(-1, 1) * sw_ref[0]
    a = acc.astype(jnp.float32) * scale + b_ref[0]
    out_ref[0] = jnp.maximum(a, 0.0) if relu else a


def splitnn_bottom_int8_gather_pallas(idx: jnp.ndarray, xw: jnp.ndarray,
                                      sx: jnp.ndarray, wq: jnp.ndarray,
                                      sw: jnp.ndarray, b: jnp.ndarray, *,
                                      relu: bool, block_b: int = 512,
                                      interpret: bool) -> jnp.ndarray:
    """int8 twin of :func:`splitnn_bottom_gather_pallas`.

    The resident slab is int8 — 1 byte/element instead of 4 — so the
    gather fusion stays within ``GATHER_VMEM_BUDGET`` at 4x the slab
    rows of the f32 variant (ops.py admits with a 1-byte element size).
    It arrives packed four rows to an i32 word row (``xw`` =
    ``pack_int8_rows(xq)``, (M, ceil(N/4), dp) i32); rows are unpacked
    into an i32 scratch tile and narrowed to i8 for the MXU matmul.
    Per-row scales commute with the row gather, so ``sx`` here is the
    ALREADY-GATHERED (M, 1, Bp) f32 scale vector for the scheduled rows
    (the (B,)-long ``jnp.take`` on the tiny exponent vector happens
    outside; only the wide (N, d) slab gather fuses into the kernel).
    Row quantization of the slab is loop-invariant across the epoch
    scan, so XLA hoists it out of the step loop — the slab is quantized
    once per epoch, not once per step.
    """
    m, nw, dp = xw.shape
    op = wq.shape[2]
    bp = idx.shape[0]
    assert bp % block_b == 0 and dp % 128 == 0 and op % 128 == 0, \
        (m, bp, dp, op, block_b)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(m, bp // block_b),
        in_specs=[
            _slab_spec(nw, dp),
            pl.BlockSpec((1, 1, block_b), lambda m, i, idx_ref: (m, 0, i)),
            pl.BlockSpec((1, dp, op), lambda m, i, idx_ref: (m, 0, 0)),
            pl.BlockSpec((1, 1, op), lambda m, i, idx_ref: (m, 0, 0)),
            pl.BlockSpec((1, 1, op), lambda m, i, idx_ref: (m, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_b, op),
                               lambda m, i, idx_ref: (m, i, 0)),
        scratch_shapes=[pltpu.VMEM((block_b, dp), jnp.int32)],
    )
    kernel = functools.partial(_bottom_int8_gather_kernel, relu, block_b)
    return pl.pallas_call(
        kernel,
        name="splitnn_bottom_int8_gather",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, bp, op), jnp.float32),
        interpret=interpret,
    )(jnp.asarray(idx, jnp.int32), xw, sx, wq, sw, b)
