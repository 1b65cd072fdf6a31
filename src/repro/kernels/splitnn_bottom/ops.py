"""Differentiable public wrapper for the fused SplitNN bottom layer.

``splitnn_bottom(x, w, b, relu, impl, block_b, idx=None, quant=None)``
pads via the shared kernel layout
(``repro.kernels.padding.pad_bottom_blocks``), dispatches to the Pallas
kernel (``impl="pallas"``) or the jnp oracle (``impl="ref"``) — in f32
or, with ``quant="int8"``, through the int8 kernel twins — and slices
padding off.

``idx`` enables the scalar-prefetch gather fusion (DESIGN.md §8): the
caller hands the FULL (M, N, d) slab plus a (B,) i32 index vector and
the per-step minibatch gather happens inside the pass — the ref oracle
gathers with ``jnp.take`` then runs the dense pass (the bitwise
contract), the Pallas impl prefetches the indices into the kernel
(``splitnn_bottom_gather_pallas``) so the gathered batch never makes a
separate HBM round trip.  Both produce bitwise-identical outputs, and
both route through the SAME backward, so fused/unfused gradients for
``w``/``b`` are bitwise-equal as well.

A ``jax.custom_vjp`` makes the Pallas forward differentiable —
pallas_call has no autodiff rule — and routes BOTH impls through the
same backward so gradients cannot diverge between them:

  dpre = g ⊙ 1[out > 0]      (ReLU mask; out > 0 ⟺ pre-activation > 0)
  dx   = dpre @ wᵀ           db = Σ_B dpre
  dw   = xᵀ @ dpre           (x = the gathered batch when idx is given;
                              dx then scatter-adds back into the slab)

all as (M,)-batched dot_generals — the backward is itself two
block-diagonal GEMMs of the same shape family as the forward, which XLA
fuses well; only the forward needs the VMEM-residency treatment (it is
the per-step hot path; the backward runs inside the same jit).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.padding import (GATHER_VMEM_BUDGET, interpret,
                                   pad_bottom_blocks,
                                   pad_bottom_blocks_gather, pad_gather_idx,
                                   record_path, round_up)
from repro.kernels.splitnn_bottom.kernel import (
    pack_int8_rows, splitnn_bottom_gather_pallas,
    splitnn_bottom_int8_gather_pallas, splitnn_bottom_int8_pallas,
    splitnn_bottom_pallas)
from repro.kernels.splitnn_bottom.ref import (splitnn_bottom_int8_ref,
                                              splitnn_bottom_ref)
from repro.quant import quantize_columns, quantize_rows


def _int8_operands(xp, wp):
    """Quantize the PADDED f32 operands (DESIGN.md §12).

    Padding first, quantizing second keeps the exact-zero invariants:
    zero pad rows/columns quantize to exponent 0 and value 0, and the
    zero padding never changes a row/column amax, so padded and
    unpadded slabs quantize each real element identically.  Exponents
    come back as f32 ``exp2`` scale vectors in the (M, 1, lanes) layout
    the kernels tile like the bias block.
    """
    xq, ex = quantize_rows(xp, "int8")            # (M, Bp, dp) i8, (M, Bp)
    wq, ew = quantize_columns(wp, "int8")         # (M, dp, op) i8, (M, op)
    sx = jnp.exp2(ex.astype(jnp.float32))[:, None, :]        # (M, 1, Bp)
    sw = jnp.exp2(ew.astype(jnp.float32))[:, None, :]        # (M, 1, op)
    return xq, sx, wq, sw


def _dense_forward(x, w, b, relu, impl, block_b, quant=None):
    m, n, d = x.shape
    o = w.shape[2]
    xp, wp, bp, bb = pad_bottom_blocks(x, w, b, block_b)
    if quant == "int8":
        xq, sx, wq, sw = _int8_operands(xp, wp)
        if impl == "pallas":
            record_path("splitnn_bottom", "int8_dense")
            out = splitnn_bottom_int8_pallas(xq, sx, wq, sw, bp, relu=relu,
                                             block_b=bb,
                                             interpret=interpret())
        else:
            out = splitnn_bottom_int8_ref(xq, sx, wq, sw, bp, relu=relu)
        return out[:, :n, :o]
    if impl == "pallas":
        record_path("splitnn_bottom", "dense")
        out = splitnn_bottom_pallas(xp, wp, bp, relu=relu, block_b=bb,
                                    interpret=interpret())
    else:
        out = splitnn_bottom_ref(xp, wp, bp, relu=relu)
    return out[:, :n, :o]


def take_rows(x: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """``x[:, idx, :]`` for an (M, N, d) slab, as one gather of whole
    rows of its (M·N, d) view.  ``jnp.take(x, idx, axis=1)`` gathers
    (M, 1, d) slices, which are not contiguous, so XLA on a TPU
    relayouts the whole slab to make them so: a copy of every row per
    call, where this reads only the B·M gathered ones."""
    m, n, d = x.shape
    rows = (jnp.arange(m, dtype=idx.dtype)[:, None] * n + idx).reshape(-1)
    return jnp.take(x.reshape(m * n, d), rows, axis=0).reshape(
        m, idx.shape[0], d)


def gather_path(impl: str, n_rows: int, d: int, quant=None) -> str:
    """The path ``splitnn_bottom(idx=...)`` takes over an (M, n_rows, d)
    slab, under the name ``record_path`` counts it by: "gather_fused"
    while one client's lane-padded slab fits ``GATHER_VMEM_BUDGET``
    (always in interpret mode), "gather_fallback" (gather, then the
    dense pass) past it, each prefixed "int8_" for the int8 twins; "ref"
    for the jnp oracle, which always gathers first."""
    if impl != "pallas":
        return "ref"
    kq = quant == "int8"
    elem = 1 if kq else 4         # int8 slab: 4x the VMEM reach
    fits = (interpret()
            or n_rows * round_up(d, 128) * elem <= GATHER_VMEM_BUDGET)
    return ("int8_gather" if kq else "gather") + (
        "_fused" if fits else "_fallback")


def _forward(x, w, b, relu, impl, block_b, idx=None, quant=None):
    if quant not in (None, "int8", "fp8"):
        raise ValueError(f"splitnn_bottom: unknown quant={quant!r}")
    # fp8 is a COMM-ONLY wire dtype (DESIGN.md §12): the MXU's native
    # narrow GEMM path is int8, so quant="fp8" keeps the f32 bottom GEMM
    # and only the activation all_gather narrows.
    kq = "int8" if quant == "int8" else None
    if idx is None:
        return _dense_forward(x, w, b, relu, impl, block_b, kq)
    o = w.shape[2]
    if impl == "pallas":
        path = gather_path(impl, x.shape[1], x.shape[2], kq)
        if path.endswith("_fused"):
            record_path("splitnn_bottom", path)
            idx_p, bb, bsz = pad_gather_idx(idx, block_b)
            xp, wp, bp = pad_bottom_blocks_gather(x, w, b)
            if kq:
                xq, sx, wq, sw = _int8_operands(xp, wp)
                # per-row scales commute with the row gather: gather the
                # tiny (M, Np) scale vector outside, fuse only the wide
                # slab gather into the kernel
                sxg = jnp.take(sx, idx_p, axis=2)
                out = splitnn_bottom_int8_gather_pallas(
                    idx_p, pack_int8_rows(xq), sxg, wq, sw, bp, relu=relu,
                    block_b=bb, interpret=interpret())
            else:
                out = splitnn_bottom_gather_pallas(idx_p, xp, wp, bp,
                                                   relu=relu, block_b=bb,
                                                   interpret=interpret())
            return out[:, :bsz, :o]
        record_path("splitnn_bottom", path)
    # ref oracle (and the past-VMEM-budget fallback): gather, then the
    # dense pass — the bitwise contract the fused kernel must match
    # (per-row int8 scales make quantize-then-gather == gather-then-
    # quantize, row by row)
    return _dense_forward(take_rows(x, idx), w, b, relu, impl, block_b,
                          kq)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 7))
def splitnn_bottom(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray,
                   relu: bool = True, impl: str = "ref",
                   block_b: int = 512, idx=None,
                   quant=None) -> jnp.ndarray:
    """x (M, B, d), w (M, d, o), b (M, o) -> (M, B, o) f32: all M clients'
    bottom activations ``relu?(x[m] @ w[m] + b[m])`` in one fused pass.

    With ``idx`` (B,) i32, ``x`` is the full (M, N, d) slab and the
    minibatch gather ``x[:, idx, :]`` fuses into the pass (scalar
    prefetch on the Pallas impl); the result is (M, B, o) for the
    gathered rows, bitwise-equal to gathering first.

    ``quant="int8"`` routes the GEMM through the i8 x i8 -> i32 kernel
    variants with per-row/per-column pow2 scales and an f32 epilogue
    (``quant="fp8"`` is comm-only and leaves the GEMM in f32).  The
    backward is the SAME f32 straight-through pass for every quant mode
    (see ``_bwd``).
    """
    return _forward(x, w, b, relu, impl, block_b, idx, quant)


def _fwd(x, w, b, relu, impl, block_b, idx, quant):
    out = _forward(x, w, b, relu, impl, block_b, idx, quant)
    return out, (x, w, out, idx)


def _bwd(relu, impl, block_b, quant, res, g):
    # Straight-through backward (DESIGN.md §12): residuals are the f32
    # operands, so quantized forwards train with the f32 gradient (the
    # ReLU mask still comes from the ACTUAL quantized forward's output,
    # keeping the mask consistent with what the forward computed).
    del quant
    x, w, out, idx = res
    dpre = g * (out > 0) if relu else g                       # (M, B, o)
    xg = x if idx is None else take_rows(x, idx)              # (M, B, d)
    xg = xg[..., :w.shape[1]]     # drop pre-padded zero columns (if any)
    dx = jax.lax.dot_general(                                 # (M, B, d)
        dpre, w, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)
    dw = jax.lax.dot_general(                                 # (M, d, o)
        xg, dpre, (((1,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)
    db = jnp.sum(dpre, axis=1)                                # (M, o)
    if idx is None:
        return dx, dw, db, None
    # slab cotangent: scatter the gathered-row grads back (duplicate
    # schedule slots accumulate; the slab may be pre-padded wider than
    # w — the extra zero columns get zero cotangent).  DCE removes the
    # scatter when x is data
    dx_full = jnp.zeros_like(x).at[:, idx, :dx.shape[-1]].add(dx)
    return dx_full, dw, db, None


splitnn_bottom.defvjp(_fwd, _bwd)
