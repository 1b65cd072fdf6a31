"""Pallas TPU kernel: Mamba2 SSD chunked scan.

TPU-native adaptation of the SSD block decomposition (arXiv:2405.21060):
the GPU kernel leans on warp-level scans; on TPU we exploit the fact that
the Pallas GRID IS SEQUENTIAL over its minor axis — the recurrent
inter-chunk state (P×N per head) lives in VMEM scratch and is carried
across chunk-grid steps, so the entire layer runs in ONE kernel launch:

  grid = (B, H, num_chunks)    # chunks iterate sequentially per (b,h)
  per step, all in VMEM/VREGs:
    intra-chunk:  (C·Bᵀ ∘ decay) · (dt·x)      — two (L,·)×(·,·) MXU calls
    state feed:   y += (C·state_prevᵀ) ∘ exp(cum)
    state update: state = exp(ΣdA)·state + Σ decay_to_end·(dt·x)⊗B

L=chunk and N=state_dim are 128-multiples (MXU aligned); P=64 rides the
lane padding. HBM traffic is exactly one read of x/dt/B/C and one write of
y — the jnp oracle materializes (B,nc,L,L,H) decay tensors in HBM instead.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(nc: int, x_ref, dt_ref, a_ref, b_ref, c_ref,
                y_ref, fs_ref, state_scr):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[0, 0, 0]       # (L, P)
    dt = dt_ref[0, 0, 0]     # (L,)
    a = a_ref[0]             # scalar A_h (negative)
    b = b_ref[0, 0]          # (L, N)
    c = c_ref[0, 0]          # (L, N)

    da = dt * a                                   # (L,)
    cum = jnp.cumsum(da)                          # (L,)
    xdt = x * dt[:, None]                         # (L, P)

    # --- intra-chunk: (C Bᵀ ∘ tril-decay) · xdt
    cb = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (L,L)
    l = cum.shape[0]
    ri = jax.lax.broadcasted_iota(jnp.int32, (l, l), 0)
    cj = jax.lax.broadcasted_iota(jnp.int32, (l, l), 1)
    diff = cum[:, None] - cum[None, :]
    decay = jnp.where(ri >= cj, jnp.exp(diff), 0.0)               # (L,L)
    y = jax.lax.dot_general(cb * decay, xdt, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)   # (L,P)

    # --- inter-chunk feed from carried state
    state = state_scr[...]                                        # (P,N)
    feed = jax.lax.dot_general(c, state, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)  # (L,P)
    y = y + feed * jnp.exp(cum)[:, None]
    y_ref[0, 0, 0] = y

    # --- state update: exp(Σda)·state + Σ_l decay_to_end_l · xdt_l ⊗ b_l
    total = cum[l - 1]
    decay_to_end = jnp.exp(total - cum)                           # (L,)
    contrib = jax.lax.dot_general(
        xdt * decay_to_end[:, None], b, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                       # (P,N)
    new_state = jnp.exp(total) * state + contrib
    state_scr[...] = new_state

    @pl.when(ci == nc - 1)
    def _emit_state():
        fs_ref[0, 0] = new_state


def ssd_scan_pallas(x, dt, A, B, C, *, chunk: int, interpret: bool):
    """x (B,H,nc,L,P), dt (B,H,nc,L), A (H,), B/C (B,nc,L,N) — all f32,
    L == chunk. Returns (y (B,H,nc,L,P), final_state (B,H,P,N))."""
    bsz, h, nc, l, p = x.shape
    n = B.shape[-1]
    assert l == chunk
    grid = (bsz, h, nc)
    kernel = functools.partial(_ssd_kernel, nc)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 1, l, p), lambda b, hh, c: (b, hh, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, l), lambda b, hh, c: (b, hh, c, 0)),
            pl.BlockSpec((1,), lambda b, hh, c: (hh,)),
            pl.BlockSpec((1, 1, l, n), lambda b, hh, c: (b, c, 0, 0)),
            pl.BlockSpec((1, 1, l, n), lambda b, hh, c: (b, c, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, l, p), lambda b, hh, c: (b, hh, c, 0, 0)),
            pl.BlockSpec((1, 1, p, n), lambda b, hh, c: (b, hh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, h, nc, l, p), jnp.float32),
            jax.ShapeDtypeStruct((bsz, h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
    )(x, dt, A, B, C)
