"""jit'd public wrapper for the SSD scan Pallas kernel.

Framework layout x (B,S,H,P), dt (B,S,H), B/C (B,S,N) — pads S to a chunk
multiple (dt=0 padding is an exact no-op for the recurrence), reshapes to
the kernel's (B,H,nc,L,·) blocked layout, restores after.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.padding import interpret
from repro.kernels.ssd_scan.kernel import ssd_scan_pallas


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd_scan(x, dt, A, B, C, *, chunk: int = 128):
    """x (B,S,H,P) f32, dt (B,S,H) f32 (softplus'ed), A (H,) negative,
    B/C (B,S,N) f32 -> (y (B,S,H,P), final_state (B,H,P,N))."""
    bsz, s, h, p = x.shape
    n = B.shape[-1]
    s_orig = s
    if s % chunk:
        pad = chunk - s % chunk
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
        s += pad
    nc = s // chunk
    xk = x.astype(jnp.float32).reshape(bsz, nc, chunk, h, p)
    xk = xk.transpose(0, 3, 1, 2, 4)                     # (B,H,nc,L,P)
    dtk = dt.astype(jnp.float32).reshape(bsz, nc, chunk, h)
    dtk = dtk.transpose(0, 3, 1, 2)                      # (B,H,nc,L)
    bk = B.astype(jnp.float32).reshape(bsz, nc, chunk, n)
    ck = C.astype(jnp.float32).reshape(bsz, nc, chunk, n)
    y, fs = ssd_scan_pallas(xk, dtk, A.astype(jnp.float32), bk, ck,
                            chunk=chunk, interpret=interpret())
    y = y.transpose(0, 2, 3, 1, 4).reshape(bsz, s, h, p)[:, :s_orig]
    return y, fs
