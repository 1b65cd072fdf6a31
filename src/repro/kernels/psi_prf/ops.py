"""jit'd public wrapper for the PSI tag PRF.

Seed-whitens the u32 id lanes (session key injection happens HERE, so
the kernel itself is constant and recompiles never depend on the seed),
pads N to the block size, dispatches to the Pallas kernel or the jnp
ref, and slices padding off.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels.padding import interpret, record_path, round_up
from repro.kernels.psi_prf import ref
from repro.kernels.psi_prf.kernel import prf_tags_pallas

BLOCK_N = 2048          # elementwise VPU tile


@functools.partial(jax.jit, static_argnames=("impl", "block_n"))
def prf_tags(id_hi: jnp.ndarray, id_lo: jnp.ndarray, seed: jnp.ndarray, *,
             impl: str = "pallas", block_n: int = BLOCK_N
             ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """id_hi/id_lo (N,) u32, seed (2,) u32 -> (tag_hi, tag_lo) (N,) u32
    with tag_hi < 2^30 (62-bit tags, so the packed sort key
    (tag << 1) | origin stays below the padding sentinels)."""
    n = id_hi.shape[0]
    hi = id_hi.astype(jnp.uint32) ^ seed[0]
    lo = id_lo.astype(jnp.uint32) ^ seed[1]
    if impl == "ref":
        return ref.prf_tags(hi, lo)
    bn = min(block_n, round_up(max(n, 1), 128))
    np_ = round_up(max(n, 1), bn)
    hi = jnp.zeros((np_,), jnp.uint32).at[:n].set(hi)
    lo = jnp.zeros((np_,), jnp.uint32).at[:n].set(lo)
    record_path("psi_prf", "pallas")
    th, tl = prf_tags_pallas(hi, lo, block_n=bn, interpret=interpret())
    return th[:n], tl[:n]
