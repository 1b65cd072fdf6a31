"""jit'd public wrapper for the sorted-intersect kernel.

Pads both sides to a common power-of-two length with their per-side
sentinels (appending max-sentinels to an ascending array preserves
sortedness) and dispatches to the Pallas kernel or jnp ref.  Also owns
the key packing: ``pack_keys`` folds a 62-bit tag and the origin bit
into the (kh, kl) u32 lane pair the merge sorts on (layout in ref.py).
Recovering plaintext ids from (sel, rank) is the engine's job.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.padding import interpret, record_path
from repro.kernels.sorted_intersect import ref
from repro.kernels.sorted_intersect.kernel import (SINGLE_PASS_MAX_P,
                                                   sorted_intersect_pallas,
                                                   sorted_intersect_tiled)
from repro.kernels.sorted_intersect.ref import PAD_A, PAD_B


def next_pow2(n: int, floor: int = 8) -> int:
    return max(1 << (max(n, 1) - 1).bit_length(), floor)


def pack_keys(tag_hi: jnp.ndarray, tag_lo: jnp.ndarray, origin: int
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(tag_hi < 2^30, tag_lo) u32 + origin bit -> (kh, kl) with
    key = (tag << 1) | origin, kh < 2^31."""
    kh = (tag_hi << 1) | (tag_lo >> 31)
    kl = (tag_lo << 1) | np.uint32(origin)
    return kh, kl


def _pad_side(kh, kl, pad, p):
    # appended constant tail, not a scatter into a filled array: XLA 0.9's
    # algebraic simplifier aborts on reverse(scatter) of the B side
    fill = p - kh.shape[0]
    if not fill:
        return kh, kl
    return (jnp.concatenate([kh, jnp.full((fill,), pad[0], jnp.uint32)]),
            jnp.concatenate([kl, jnp.full((fill,), pad[1], jnp.uint32)]))


@functools.partial(jax.jit, static_argnames=("impl",))
def sorted_intersect(a_kh: jnp.ndarray, a_kl: jnp.ndarray,
                     b_kh: jnp.ndarray, b_kl: jnp.ndarray, *,
                     impl: str = "pallas") -> Tuple[jnp.ndarray, ...]:
    """Receiver keys A (ascending, unique) / sender keys B (ascending,
    unique) as u32 lane pairs -> (sel (2P,) i32, rank (2P,) i32,
    merged_kh, merged_kl) with P = next_pow2(max(|A|, |B|))."""
    p = next_pow2(max(a_kh.shape[0], b_kh.shape[0]))
    a_kh, a_kl = _pad_side(a_kh, a_kl, PAD_A, p)
    b_kh, b_kl = _pad_side(b_kh, b_kl, PAD_B, p)
    if impl == "ref":
        return ref.sorted_intersect(a_kh, a_kl, b_kh, b_kl)
    # past the single-block VMEM bound the same merge network runs as a
    # multi-pass grid schedule (cross-stage passes + VMEM-resident chunk
    # finish) — bitwise-identical outputs, no jnp fallback
    if p > SINGLE_PASS_MAX_P:
        record_path("sorted_intersect", "tiled")
        return sorted_intersect_tiled(a_kh, a_kl, b_kh, b_kl,
                                      interpret=interpret())
    record_path("sorted_intersect", "single_pass")
    return sorted_intersect_pallas(a_kh, a_kl, b_kh, b_kl,
                                   interpret=interpret())
