"""Pure-jnp oracle for the sorted-intersect (bitonic sort-merge) step.

Key layout — one 63-bit integer per element, split into u32 lanes:

    key = (tag << 1) | origin        tag < 2^62,  origin: 0=sender 1=receiver
    kh  = key >> 32   (< 2^31 for real elements)
    kl  = key & 0xFFFFFFFF

Packing the origin into bit 0 keeps the merge TWO lanes wide (the u32
pair) instead of dragging payload/origin lanes through every
compare-exchange stage: equal tags sort sender-immediately-before-
receiver, so a receiver element is matched iff its predecessor is the
same tag with origin 0 — i.e. ``key[i] == key[i-1] + 1`` with bit 0
set.  The receiver's plaintext id is NOT carried through the merge;
instead ``rank[i] = cumsum(origin)`` counts receiver elements in merged
order, which (receiver pads sort last) indexes the receiver's
tag-sorted id array directly: id of a selected slot = r_ids_by_tag[
rank-1].  The engine does that gather outside the kernel.

Inputs are two PADDED SORTED key arrays of equal power-of-two length P,
ascending; each side pads its tail with its own sentinel (top bit set,
so pads sort last, never satisfy the validity check, and — the
sentinels differing — never form a cross-side match).

Precondition: tags are UNIQUE within each side (the engine dedups ids
before tagging; the PRF is a bijection pre-mask).  Then every equal-tag
run is one sender followed by one receiver, and predecessor-equality is
exactly set intersection.

Algorithm: C = [A, reverse(B)] is a bitonic sequence of length 2P, so
one bitonic MERGE network (log2(2P) vectorized compare-exchange stages,
each a reshape + lexicographic min/max on the u32 lane pair) sorts it.
The network is a sorting network, so its output is THE sorted sequence:
any schedule of the same stages (the Pallas kernels' 2-D layouts) is
bitwise equal to this oracle.  ``bitonic_lanes`` (build C) and
``select_rank`` (sel/rank recovery) are plain XLA shared by every impl;
only the network itself differs between the oracle and the kernels.
"""
from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp
import numpy as np

_u32 = np.uint32

# per-side padding sentinels: top bit set → after all real (63-bit) keys
PAD_A = (0xFFFFFFFF, 0xFFFFFFFF)      # receiver-side pad key (kh, kl)
PAD_B = (0xFFFFFFFF, 0xFFFFFFFE)      # sender-side pad key
VALID_LIMIT = 0x80000000              # real keys have kh < 2^31


def lex_gt(xh, xl, yh, yl):
    """x > y on the (kh, kl) lane pair, lexicographically."""
    return (xh > yh) | ((xh == yh) & (xl > yl))


def bitonic_lanes(a_kh, a_kl, b_kh, b_kl, length: int = 0
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(P,) ascending A and B lanes -> the bitonic C = [A, reverse(B)].

    ``length`` > 2P widens C with PAD_A (the largest key) between the
    halves — still bitonic, and every extra element sorts to the tail,
    so the first 2P merged slots are exactly the 2P merge's (how the
    kernels reach their minimum (8, 128) tile at small P)."""
    p = a_kh.shape[0]
    fill = max(length - 2 * p, 0)
    out = []
    for a, b, pad in ((a_kh, b_kh, PAD_A[0]), (a_kl, b_kl, PAD_A[1])):
        parts = [a] + ([jnp.full((fill,), pad, jnp.uint32)] if fill else [])
        out.append(jnp.concatenate(parts + [jnp.flip(b)]))
    return out[0], out[1]


def merge_network(kh: jnp.ndarray, kl: jnp.ndarray
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Bitonic merge of a (L,) bitonic lane pair, L a power of two: stage
    s compare-exchanges c[i] with c[i+s] inside every 2s block, the
    smaller key to the lower slot.  Each stage is written as two
    lexicographic min/max selects on (L/2s, s) halves."""
    length = kh.shape[0]
    s = length // 2
    while s >= 1:
        h = kh.reshape(-1, 2, s)
        l = kl.reshape(-1, 2, s)
        xh, yh, xl, yl = h[:, 0], h[:, 1], l[:, 0], l[:, 1]
        swap = lex_gt(xh, xl, yh, yl)
        kh = jnp.concatenate([jnp.where(swap, yh, xh)[:, None],
                              jnp.where(swap, xh, yh)[:, None]],
                             axis=1).reshape(length)
        kl = jnp.concatenate([jnp.where(swap, yl, xl)[:, None],
                              jnp.where(swap, xl, yl)[:, None]],
                             axis=1).reshape(length)
        s //= 2
    return kh, kl


def select_rank(kh: jnp.ndarray, kl: jnp.ndarray
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Merged lanes -> (sel i32, rank i32): ``sel`` marks merged slots
    holding a matched RECEIVER element; ``rank`` is the 1-based count of
    receiver-origin slots up to and including each position."""
    origin = (kl & _u32(1)).astype(jnp.int32)
    rank = jnp.cumsum(origin)
    # receiver slot matched ⇔ predecessor is the same tag from the sender
    # side: key equality up to the origin bit, with sender (even) first
    prev_match = (kh[1:] == kh[:-1]) & (kl[1:] == kl[:-1] + _u32(1))
    sel = (jnp.concatenate([jnp.zeros((1,), bool), prev_match])
           & (origin == 1) & (kh < _u32(VALID_LIMIT)))
    return sel.astype(jnp.int32), rank


def sorted_intersect(a_kh: jnp.ndarray, a_kl: jnp.ndarray,
                     b_kh: jnp.ndarray, b_kl: jnp.ndarray
                     ) -> Tuple[jnp.ndarray, ...]:
    """Receiver keys A / sender keys B, each (P,) u32 lane pairs with P a
    power of two, ascending -> (sel (2P,) i32, rank (2P,) i32,
    merged_kh, merged_kl)."""
    kh, kl = merge_network(*bitonic_lanes(a_kh, a_kl, b_kh, b_kl))
    return (*select_rank(kh, kl), kh, kl)
