"""Work of one sorted-merge intersection of two pre-sorted key lists.

Counted from logical sizes: ``n_keys`` is the real keys of both sides
together (n_a + n_b), never the padded power of two the kernel runs at,
and never the passes a merge schedule chooses.  The merge has to read
every key once (8 bytes: the (tag << 1 | side) key as two u32 lanes)
and write, per merged slot, the match flag and the receiver rank that
recover the id (4 + 4 bytes); it makes one comparison per key.  So a
single-pass merge at P = 2^17 and a tiled one at P = 2^19 are held to
the same work per key.
"""
from __future__ import annotations

from typing import Tuple

KEY_BYTES = 8          # (tag << 1 | side) as two u32 lanes
OUT_BYTES = 8          # match flag (i32) + receiver rank (i32) per slot


def count(n_keys: int) -> Tuple[int, int]:
    """(operations, bytes) for merging ``n_keys`` real keys."""
    n = int(n_keys)
    return n, n * (KEY_BYTES + OUT_BYTES)
