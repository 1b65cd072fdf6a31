"""Pallas TPU kernel: sort-merge intersection of two padded sorted
63-bit key arrays (bitonic merge network).

The two u32 key lanes of the bitonic sequence C = [A, reverse(B)]
(built in XLA by ``ref.bitonic_lanes``) are laid out as (rows, 128)
tiles, element i at (i // 128, i % 128), so every block is Mosaic's
native (8, 128) u32 tiling.  A compare-exchange stage of stride s pairs
element i with i ^ s, and the layout turns each stride into one of
three moves:

  s < 128          lane partner: ``pltpu.roll`` along lanes by ±s and a
                   lane-iota mask picks which roll is the partner;
  128 ≤ s < 128·SB row partner inside a sub-block of SB rows: the same
                   with sublane rolls by ±s/128 rows;
  s ≥ 128·SB       row-block partner: two aligned (SB, 128) sub-blocks
                   at row distance s/128 exchange as a pair.

The lower slot keeps the lexicographic min of (kh, kl), the upper the
max.  The network is a sorting network, so ANY schedule of its stages
is bitwise equal to the jnp oracle (``ref.merge_network``); selection
and rank recovery (``ref.select_rank``: predecessor compare + cumsum)
stream over the merged lanes in XLA outside the kernels.

Single pass (P ≤ SINGLE_PASS_MAX_P): one grid step holds the whole
(2P/128, 128) lane pair in VMEM and runs all log2(2P) stages there, in
fori_loops over sub-blocks so the kernel body stays small.  Past that
bound ``sorted_intersect_tiled`` splits the same stages across
dispatches (DESIGN.md §5):

  cross passes   strides whose 2s-blocks exceed a chunk: one grid
                 kernel per stage; each grid step loads one (x, y)
                 (tile, 128) pair at row distance s/128, exchanges it,
                 writes it back (input/output aliased);
  local pass     the remaining strides: one grid kernel over contiguous
                 (chunk, 128) blocks, each running the single-pass body
                 at chunk scale.

VMEM bounds, as the v5e compiler admits them under its default 16 MiB
scoped VMEM: a gridded pass double-buffers 2 input and 2 output
(chunk/128, 128) u32 blocks — 64 B per element of the per-side span —
so a span of 2^18 (16 MiB) compiles and 2^19 is refused ("Scoped
allocation with size 32.00M and limit 16.00M").  One bound,
SINGLE_PASS_MAX_P = 2^18, therefore caps both the single pass (the
engine vmaps it over pairs, which gives it a grid and the same double
buffers) and the tiled local chunk, which runs the single-pass body;
``tests/test_tpu_compile.py`` compiles both paths for v5e, and
``analysis/blocks.py`` counts the same 64 B.

Padding contract (ops.py): P is a power of two; A pads with PAD_A,
B with PAD_B — distinct sentinels with the top bit set, so pads sort
last and can never count as matches (real keys are 63-bit).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.sorted_intersect import ref

LANES = 128
MIN_ROWS = 8                  # one (8, 128) u32 tile: the smallest block
MIN_LENGTH = MIN_ROWS * LANES
SUB_ROWS = 64                 # rows per in-kernel sub-block (8 vregs/lane)

SINGLE_PASS_MAX_P = 1 << 18   # per-side span of one merge pass in VMEM
CROSS_TILE_ROWS = 512         # rows per cross-pass tile (256 KiB/lane)


def _exchange(xh, xl, yh, yl):
    """Pairwise (x, y) -> (min, max) on the (kh, kl) lane pair."""
    swap = ref.lex_gt(xh, xl, yh, yl)
    return (jnp.where(swap, yh, xh), jnp.where(swap, yl, xl),
            jnp.where(swap, xh, yh), jnp.where(swap, xl, yl))


def _in_block_stages(kh, kl, s_top: int):
    """All strides s_top, s_top/2, ..., 1 on one (rows, 128) value whose
    2·s_top span fits in it: the partner comes from a roll (lanes for
    s < 128, sublanes above), the iota mask says which roll."""
    rows = kh.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, kh.shape, 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, kh.shape, 1)
    s = s_top
    while s >= 1:
        if s >= LANES:
            axis, size, dist, pos = 0, rows, s // LANES, row
        else:
            axis, size, dist, pos = 1, LANES, s, lane
        lower = (pos & dist) == 0
        up = lambda v: pltpu.roll(v, size - dist, axis)    # v[i + dist]
        down = lambda v: pltpu.roll(v, dist, axis)         # v[i - dist]
        ph = jnp.where(lower, up(kh), down(kh))
        pl_ = jnp.where(lower, up(kl), down(kl))
        # lower slot takes the partner iff it is smaller, upper iff not
        take = ref.lex_gt(kh, kl, ph, pl_) == lower
        kh = jnp.where(take, ph, kh)
        kl = jnp.where(take, pl_, kl)
        s //= 2
    return kh, kl


def _merge_block(rows: int, s_top: int, kh_ref, kl_ref, okh_ref, okl_ref):
    """Run strides s_top..1 over one (rows, 128) VMEM block, in place in
    the output refs: row-block exchanges for strides ≥ 128·SB, then one
    sweep of SB-row sub-blocks for everything below."""
    sb = min(SUB_ROWS, rows)
    n_sub = rows // sb

    def copy(q, c):
        r = pl.multiple_of(q * sb, sb)
        okh_ref[pl.ds(r, sb), :] = kh_ref[pl.ds(r, sb), :]
        okl_ref[pl.ds(r, sb), :] = kl_ref[pl.ds(r, sb), :]
        return c
    jax.lax.fori_loop(0, n_sub, copy, 0)

    s = s_top
    while s >= sb * LANES:
        t = s // LANES                       # row distance, multiple of sb

        def pair(q, c, t=t):
            u = q * sb
            x = pl.multiple_of(u + (u & ~(t - 1)), sb)   # block·2t + offset
            y = pl.multiple_of(x + t, sb)
            xh, xl, yh, yl = _exchange(
                okh_ref[pl.ds(x, sb), :], okl_ref[pl.ds(x, sb), :],
                okh_ref[pl.ds(y, sb), :], okl_ref[pl.ds(y, sb), :])
            okh_ref[pl.ds(x, sb), :] = xh
            okl_ref[pl.ds(x, sb), :] = xl
            okh_ref[pl.ds(y, sb), :] = yh
            okl_ref[pl.ds(y, sb), :] = yl
            return c
        jax.lax.fori_loop(0, n_sub // 2, pair, 0)
        s //= 2

    def local(q, c):
        r = pl.multiple_of(q * sb, sb)
        kh, kl = _in_block_stages(okh_ref[pl.ds(r, sb), :],
                                  okl_ref[pl.ds(r, sb), :], s)
        okh_ref[pl.ds(r, sb), :] = kh
        okl_ref[pl.ds(r, sb), :] = kl
        return c
    if s >= 1:
        jax.lax.fori_loop(0, n_sub, local, 0)


def _merge_pass(kh, kl, chunk_rows: int, s_top: int, interpret: bool):
    """(R, 128) lane pair -> the same with strides s_top..1 applied
    inside every (chunk_rows, 128) block, one block per grid step."""
    rows = kh.shape[0]
    spec = pl.BlockSpec((chunk_rows, LANES), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_merge_block, chunk_rows, s_top),
        name="sorted_intersect_merge",
        grid=(rows // chunk_rows,),
        in_specs=[spec, spec],
        out_specs=[spec, spec],
        out_shape=[jax.ShapeDtypeStruct(kh.shape, jnp.uint32)] * 2,
        input_output_aliases={0: 0, 1: 1},
        interpret=interpret,
    )(kh, kl)


def _cross_stage_kernel(kh_ref, kl_ref, okh_ref, okl_ref):
    """One compare-exchange stage tile: block (1, 2, T, 128) holds the x
    tile (dim-1 index 0) and its partner y tile at row distance t."""
    out = _exchange(kh_ref[0, 0], kl_ref[0, 0], kh_ref[0, 1], kl_ref[0, 1])
    okh_ref[0, 0], okl_ref[0, 0], okh_ref[0, 1], okl_ref[0, 1] = out


def _cross_stage(kh, kl, s: int, tile_rows: int, interpret: bool):
    """Stride-s stage over (R, 128) lanes as a grid pass: reshaping to
    (R/2t, 2, t, 128), t = s/128 rows, puts every (c[i], c[i+s]) pair at
    dim-1 indices (0, 1), so a (1, 2, T, 128) block is a self-contained
    tile pair and the grid streams t/T tiles per 2t-row block."""
    rows = kh.shape[0]
    t = s // LANES
    r = rows // (2 * t)
    tt = min(t, tile_rows)
    spec = pl.BlockSpec((1, 2, tt, LANES), lambda i, j: (i, 0, j, 0))
    okh, okl = pl.pallas_call(
        _cross_stage_kernel,
        name="sorted_intersect_cross",
        grid=(r, t // tt),
        in_specs=[spec, spec],
        out_specs=[spec, spec],
        out_shape=[jax.ShapeDtypeStruct((r, 2, t, LANES), jnp.uint32)] * 2,
        input_output_aliases={0: 0, 1: 1},
        interpret=interpret,
    )(kh.reshape(r, 2, t, LANES), kl.reshape(r, 2, t, LANES))
    return okh.reshape(rows, LANES), okl.reshape(rows, LANES)


def _layout(a_kh, a_kl, b_kh, b_kl):
    """Padded (P,) sides -> the (R, 128) bitonic lane pair (at least one
    (8, 128) tile) and the merged length 2P to keep."""
    p = a_kh.shape[0]
    assert p & (p - 1) == 0, p
    length = max(2 * p, MIN_LENGTH)
    kh, kl = ref.bitonic_lanes(a_kh, a_kl, b_kh, b_kl, length)
    return kh.reshape(-1, LANES), kl.reshape(-1, LANES), 2 * p


def _finish(kh, kl, two_p: int):
    return kh.reshape(-1)[:two_p], kl.reshape(-1)[:two_p]


def merge_pallas(a_kh, a_kl, b_kh, b_kl, *, interpret: bool):
    """Single-pass merge.  Inputs (P,) u32, P a power of two, per-side
    sorted+padded -> merged (kh, kl) lanes, each (2P,) u32."""
    kh, kl, two_p = _layout(a_kh, a_kl, b_kh, b_kl)
    kh, kl = _merge_pass(kh, kl, kh.shape[0], kh.size // 2, interpret)
    return _finish(kh, kl, two_p)


def merge_tiled(a_kh, a_kl, b_kh, b_kl, *, interpret: bool,
                chunk_p: int = SINGLE_PASS_MAX_P,
                tile: int = CROSS_TILE_ROWS * LANES):
    """Multi-pass merge for P past the single-block bound, same
    signature/outputs as ``merge_pallas``; ``chunk_p`` caps the local
    pass's VMEM residency at 2·chunk_p elements per lane and ``tile``
    the per-step footprint (elements) of the cross passes — both
    multiples of one (8, 128) tile (tests shrink them to exercise the
    multi-pass structure at small P)."""
    kh, kl, two_p = _layout(a_kh, a_kl, b_kh, b_kl)
    length = kh.size
    chunk = min(2 * chunk_p, length)
    assert chunk % MIN_LENGTH == 0 and tile % MIN_LENGTH == 0, (chunk, tile)
    s = length // 2
    while 2 * s > chunk:          # stages whose 2s-blocks exceed a chunk
        kh, kl = _cross_stage(kh, kl, s, tile // LANES, interpret)
        s //= 2
    kh, kl = _merge_pass(kh, kl, chunk // LANES, s, interpret)
    return _finish(kh, kl, two_p)


def sorted_intersect_pallas(a_kh, a_kl, b_kh, b_kl, *, interpret: bool):
    """(P,) sides -> (sel (2P,) i32, rank (2P,) i32, merged_kh,
    merged_kl) through the single-pass merge."""
    kh, kl = merge_pallas(a_kh, a_kl, b_kh, b_kl, interpret=interpret)
    return (*ref.select_rank(kh, kl), kh, kl)


def sorted_intersect_tiled(a_kh, a_kl, b_kh, b_kl, *, interpret: bool,
                           **split):
    """``sorted_intersect_pallas`` through the multi-pass merge
    (``split``: ``chunk_p``/``tile`` of ``merge_tiled``)."""
    kh, kl = merge_tiled(a_kh, a_kl, b_kh, b_kl, interpret=interpret,
                         **split)
    return (*ref.select_rank(kh, kl), kh, kl)
