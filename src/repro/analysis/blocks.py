"""Static Pallas BlockSpec VMEM-footprint estimates.

Each Pallas kernel's per-grid-step resident set is a pure function of
its BlockSpecs, which are themselves pure functions of the padded shapes
(``repro.kernels.padding``).  This module mirrors those layouts — the
same ``round_up``/block-shrink rules the ops wrappers apply — and sums
the resident block bytes, so the 16 MB VMEM budget (and the
``GATHER_VMEM_BUDGET`` fallback predicate the gather ops check at call
time) can be verified statically for any shape the engines run, instead
of being discovered as a Mosaic OOM on real hardware.

Estimates count one copy of every input/output block named in the
kernel's in_specs/out_specs (scalar-prefetch operands live in SMEM and
are excluded).  That single-copy sum is the HARD floor the ``ok`` flag
enforces: a kernel whose blocks don't fit even once cannot launch on
hardware.  Pipeline double-buffering of the *streamed* blocks adds up
to one extra copy of those (not of grid-invariant resident slabs); the
remaining headroom below 16 MB is the budget for it, which the
hardware-validation sweep (ROADMAP carry-over) measures for real.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List

from repro.kernels.padding import GATHER_VMEM_BUDGET, round_up
from repro.kernels.sorted_intersect.kernel import SINGLE_PASS_MAX_P

VMEM_BUDGET = 16 * 2 ** 20          # bytes of VMEM per TensorCore
F32 = 4
U32 = 4
I8 = 1


@dataclasses.dataclass
class BlockReport:
    kernel: str
    shape: str                       # human-readable shape key
    resident_bytes: int              # Σ block bytes resident per grid step
    budget: int                      # the budget this kernel is held to
    fallback: bool = False           # ops wrapper falls back before launch
    note: str = ""

    @property
    def ok(self) -> bool:
        # fallback shapes never launch the kernel; launched shapes must
        # fit at least one copy of every block
        return self.fallback or self.resident_bytes <= self.budget

    def as_row(self) -> Dict[str, object]:
        return {"kernel": self.kernel, "shape": self.shape,
                "resident_bytes": self.resident_bytes,
                "budget": self.budget, "fallback": self.fallback,
                "ok": self.ok, "note": self.note}


def splitnn_bottom_blocks(b: int, d: int, o: int, block_b: int = 512,
                          quant: str = None) -> BlockReport:
    """Dense slab pass: grid (M, B/bb); x (1,bb,dp) streams, w (1,dp,op)
    + bias (1,1,op) resident across batch tiles, out (1,bb,op).

    ``quant="int8"`` mirrors the i8 twin: x/w blocks shrink to 1 B per
    element and two f32 scale rows — sx (1,1,bb) streaming with the
    batch tile, sw (1,1,op) resident like the bias — join the set."""
    bb = min(block_b, round_up(b, 8))
    dp, op = round_up(d, 128), round_up(o, 128)
    if quant == "int8":
        resident = (I8 * (bb * dp + dp * op)
                    + F32 * (bb + 2 * op + bb * op))
    else:
        resident = F32 * (bb * dp + dp * op + op + bb * op)
    tag = "splitnn_bottom_int8" if quant == "int8" else "splitnn_bottom"
    return BlockReport(tag, f"B={b},d={d},o={o},bb={bb}",
                       resident, VMEM_BUDGET)


def splitnn_bottom_gather_blocks(n: int, d: int, o: int, b: int,
                                 block_b: int = 512,
                                 quant: str = None) -> BlockReport:
    """Gather-fused pass: the client's FULL (1,N,dp) slab is the
    resident block (rows gathered in-kernel by the prefetched idx), so
    the slab itself is held to ``GATHER_VMEM_BUDGET`` — past it the ops
    wrapper falls back to gather-then-dense before launching.

    ``quant="int8"`` mirrors the i8 gather twin: the resident slab is
    int8 (1 B/element — the same byte budget admits 4x the rows, the
    ops predicate scales ``elem`` accordingly) and the pre-gathered
    sx (1,1,bb) f32 scale tile streams with the batch block."""
    bb = min(block_b, round_up(b, 8))
    dp, op = round_up(d, 128), round_up(o, 128)
    if quant == "int8":
        slab = I8 * n * dp
        resident = slab + I8 * dp * op + F32 * (bb + 2 * op + bb * op)
        tag = "splitnn_bottom_int8_gather"
    else:
        slab = F32 * n * dp
        resident = slab + F32 * (dp * op + op + bb * op)
        tag = "splitnn_bottom_gather"
    return BlockReport(
        tag, f"N={n},d={d},o={o},B={b},bb={bb}",
        resident, VMEM_BUDGET, fallback=slab > GATHER_VMEM_BUDGET,
        note=f"slab={slab}B vs gather budget {GATHER_VMEM_BUDGET}B")


def kmeans_update_blocks(n: int, d: int, k: int,
                         block_n: int = 1024) -> BlockReport:
    """Fused Lloyd update: point tile (bn,dp) streams; all centroids
    (kp,dp) plus the (kp,dp) sums / (1,kp) counts accumulators resident
    across tiles; per-tile assign/sqd (bn,) outputs."""
    bn = min(block_n, round_up(n, 128))
    dp, kp = round_up(d, 128), round_up(k, 128)
    resident = F32 * (bn * dp + 2 * kp * dp + kp + 2 * bn)
    return BlockReport("kmeans_update", f"N={n},d={d},K={k},bn={bn}",
                       resident, VMEM_BUDGET)


def kmeans_update_gather_blocks(n: int, d: int, k: int, b: int,
                                block_n: int = 1024) -> BlockReport:
    """Gather-fused Lloyd update: the FULL (Np,dp) point slab resident
    (held to GATHER_VMEM_BUDGET, same fallback as the bottom kernel)."""
    bn = min(block_n, round_up(b, 128))
    np_, dp, kp = round_up(n, 128), round_up(d, 128), round_up(k, 128)
    slab = F32 * np_ * dp
    resident = slab + F32 * (2 * kp * dp + kp + 2 * bn)
    return BlockReport(
        "kmeans_update_gather", f"N={n},d={d},K={k},B={b},bn={bn}",
        resident, VMEM_BUDGET, fallback=slab > GATHER_VMEM_BUDGET,
        note=f"slab={slab}B vs gather budget {GATHER_VMEM_BUDGET}B")


def psi_prf_blocks(p: int, block_n: int = 2048) -> BlockReport:
    """Tag PRF: elementwise over (bn,) u32 id lanes, 2 in + 2 out."""
    bn = min(block_n, round_up(max(p, 1), 128))
    return BlockReport("psi_prf", f"P={p},bn={bn}", U32 * 4 * bn,
                       VMEM_BUDGET)


def sorted_intersect_blocks(p: int,
                            max_p: int = SINGLE_PASS_MAX_P) -> BlockReport:
    """Bitonic merge.  Unlike the other kernels, this count includes the
    pipeline's second buffer: the merge blocks are the whole lane pair
    (or a whole chunk of it), so the two copies the v5e compiler
    allocates are what decides the bound.  Single pass (P ≤
    SINGLE_PASS_MAX_P): 2 in + 2 out (2P/128, 128) u32 blocks, twice →
    64 bytes per per-side element, which fills the 16 MB budget exactly
    at 2^18 (the compiler refuses 2^19: "Scoped allocation with size
    32.00M and limit 16.00M").  Past it the ops wrapper routes to the
    multi-pass tiled merge, whose largest block is the local pass over
    ``chunk = 2·SINGLE_PASS_MAX_P`` elements — the same four lanes, so
    the same bound."""
    if p > max_p:
        chunk = min(2 * SINGLE_PASS_MAX_P, 2 * p)
        resident = 2 * U32 * 4 * chunk
        note = f"tiled multi-pass merge (chunk={chunk})"
    else:
        resident = 2 * U32 * 4 * 2 * p
        note = ""
    return BlockReport("sorted_intersect", f"P={p}", resident,
                       VMEM_BUDGET, note=note)


def vmem_report(shapes: Dict[str, Dict[str, int]] = None
                ) -> List[BlockReport]:
    """The default block-check matrix: every Pallas kernel at its
    engine-typical shapes plus the largest shape that must still fit
    (the gather kernels exactly AT the budget boundary, the merge at
    SINGLE_PASS_MAX_P)."""
    budget_rows = GATHER_VMEM_BUDGET // (F32 * 128)   # N at d_pad=128
    i8_rows = GATHER_VMEM_BUDGET // (I8 * 128)        # 4x the f32 reach
    reports = [
        splitnn_bottom_blocks(512, 128, 128),
        splitnn_bottom_blocks(4096, 512, 128),
        splitnn_bottom_blocks(4096, 512, 128, quant="int8"),
        splitnn_bottom_gather_blocks(budget_rows, 128, 128, 512),
        splitnn_bottom_gather_blocks(budget_rows + 1, 128, 128, 512),
        splitnn_bottom_gather_blocks(i8_rows, 128, 128, 512,
                                     quant="int8"),
        splitnn_bottom_gather_blocks(i8_rows + 1, 128, 128, 512,
                                     quant="int8"),
        kmeans_update_blocks(1 << 20, 16, 10),
        kmeans_update_gather_blocks(budget_rows, 16, 10, 1024),
        kmeans_update_gather_blocks(4 * budget_rows, 16, 10, 1024),
        psi_prf_blocks(1 << 20),
        sorted_intersect_blocks(SINGLE_PASS_MAX_P),   # largest 1-pass fit
        sorted_intersect_blocks(1 << 19),      # first tiled power of two
        sorted_intersect_blocks(1 << 21),      # tiled multi-pass route
    ]
    return reports
