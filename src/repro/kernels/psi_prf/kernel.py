"""Pallas TPU kernel: vectorized PSI tag PRF over u64 id lanes.

Elementwise VPU work: each grid step loads a (BN/128, 128) tile of the
hi/lo u32 id lanes into VMEM and runs the 5-round Feistel / multiply–xorshift
network entirely in VREGs — one HBM read and one write per lane for the
whole tag evaluation, where the host OPRF path paid a Python + sha256
round trip per element.

The round network IS the jnp ref — ``ref.prf_tags`` is pure value math
on u32 lanes (its constants are numpy scalars, which fold into the
kernel as literals; jnp scalars would be captured tracers, which
pallas_call rejects), so the kernel body invokes it on the VMEM tile
and the two implementations cannot drift.  What the pallas_call adds is
the tiled VMEM residency that Mosaic compiles on real TPU
(parity-tested in interpret mode).

Padding contract (enforced by ops.py): N % block_n == 0.  Padded lanes
produce garbage tags that the wrapper slices off — the PRF has no
cross-lane data flow, so padding cannot perturb real lanes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.psi_prf import ref


def _prf_kernel(hi_ref, lo_ref, tag_hi_ref, tag_lo_ref):
    tag_hi_ref[...], tag_lo_ref[...] = ref.prf_tags(hi_ref[...],
                                                    lo_ref[...])


def prf_tags_pallas(hi: jnp.ndarray, lo: jnp.ndarray, *, block_n: int,
                    interpret: bool):
    """hi/lo (N,) u32 (seed-whitened, padded) -> (tag_hi, tag_lo) (N,) u32.

    N % block_n == 0 and block_n % 128 == 0.  The lanes run as
    (N/128, 128) rows so every block sits on the (8, 128) u32 tiling —
    also when the engine vmaps the call over PSI pairs — which needs
    block_n/128 a multiple of 8 unless one block covers N.  Caller
    slices off padding.
    """
    n = hi.shape[0]
    rows = block_n // 128
    assert n % block_n == 0 and block_n % 128 == 0, (n, block_n)
    assert rows % 8 == 0 or block_n == n, (n, block_n)
    spec = pl.BlockSpec((rows, 128), lambda i: (i, 0))
    th, tl = pl.pallas_call(
        _prf_kernel,
        name="psi_prf",
        grid=(n // block_n,),
        in_specs=[spec] * 2,
        out_specs=[spec] * 2,
        out_shape=[jax.ShapeDtypeStruct((n // 128, 128), jnp.uint32)] * 2,
        interpret=interpret,
    )(hi.reshape(-1, 128), lo.reshape(-1, 128))
    return th.reshape(n), tl.reshape(n)
