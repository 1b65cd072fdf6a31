"""Pallas TPU kernels for the framework's compute hot-spots.

  kmeans_assign   — fused K-Means distance+argmin (final assign pass)
  kmeans_update   — fused Lloyd update: distance+argmin+per-cluster
                    sum/count accumulation in one pass, the point tile
                    resident in VMEM (Cluster-Coreset hot loop)
  psi_prf         — PSI tag PRF: Feistel multiply–xorshift rounds over
                    u64 id lanes as 2×u32 (OPRF tag evaluation)
  sorted_intersect— bitonic sort-merge intersection of two padded
                    sorted tag arrays (TPSI matching, DESIGN.md §6)
  splitnn_bottom  — fused block-diagonal VFL bottom layer: all M
                    clients' relu(x_m @ w_m + b_m) in one pass, weight
                    blocks VMEM-resident across batch tiles (§7)

Each subpackage: kernel.py (pl.pallas_call + BlockSpec), ops.py (jit'd
public wrapper, padding + layout), ref.py (pure-jnp oracle). The ops
wrappers run the kernels in interpret mode exactly when JAX's backend is
the CPU (``padding.interpret``, decided at trace time); on a TPU they
always compile with Mosaic.
"""
