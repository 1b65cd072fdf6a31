"""Pallas TPU flash attention (online softmax), GQA-native.

TPU adaptation of the FlashAttention blocking (the paper's SplitNN LLM
training/serving hot-spot):
  · grid (batch·kv_head, q_blocks, k_blocks); the k axis is the MINOR
    sequential grid dim, so the (m, l, acc) running softmax state lives in
    VMEM scratch across k steps — no HBM round-trips,
  · the q tile keeps all G=H/KV query heads of one kv head together:
    the (G·BQ, D)×(D, BK) score matmul feeds the MXU with the contraction
    on D (multiple of 128 after ops.py padding),
  · causal/sliding-window/prefix masking is computed from block-relative
    iotas; fully-masked k blocks are skipped via ``pl.when`` (block-level
    early-out ≈ the CUDA kernel's tile skipping),
  · gemma2-style tanh softcapping is fused on the score tile in VREGs.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _fa_kernel(causal: bool, window: int, prefix: int, logit_cap: float,
               scale: float, bq: int, bk: int, sq: int, sk: int,
               q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # absolute positions (suffix-aligned: q row r ↔ position sk - sq + ...)
    q_start = sk - sq + qi * bq
    k_start = ki * bk

    # block-level visibility: skip k blocks fully outside the mask
    # (program ids are traced scalars — use jnp logical ops, not python)
    run = jnp.bool_(True)
    if causal:
        run = run & (k_start <= q_start + bq - 1)
    if window > 0:
        # fully invisible iff even the closest (q,k) pair — oldest q row vs
        # youngest k col — is >= window apart, and no prefix overlap
        blk_visible = (q_start - (k_start + bk - 1)) < window
        blk_visible = blk_visible | (k_start < prefix)
        run = run & blk_visible

    @pl.when(run)
    def _compute():
        q = q_ref[0]          # (G, BQ, D)
        k = k_ref[0]          # (BK, D)
        v = v_ref[0]          # (BK, D)
        g, _, d = q.shape
        qf = q.reshape(g * bq, d)
        s = jax.lax.dot_general(qf, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * scale
        if logit_cap:
            s = jnp.tanh(s / logit_cap) * logit_cap
        s = s.reshape(g, bq, bk)

        rows = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        ok = cols < sk  # guard k padding
        if causal:
            ok &= cols <= rows
        if window > 0:
            ok &= ((rows - cols) < window) | (cols < prefix)
        s = jnp.where(ok[None], s, NEG_INF)

        m_prev = m_scr[...]                    # (G, BQ)
        l_prev = l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_prev * corr + jnp.sum(p, axis=-1)
        m_scr[...] = m_new
        pv = jax.lax.dot_general(
            p.reshape(g * bq, bk), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).reshape(g, bq, d)
        acc_scr[...] = acc_scr[...] * corr[..., None] + pv

    @pl.when(ki == nk - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l[..., None]).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal: bool = True, window: int = 0,  # lint-ok: config-sprawl
                           prefix: int = 0, logit_cap: float = 0.0,
                           block_q: int = 512, block_k: int = 512,
                           sq_real: int, sk_real: int, d_real: int,
                           interpret: bool):
    """q (BKV, G, Sq, D), k/v (BKV, Sk, D) — padded so Sq % block_q == 0,
    Sk % block_k == 0, D % 128 == 0. Returns (BKV, G, Sq, D) f32.

    ``sq_real``/``sk_real``/``d_real`` are the pre-padding sizes: the first
    two drive masking, ``d_real`` the softmax scale (zero-padded D columns
    contribute nothing to the dot products).
    """
    bkv, g, sq, d = q.shape
    sk = k.shape[1]
    assert sq % block_q == 0 and sk % block_k == 0 and d % 128 == 0
    grid = (bkv, sq // block_q, sk // block_k)
    scale = 1.0 / math.sqrt(d_real)
    kernel = functools.partial(
        _fa_kernel, causal, window, prefix, logit_cap, scale,
        block_q, block_k, sq_real, sk_real)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, g, block_q, d), lambda b, i, j: (b, 0, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, g, block_q, d),
                               lambda b, i, j: (b, 0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bkv, g, sq, d), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((g, block_q), jnp.float32),
            pltpu.VMEM((g, block_q), jnp.float32),
            pltpu.VMEM((g, block_q, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
