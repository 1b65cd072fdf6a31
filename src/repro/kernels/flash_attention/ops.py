"""jit'd public wrapper for the flash attention Pallas kernel.

Accepts framework-layout tensors q (B,Sq,H,Dh), k/v (B,Sk,KV,Dh); folds
GQA groups, pads Sq/Sk to the block size and Dh to 128, runs the kernel,
and restores layout. Interpret mode exactly on a CPU backend
(``padding.interpret``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.padding import interpret


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "prefix", "logit_cap", "block_q", "block_k"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    prefix: int = 0, logit_cap: float = 0.0,
                    block_q: int = 512, block_k: int = 512):
    """q (B,Sq,H,Dh), k/v (B,Sk,KV,Dh) -> (B,Sq,H,Dh), same dtype as q."""
    b, sq, h, dh = q.shape
    sk, kv = k.shape[1], k.shape[2]
    g = h // kv
    bq = min(block_q, _round_up(sq, 128))
    bk = min(block_k, _round_up(sk, 128))
    sqp, skp, dp = _round_up(sq, bq), _round_up(sk, bk), _round_up(dh, 128)

    # (B,S,H,D) -> (B*KV, G, Sq, Dp) / (B*KV, Sk, Dp)
    qf = jnp.zeros((b, sqp, h, dp), jnp.float32)
    qf = qf.at[:, :sq, :, :dh].set(q.astype(jnp.float32))
    qf = qf.reshape(b, sqp, kv, g, dp).transpose(0, 2, 3, 1, 4)
    qf = qf.reshape(b * kv, g, sqp, dp)
    kf = jnp.zeros((b, skp, kv, dp), jnp.float32)
    kf = kf.at[:, :sk, :, :dh].set(k.astype(jnp.float32))
    kf = kf.transpose(0, 2, 1, 3).reshape(b * kv, skp, dp)
    vf = jnp.zeros((b, skp, kv, dp), jnp.float32)
    vf = vf.at[:, :sk, :, :dh].set(v.astype(jnp.float32))
    vf = vf.transpose(0, 2, 1, 3).reshape(b * kv, skp, dp)

    out = flash_attention_pallas(
        qf, kf, vf, causal=causal, window=window, prefix=prefix,
        logit_cap=logit_cap, block_q=bq, block_k=bk,
        sq_real=sq, sk_real=sk, d_real=dh, interpret=interpret())

    out = out.reshape(b, kv, g, sqp, dp).transpose(0, 3, 1, 2, 4)
    out = out.reshape(b, sqp, h, dp)[:, :sq, :, :dh]
    return out.astype(q.dtype)
