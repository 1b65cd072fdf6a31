"""The Adam optimizer in pure JAX (pytree-based, no optax dependency).

Adam follows Kingma & Ba [arXiv:1412.6980], the paper's optimizer choice
(§5.1 Protocols). States are pytrees matching the param tree, so they shard
with the same specs as the params.
"""
from __future__ import annotations

from typing import Any, NamedTuple, Tuple

import jax
import jax.numpy as jnp


class AdamState(NamedTuple):
    step: jnp.ndarray
    mu: Any
    nu: Any


def adam_init(params) -> AdamState:
    zeros = jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params)
    return AdamState(step=jnp.zeros((), jnp.int32), mu=zeros,
                     nu=jax.tree_util.tree_map(jnp.copy, zeros))


def adam_update(params, grads, state: AdamState, *, lr: float = 1e-3,
                b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8,
                weight_decay: float = 0.0) -> Tuple[Any, AdamState]:
    step = state.step + 1
    t = step.astype(jnp.float32)
    bc1 = 1.0 - b1 ** t
    bc2 = 1.0 - b2 ** t

    def upd(p, g, m, v):
        g32 = g.astype(jnp.float32)
        m_new = b1 * m + (1.0 - b1) * g32
        v_new = b2 * v + (1.0 - b2) * jnp.square(g32)
        update = (m_new / bc1) / (jnp.sqrt(v_new / bc2) + eps)
        if weight_decay:
            update = update + weight_decay * p.astype(jnp.float32)
        return (p.astype(jnp.float32) - lr * update).astype(p.dtype), m_new, v_new

    flat_p, treedef = jax.tree_util.tree_flatten(params)
    flat_g = treedef.flatten_up_to(grads)
    flat_m = treedef.flatten_up_to(state.mu)
    flat_v = treedef.flatten_up_to(state.nu)
    out = [upd(p, g, m, v) for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v)]
    new_p = treedef.unflatten([o[0] for o in out])
    new_m = treedef.unflatten([o[1] for o in out])
    new_v = treedef.unflatten([o[2] for o in out])
    return new_p, AdamState(step=step, mu=new_m, nu=new_v)
