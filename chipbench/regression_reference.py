"""Plain references for a regression TreeCSS job.

Written from the paper's description (TreeCSS, arXiv:2408.01691 §4.2,
§5.1) in numpy and plain ``jax.numpy``; nothing here imports the
program.

- Cluster-Coreset steps 4-5 on a numeric label: the label cut into 16
  bins at its quantiles (``numpy.quantile``), each row's bin the count of
  cut points below it, then ``chipbench.reference.select_coreset`` on the
  bins.
- The split linear regression of §5.1: per party a bias-free linear
  bottom ``d_m -> 1``, the server summing the parties' outputs and one
  bias; weights N(0, 1/d_m) x 0.1 from ``PRNGKey(seed)`` split M+2 ways,
  bias 0; trained by mini-batch Adam on the Eq. (2) weighted squared
  error, in float32 at the matrix-product precision the configuration
  states (``dtype="bfloat16"`` computes everything in bfloat16 instead:
  the control).
"""
from __future__ import annotations

import functools
from typing import Dict, Sequence, Tuple

import numpy as np

from chipbench import reference

BINS = 16


def label_bins(labels: np.ndarray, bins: int = BINS) -> np.ndarray:
    """Each label's quantile bin: how many of the ``bins - 1`` inner
    quantiles of the labels lie strictly below it."""
    cuts = np.quantile(labels, np.arange(1, bins) / bins)
    return np.sum(cuts[None, :] < labels[:, None], axis=1).astype(np.int64)


def select_coreset(assigns: Sequence[np.ndarray],
                   sq_dists: Sequence[np.ndarray], labels: np.ndarray,
                   k: int) -> Tuple[np.ndarray, np.ndarray]:
    """Steps 4-5 with the (CT, label bin) groups."""
    return reference.select_coreset(assigns, sq_dists, label_bins(labels), k)


# --------------------------------------------------------------- training


def init(seed: int, widths: Sequence[int], dtype):
    import jax
    import jax.numpy as jnp

    ks = jax.random.split(jax.random.PRNGKey(seed), len(widths) + 2)
    params = {"bottoms": [{"w": jax.random.normal(ks[i], (d, 1), jnp.float32)
                           * (d ** -0.5) * 0.1}
                          for i, d in enumerate(widths)],
              "top": {"b": jnp.zeros((1,), jnp.float32)}}
    return jax.tree_util.tree_map(lambda t: t.astype(dtype), params)


def forward(params, xs):
    """(B, 1): the sum of the parties' linear outputs plus the bias."""
    out = xs[0] @ params["bottoms"][0]["w"]
    for p, x in zip(params["bottoms"][1:], xs[1:]):
        out = out + x @ p["w"]
    return out + params["top"]["b"]


def _weighted_mse(pred, y, w):
    import jax.numpy as jnp

    return jnp.sum(w * jnp.square(pred - y)) / jnp.maximum(jnp.sum(w),
                                                           1e-12)


@functools.lru_cache(maxsize=4)
def _epoch_fn(lr: float, dtype_name: str):
    import jax
    import jax.numpy as jnp

    dtype = jnp.dtype(dtype_name)
    b1, b2, eps = 0.9, 0.999, 1e-8

    def loss_fn(p, xs, y, w):
        return _weighted_mse(forward(p, xs)[:, 0], y, w)

    def epoch(params, mu, nu, t, xs_all, y_all, w_all, idx, mask):
        def step(carry, sched):
            p, m_, v_, t_ = carry
            ib, mb = sched
            xs = [x[ib] for x in xs_all]
            loss, g = jax.value_and_grad(loss_fn)(p, xs, y_all[ib],
                                                  w_all[ib] * mb)
            t_ = t_ + 1
            bc1 = (1 - b1 ** t_).astype(dtype)
            bc2 = (1 - b2 ** t_).astype(dtype)
            m_ = jax.tree_util.tree_map(
                lambda a, b: (b1 * a + (1 - b1) * b).astype(dtype), m_, g)
            v_ = jax.tree_util.tree_map(
                lambda a, b: (b2 * a + (1 - b2) * b * b).astype(dtype),
                v_, g)
            p = jax.tree_util.tree_map(
                lambda a, m1, v1: (a - lr * (m1 / bc1)
                                   / (jnp.sqrt(v1 / bc2) + eps)
                                   ).astype(dtype), p, m_, v_)
            return (p, m_, v_, t_), loss

        (params, mu, nu, t), losses = jax.lax.scan(
            step, (params, mu, nu, t), (idx, mask))
        return params, mu, nu, t, jnp.mean(losses.astype(jnp.float32))

    return jax.jit(epoch)


def train(xs: Sequence[np.ndarray], y: np.ndarray, w: np.ndarray, *,
          seed: int, epochs: int, batch: int, lr: float, precision: str,
          dtype: str = "float32", half_batch: bool = False):
    """Mini-batch Adam (b1 0.9, b2 0.999, eps 1e-8, bias-corrected) on
    the weighted squared error, ``epochs`` epochs, each over a fresh
    permutation from ``numpy.random.default_rng(seed)`` in batches of
    ``batch`` rows (the last one short), matrix products at
    ``precision``.  ``half_batch`` is a planted fault: the second half of
    every batch is left out and the mean taken over the rest.  Returns
    (params0, params, per-epoch mean loss)."""
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(dtype)
    n = int(y.shape[0])
    bs = min(batch, n)
    steps = -(-n // bs)
    p0 = init(seed, [x.shape[1] for x in xs], dt)
    zeros = jax.tree_util.tree_map(jnp.zeros_like, p0)
    params, mu, nu = p0, zeros, zeros
    t = jnp.zeros((), jnp.float32)
    xs_d = [jnp.asarray(x, dt) for x in xs]
    y_d = jnp.asarray(y, dt)
    w_d = jnp.asarray(w, dt)
    fn = _epoch_fn(float(lr), dt.name)
    rng = np.random.default_rng(seed)
    losses = []
    with jax.default_matmul_precision(precision):
        for _ in range(epochs):
            flat = np.zeros(steps * bs, np.int32)
            flat[:n] = rng.permutation(n)
            mask = np.zeros(steps * bs, np.float32)
            mask[:n] = 1.0
            if half_batch:
                mask.reshape(steps, bs)[:, bs // 2:] = 0.0
            params, mu, nu, t, loss = fn(
                params, mu, nu, t, xs_d, y_d, w_d,
                jnp.asarray(flat.reshape(steps, bs)),
                jnp.asarray(mask.reshape(steps, bs), dt))
            losses.append(float(loss))
    return p0, params, losses


def predict(params, xs: Sequence[np.ndarray], *, precision: str,
            block: int = 8192) -> np.ndarray:
    """Forward over rows in blocks at the parameters' dtype and
    ``precision``: (N,) float32."""
    import jax
    import jax.numpy as jnp

    dt = params["top"]["b"].dtype
    out = []
    with jax.default_matmul_precision(precision):
        for s in range(0, xs[0].shape[0], block):
            out.append(np.asarray(forward(
                params, [jnp.asarray(x[s:s + block], dt) for x in xs]),
                np.float32))
    return np.concatenate(out)[:, 0]


def leaves(params) -> Dict[str, np.ndarray]:
    """Flat ``{path: float64 array}`` view of a split linear model."""
    out = {f"bottoms.{i}.w": np.asarray(b["w"], np.float64)
           for i, b in enumerate(params["bottoms"])}
    out["top.b"] = np.asarray(params["top"]["b"], np.float64)
    return out
