"""A plain per-step float32 trainer: the reference the scan engine's
parity tests compare against (``tests/test_vfl_engine.py``).

It trains the same SplitNN as ``repro.train.vfl.train_scan`` with none of
the engine's machinery: the per-client forward
(``core.splitnn.splitnn_forward``), the Eq.(2) loss, ``jax.value_and_grad``
and ``adam_update``, one minibatch at a time, matmuls at the highest
precision.  It follows the engine's schedule (one
``default_rng(cfg.seed).permutation`` per epoch), trains the ``n mod bs``
remainder rows as a short batch, and stops on the paper's convergence
criterion.  It keeps no stats, spans or caches.
"""
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.splitnn import (_loss_from_out, activation_bytes_per_sample,
                                init_splitnn, splitnn_forward)
from repro.train.optimizer import adam_init, adam_update


def train_reference(partition, cfg, sample_weights=None):
    """``(losses, epochs, steps, comm_bytes, params)`` as a namespace, the
    fields of ``TrainReport`` the parity tests compare."""
    n = partition.n_samples
    xs = [jnp.asarray(f, jnp.float32) for f in partition.client_features]
    y = jnp.asarray(partition.labels,
                    jnp.float32 if cfg.n_classes == 0 else jnp.int32)
    w = jnp.asarray(np.ones(n) if sample_weights is None else sample_weights,
                    jnp.float32)

    def loss(params, idx):
        out = splitnn_forward(params, cfg, [x[idx] for x in xs])
        return _loss_from_out(out, cfg, y[idx], w[idx])

    @jax.jit
    def step(params, opt, idx):
        with jax.default_matmul_precision("highest"):
            value, grads = jax.value_and_grad(loss)(params, idx)
            params, opt = adam_update(params, grads, opt, lr=cfg.lr)
        return params, opt, value

    params = init_splitnn(cfg, [x.shape[1] for x in xs])
    opt = adam_init(params)
    rng = np.random.default_rng(cfg.seed)
    bs = min(cfg.batch_size, n)
    losses, steps, epoch = [], 0, 0
    for epoch in range(1, cfg.max_epochs + 1):
        order = rng.permutation(n)
        batch_losses = []
        for s in range(0, n, bs):
            params, opt, value = step(params, opt, jnp.asarray(order[s:s + bs]))
            batch_losses.append(float(value))
        losses.append(float(np.mean(batch_losses)))
        steps += len(batch_losses)
        wlen = cfg.convergence_window
        if (len(losses) > wlen
                and abs(losses[-1 - wlen] - losses[-1]) < cfg.convergence_eps):
            break
    per_sample = activation_bytes_per_sample(cfg, len(xs))
    return SimpleNamespace(losses=losses, epochs=epoch, steps=steps,
                           comm_bytes=per_sample * n * epoch, params=params)
