"""SplitNN VFL model zoo (paper §3) with instance-wise communication
accounting.

Roles: M clients (bottom models f_b^m over local feature slices), an
aggregation server (top model f_t), and the label owner (loss). Per step:
  ① clients run bottoms on their slices → intermediate activations,
  ② server merges (concat) and runs the top model,
  ③ label owner computes the (optionally Eq.2-weighted) loss → top grads,
  ④ server backprops, returns per-client bottom grads.

Mathematically this is one partitioned forward/backward, so on-device we
jit a single function; the VFL structure shows up as (a) the feature-block-
diagonal bottom layer — fused into one slab pass by
``kernels/splitnn_bottom`` — and (b) the counted activation/gradient bytes
per sample per step, the "instance-wise communication" whose reduction by
coreset training the paper measures.

Training itself lives in ``repro.train.vfl`` (DESIGN.md §7): a scan-based
epoch engine (one dispatch + one host sync per epoch, mesh-shardable) and
the legacy per-step loop kept as its parity oracle.  ``train_splitnn``
here is the thin stage entry point the pipeline calls.

Models: LR / MLP (classification), LinearReg (regression) as SplitNN;
KNN as distributed distance aggregation (squared L2 decomposes per client).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.vertical import VerticalPartition
from repro.train.losses import weighted_mse, weighted_softmax_xent
from repro.train.vfl import EngineStats, TrainReport  # re-export (compat)

ACT_BYTES = 4  # f32 activation/gradient element on the wire

__all__ = [
    "ACT_BYTES", "SplitNNConfig", "TrainReport", "EngineStats",
    "init_splitnn", "splitnn_forward", "activation_width",
    "activation_bytes_per_sample",
    "train_splitnn", "predict", "evaluate", "knn_predict",
]


# ------------------------------------------------------------------ configs

@dataclasses.dataclass(frozen=True)
class SplitNNConfig:
    model: str                  # "lr" | "mlp" | "linreg"
    n_classes: int              # 0 => regression
    bottom_dim: int = 8         # per-client intermediate width
    hidden_dim: int = 64        # top-model hidden width (mlp)
    lr: float = 0.01
    batch_size: int = 64
    max_epochs: int = 200
    convergence_eps: float = 1e-4   # paper: loss change over 5 epochs < 1e-4
    convergence_window: int = 5
    seed: int = 0


# ----------------------------------------------------------------- modeling

def init_splitnn(cfg: SplitNNConfig, feature_dims: Sequence[int]):
    key = jax.random.PRNGKey(cfg.seed)
    m = len(feature_dims)
    ks = jax.random.split(key, m + 2)
    if cfg.model == "lr":
        # logistic regression: bottoms are the local linear partial-sums;
        # top is identity-sum + bias. bottom_dim == n_out.
        n_out = max(cfg.n_classes, 1) if cfg.n_classes != 2 else 1
        bottoms = [
            {"w": jax.random.normal(ks[i], (d, n_out), jnp.float32)
             * (d ** -0.5) * 0.1}
            for i, d in enumerate(feature_dims)]
        top = {"b": jnp.zeros((n_out,), jnp.float32)}
        return {"bottoms": bottoms, "top": top}
    if cfg.model == "linreg":
        bottoms = [
            {"w": jax.random.normal(ks[i], (d, 1), jnp.float32)
             * (d ** -0.5) * 0.1}
            for i, d in enumerate(feature_dims)]
        top = {"b": jnp.zeros((1,), jnp.float32)}
        return {"bottoms": bottoms, "top": top}
    if cfg.model == "mlp":
        n_out = cfg.n_classes if cfg.n_classes > 2 else 1
        bottoms = [
            {"w": jax.random.normal(ks[i], (d, cfg.bottom_dim), jnp.float32)
             * (d ** -0.5),
             "b": jnp.zeros((cfg.bottom_dim,), jnp.float32)}
            for i, d in enumerate(feature_dims)]
        top = {
            "w1": jax.random.normal(ks[m], (m * cfg.bottom_dim,
                                            cfg.hidden_dim), jnp.float32)
            * ((m * cfg.bottom_dim) ** -0.5),
            "b1": jnp.zeros((cfg.hidden_dim,), jnp.float32),
            "w2": jax.random.normal(ks[m + 1], (cfg.hidden_dim, n_out),
                                    jnp.float32) * (cfg.hidden_dim ** -0.5),
            "b2": jnp.zeros((n_out,), jnp.float32),
        }
        return {"bottoms": bottoms, "top": top}
    raise ValueError(cfg.model)


def splitnn_forward(params, cfg: SplitNNConfig, xs: Sequence[jnp.ndarray]):
    """xs: per-client feature slices [(B, d_m)]. Returns logits/preds (B, o).

    Per-client loop form — the slab form (one fused block-diagonal pass
    over all M clients) is ``repro.train.vfl.forward_slab_packed``.
    """
    acts = []
    for bp, x in zip(params["bottoms"], xs):
        a = x @ bp["w"]
        if "b" in bp:
            a = jax.nn.relu(a + bp["b"])
        acts.append(a)
    if cfg.model in ("lr", "linreg"):
        out = sum(acts) + params["top"]["b"]
        return out
    h = jnp.concatenate(acts, axis=1)
    h = jax.nn.relu(h @ params["top"]["w1"] + params["top"]["b1"])
    return h @ params["top"]["w2"] + params["top"]["b2"]


def _loss_from_out(out, cfg: SplitNNConfig, y, w):
    """Eq.(2) weighted loss from model output (shared by both engines)."""
    if cfg.n_classes == 0:
        return weighted_mse(out[:, 0:1], y[:, None], w)
    if cfg.n_classes == 2 and out.shape[-1] == 1:
        from repro.train.losses import weighted_binary_xent
        return weighted_binary_xent(out[:, 0], y, w)
    return weighted_softmax_xent(out, y, w)


def _loss_fn(params, cfg: SplitNNConfig, xs, y, w):
    return _loss_from_out(splitnn_forward(params, cfg, xs), cfg, y, w)


def activation_width(cfg: SplitNNConfig) -> int:
    """Per-client activation elements per sample on the wire."""
    if cfg.model in ("lr", "linreg"):
        return 1 if cfg.n_classes in (0, 2) else cfg.n_classes
    return cfg.bottom_dim


def activation_bytes_per_sample(cfg: SplitNNConfig, m_clients: int,
                                quant: Optional[str] = None) -> int:
    """Instance-wise communication per sample per step (fwd act + bwd grad).

    Derived from the communicated dtypes, not a hardcoded 4 B/elem: the
    forward activation ships in the wire dtype (1 byte quantized, 4
    f32 — ``repro.quant.wire_bytes``), the backward gradient is always
    f32 (the straight-through backward of DESIGN.md §12).  A quantized
    payload's per-row-block scale bytes are per STEP, not per sample —
    the engines account them via ``repro.quant.scale_bytes_per_step``.
    """
    from repro.quant import wire_bytes

    width = activation_width(cfg)
    return (wire_bytes(quant) + ACT_BYTES) * width * m_clients


# ------------------------------------------------------------------ training

def train_splitnn(partition: VerticalPartition, cfg: SplitNNConfig, *,
                  sample_weights: Optional[np.ndarray] = None,
                  bandwidth: float = 10e9 / 8, latency: float = 2e-4,
                  verbose: bool = False,
                  options: Optional["EngineOptions"] = None,
                  **legacy) -> TrainReport:
    """Mini-batch Adam training to the paper's convergence criterion.

    Thin stage entry point over ``repro.train.vfl``.  Engine knobs live
    on ``options=EngineOptions(...)`` (``repro.config``; legacy
    ``engine=``/``mesh=``/``bottom_impl=``/... kwargs coerce through
    the shared shim with a ``DeprecationWarning``, bitwise-identical):

    - ``train_engine="scan"`` (default): compiled epoch engine — one
      dispatch and one host sync per epoch, remainder batches
      pad-and-masked, ``mesh``/``shard_axis`` shard the per-step batch
      axis over ``data`` and (on a 2-D ``(data, model)`` mesh) the
      M-client bottom axis over ``model`` (DESIGN.md §8),
      ``bottom_impl`` selects the block-diagonal bottom layer ("ref"
      slab oracle / "pallas" fused kernel / "loop" per-client), and
      ``fuse_gather`` scalar-prefetches the per-step schedule indices
      into that pass (bitwise-equal to the explicit ``slab[:, idx, :]``
      gather).
    - ``train_engine="loop"``: the legacy per-minibatch host loop
      (parity oracle and dispatch-overhead baseline; single-device
      only, f32 only — ``quant`` needs the scan engine's slab path).

    ``quant`` ("int8"|"fp8", DESIGN.md §12) quantizes the per-step
    activation send (and, for int8, the bottom GEMM) to a 1-byte wire
    dtype with pow2 block scales.
    """
    from repro.config import ENGINE_ALIASES, EngineOptions, _coerce_options
    from repro.quant import resolve_quant
    from repro.train import vfl

    (options,) = _coerce_options(
        "train_splitnn", legacy, ("options", EngineOptions, options,
                                  ENGINE_ALIASES))
    if options.train_engine == "loop":
        if options.mesh is not None:
            raise ValueError("engine='loop' does not shard; use the scan "
                             "engine for mesh training")
        if resolve_quant(options.quant) is not None:
            raise ValueError("engine='loop' communicates f32 only; use the "
                             "scan engine for quantized training")
        return vfl.train_loop(partition, cfg, sample_weights=sample_weights,
                              bandwidth=bandwidth, latency=latency,
                              verbose=verbose)
    if options.train_engine != "scan":
        raise ValueError(options.train_engine)
    return vfl.train_scan(partition, cfg, sample_weights=sample_weights,
                          bandwidth=bandwidth, latency=latency,
                          options=options, verbose=verbose)


# ---------------------------------------------------------------- evaluation

def predict(params, cfg: SplitNNConfig, partition: VerticalPartition, *,
            block_b: int = 512, bottom_impl: str = "ref",
            quant: Optional[str] = None) -> np.ndarray:
    """Batched prediction through the serving score path.

    Historically this pushed the WHOLE partition through the per-client
    loop forward in one unbatched dispatch; it now routes through
    ``repro.serve.vfl.score_partition`` — fixed ``block_b``-row slab
    batches (remainder zero-padded and truncated) mapped over the
    partition by one device program, so eval device memory is bounded
    by ``repro.serve.vfl.SCORE_DEVICE_BUDGET`` and the
    ``splitnn_bottom`` slab kernel is exercised.  Outputs are
    bitwise-equal to the one-shot forward on full batches (row
    independence; the scoring forward reproduces ``splitnn_forward``'s
    reduction order).  ``quant`` applies the wire rounding quantized
    training saw, so quantized checkpoints evaluate under their
    training numerics."""
    from repro.serve.vfl import score_partition

    out = score_partition(params, cfg, partition, block_b=block_b,
                          bottom_impl=bottom_impl, quant=quant)
    if cfg.n_classes == 0:
        return out[:, 0]
    if cfg.n_classes == 2 and out.shape[-1] == 1:
        return (out[:, 0] > 0).astype(np.int64)
    return out.argmax(axis=1)


def evaluate(params, cfg: SplitNNConfig, partition: VerticalPartition, *,
             block_b: int = 512, bottom_impl: str = "ref",
             quant: Optional[str] = None) -> float:
    """Accuracy for classification, MSE for regression (batched through
    the serving score path — see ``predict``)."""
    pred = predict(params, cfg, partition, block_b=block_b,
                   bottom_impl=bottom_impl, quant=quant)
    if cfg.n_classes == 0:
        return float(np.mean((pred - partition.labels) ** 2))
    return float(np.mean(pred == partition.labels))


# --------------------------------------------------------------- VFL k-NN

@functools.partial(jax.jit, static_argnames=("kk",))
def _knn_neighbors(test_feats, train_feats, train_sq, kk: int):
    """Top-k nearest training rows for one test batch, on device.

    ‖x−z‖² = Σ_m ‖x^m−z^m‖² decomposes per client, so every client
    contributes its local partial Gram/norm terms; the per-client
    accumulation is a sum of M batched GEMMs (f32, device) instead of
    the historical pure-numpy double loop.
    """
    a_sq = sum(jnp.sum(a * a, axis=1) for a in test_feats)        # (B,)
    cross = sum(a @ b.T for a, b in zip(test_feats, train_feats))  # (B,Ntr)
    d = a_sq[:, None] - 2.0 * cross + train_sq[None]
    _, nn = jax.lax.top_k(-d, kk)
    return nn


def knn_predict(train_part: VerticalPartition, test_part: VerticalPartition,
                k: int = 5, *, sample_weights: Optional[np.ndarray] = None,
                batch: int = 512) -> np.ndarray:
    """VFL k-NN: clients contribute local partial distances (on device),
    the label owner votes — optionally weighted by the coreset weights —
    via one vectorized scatter-add per batch (``np.add.at`` over the
    (batch, k) neighbor grid; duplicate class indices accumulate in the
    same j-ascending order as the per-neighbor loop it replaces).

    When n_te does not divide ``batch``, the final partial batch is
    zero-padded to ``batch`` rows and its outputs truncated, so
    ``_knn_neighbors`` compiles for exactly ONE test-batch shape instead
    of retriggering a shape-specialized recompile on the remainder
    (padded rows' neighbors are computed and discarded — predictions
    are identical)."""
    n_tr = train_part.n_samples
    n_te = test_part.n_samples
    preds = np.empty(n_te, np.int64)
    w = (np.asarray(sample_weights, np.float64)
         if sample_weights is not None else np.ones(n_tr))
    labels = train_part.labels.astype(np.int64)
    n_classes = int(labels.max()) + 1
    kk = min(k, n_tr)
    train_feats = [jnp.asarray(f, jnp.float32)
                   for f in train_part.client_features]
    train_sq = sum(jnp.sum(b * b, axis=1) for b in train_feats)
    for s in range(0, n_te, batch):
        e = min(s + batch, n_te)
        feats = [f[s:e] for f in test_part.client_features]
        if e - s < batch and n_te > batch:
            # pad the final partial batch back to the full-batch shape
            pad = batch - (e - s)
            feats = [np.concatenate(
                [f, np.zeros((pad, f.shape[1]), f.dtype)]) for f in feats]
        test_feats = [jnp.asarray(f, jnp.float32) for f in feats]
        nn = np.asarray(_knn_neighbors(test_feats, train_feats, train_sq,
                                       kk))[:e - s]
        votes = np.zeros((e - s, n_classes))
        rows = np.broadcast_to(np.arange(e - s)[:, None], nn.shape)
        np.add.at(votes, (rows, labels[nn]), w[nn])
        preds[s:e] = votes.argmax(axis=1)
    return preds
