"""Compiled VFL training engines (paper §3 training stage, DESIGN.md §7–§8).

Two engines drive the SplitNN runtime (model zoo in
``repro.core.splitnn``):

``train_scan`` — the device engine.  One epoch is ONE compiled dispatch:
a ``lax.scan`` over a precomputed permutation schedule with the
``(params, opt)`` carry donated between epochs, per-step minibatch
gather + forward/backward/Adam in-graph, and the epoch loss accumulated
on device.  The host syncs exactly once per epoch (the ``float(loss)``
that feeds the paper's convergence-window check) instead of once per
minibatch — the legacy loop paid one dispatch *and* one blocking sync
per step.  Remainder batches are padded to the step shape and masked
out through the Eq.(2) sample weights (w = 0 rows contribute exactly
0.0 to every loss sum and gradient), so the last ``n mod bs`` rows
train instead of being dropped.  The M-client bottom layer runs as one
block-diagonal slab pass (``kernels/splitnn_bottom``); the per-step
``slab[:, idx, :]`` minibatch gather fuses INTO that pass
(``fuse_gather=True``, the default): the schedule indices
scalar-prefetch into the kernel, so the gathered batch never makes a
separate HBM round trip — bitwise-identical to gathering first.

With ``mesh=`` the engine shards over a 1-D ``("data",)`` or 2-D
``(data, model)`` mesh (``sharding.resolve_train_mesh``):

- ``data`` shards the per-step batch columns.  Each device computes its
  shard's unnormalized loss/grad sums; ``psum`` totals them before the
  replicated Adam update, so results match single-device training up to
  gemm/psum-reassociation ulps (DESIGN.md §5 parity rules — NOT
  byte-identical, unlike the gather-free PSI/CSS shardings).
- ``model`` shards the M-client bottom axis (DESIGN.md §8): each device
  owns a contiguous block of client weight slabs (and their Adam
  moments and feature slabs), computes its clients' activations, and
  the paper's "clients send activations to the server" step lowers to
  ONE ``all_gather`` over ``model`` per scan step.  The label-owner
  loss is computed on model-rank 0 only (other ranks' redundant copies
  are masked to exactly 0.0 before the psum), which keeps the
  all-gather's transpose — a psum_scatter handing each device the
  cotangent for ITS activation block — free of redundancy factors:
  bottom grads psum over ``data`` only, top grads over both axes.

``train_loop`` — the legacy host epoch loop (one jit dispatch + one
blocking sync per minibatch), kept as the parity oracle and timing
baseline.  Its remainder-batch drop is fixed here too: every epoch
trains all n rows, and ``comm_bytes`` counts the actual rows of the
partial batch.

Both return the same ``TrainReport`` (byte-compatible with the
pre-refactor report; ``engine_stats`` is appended with a default for
old constructors) and share the convergence criterion: |loss[-1-w] -
loss[-1]| < eps over the epoch-loss trace.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.kernels.padding import round_up
from repro.obs.metrics import StatsMixin
from repro.obs.trace import span
from repro.quant import (all_gather_quantized, fake_quantize, payload_bytes,
                         resolve_quant, scale_bytes_per_step)
from repro.sharding import padded_rows, resolve_train_mesh, spec_shard_map
from repro.train.optimizer import adam_init, adam_update

# ------------------------------------------------------------------ reports


@dataclasses.dataclass
class EngineStats(StatsMixin):
    """Measured execution counts for one training run.

    ``dispatches`` counts compiled-function invocations in the timed
    training loop; ``host_syncs`` counts blocking device→host transfers
    (the scan engine's contract is exactly one of each per epoch; the
    legacy loop pays one of each per minibatch step).  The one-time
    compile/warm-up dispatch before the timed region is excluded.
    ``shards``/``model_shards`` are the (data, model) mesh-axis sizes
    the run sharded over (1 = unsharded).

    ``StatsMixin`` (DESIGN.md §10) supplies ``to_dict``/``as_row`` and
    ``emit(registry)``; ``CONTRACT_FIELDS`` names the raw counters the
    CI perf contract derives its per-epoch ratios from.

    ``quant`` is the activation wire dtype ("none" = f32) and
    ``gather_payload_bytes`` the modeled per-step forward activation
    payload (values + pow2-exponent scale bytes when quantized) at the
    LOGICAL batch size — mesh-invariant, like ``comm_bytes``; the
    contract gate checks the quantized rows shrink it <= 0.3x vs f32.
    """
    dispatches: int = 0
    host_syncs: int = 0
    shards: int = 1
    steps_per_epoch: int = 0
    padded_batch: int = 0
    engine: str = "scan"
    bottom_impl: str = "ref"
    model_shards: int = 1
    fused_gather: bool = False
    quant: str = "none"
    gather_payload_bytes: int = 0

    CONTRACT_FIELDS = ("dispatches", "host_syncs", "steps_per_epoch")


@dataclasses.dataclass
class TrainReport:
    losses: List[float]
    epochs: int
    steps: int
    train_seconds: float          # measured compute
    comm_bytes: int               # instance-wise activation/grad traffic
    simulated_comm_seconds: float
    params: Any
    engine_stats: Optional[EngineStats] = None


# ------------------------------------------------------------ slab params


def pack_slab(features: Sequence[np.ndarray], m_pad: int = 0) -> np.ndarray:
    """Stack per-client (N, d_m) slices into the (M, N, d_max) slab.

    ``m_pad`` > M appends all-zero dummy clients (the model-axis padding
    of DESIGN.md §8: their activations are exactly 0 and are sliced off
    before the top model)."""
    m = len(features)
    n = features[0].shape[0]
    d_max = max(f.shape[1] for f in features)
    slab = np.zeros((max(m, m_pad), n, d_max), np.float32)
    for i, f in enumerate(features):
        slab[i, :, :f.shape[1]] = f
    return slab


def pack_slab_params(params, d_max: int, m_pad: int = 0):
    """Model-zoo params → the scan carry's slab form.

    ``{"bw": (Mp, d_max, o), ["bb": (Mp, o)], "top": {...}}`` — the
    per-client bottom blocks zero-padded to the widest client and
    stacked (plus ``m_pad - M`` all-zero dummy clients for the model
    axis), so the bottom carry is ONE shardable leaf instead of a
    ragged list.  Zero padding is exact: padded d rows multiply
    zero-padded feature columns and receive zero gradients, so they
    stay zero through Adam (as do dummy clients, whose activations are
    sliced off before the top model and therefore see zero cotangent).
    ``bb`` exists only when the zoo model has bottom biases (mlp) —
    bias-free models (lr/linreg) use a constant zero inside the
    forward, exactly like the zoo path, so no phantom bias trains.
    """
    ws = [bp["w"] for bp in params["bottoms"]]
    m = len(ws)
    mp = max(m, m_pad)
    o = ws[0].shape[1]
    w = jnp.zeros((mp, d_max, o), jnp.float32)
    for i, wm in enumerate(ws):
        w = w.at[i, :wm.shape[0], :].set(wm.astype(jnp.float32))
    packed = {"bw": w, "top": params["top"]}
    if "b" in params["bottoms"][0]:
        packed["bb"] = jnp.zeros((mp, o), jnp.float32).at[:m, :].set(
            jnp.stack([bp["b"] for bp in params["bottoms"]]))
    return packed


def unpack_slab_params(packed, feature_dims: Sequence[int]):
    """Slab-form carry → model-zoo params (exact slices; the inverse of
    ``pack_slab_params`` for the real clients)."""
    bottoms = []
    for i, d in enumerate(feature_dims):
        bp = {"w": packed["bw"][i, :d, :]}
        if "bb" in packed:
            bp["b"] = packed["bb"][i]
        bottoms.append(bp)
    return {"bottoms": bottoms, "top": packed["top"]}


# ------------------------------------------------------------ slab forward


def forward_slab_packed(packed, cfg, m: int, x_slab: jnp.ndarray, *,
                        bottom_impl: str = "ref", block_b: int = 512,
                        idx=None, model_axis: Optional[str] = None,
                        quant: Optional[str] = None):
    """SplitNN forward from slab-form params.

    ``x_slab`` is the local (M_loc, B, d_max) batch slab — or, with
    ``idx`` (B,) i32, the local FULL (M_loc, N, d_max) slab whose
    minibatch gather fuses into the bottom pass (scalar prefetch on the
    pallas impl).  ``model_axis`` names the mesh axis the M-client axis
    is sharded over: the client→server activation send then lowers to
    one ``all_gather`` (DESIGN.md §8); padded dummy clients are sliced
    off before the top model.  Matches ``splitnn_forward`` on the
    equivalent per-client slices (zero padding is exact).

    ``quant`` ("int8"|"fp8", DESIGN.md §12) narrows the activation send
    to a 1-byte wire dtype: the bottom pass runs the int8 kernel twins
    (int8 mode), and the collective becomes the quantized all_gather —
    still exactly ONE collective per step (scales ride in the same
    payload).  Off-mesh the same wire rounding applies via
    ``fake_quantize``, so single-device runs match mesh runs.  Dummy
    clients' all-zero activations quantize to exact zero, so the
    ``acts[:m]`` invariant is unchanged.
    """
    from repro.kernels.splitnn_bottom.ops import splitnn_bottom

    w = packed["bw"]
    o = w.shape[2]
    b = packed.get("bb")
    if b is None:
        b = jnp.zeros((w.shape[0], o), jnp.float32)
    relu = cfg.model == "mlp"
    acts = splitnn_bottom(x_slab, w, b, relu, bottom_impl, block_b, idx,
                          quant)
    if model_axis is not None:
        # §3 "send activations to the server": one collective per step
        if quant is None:
            acts = jax.lax.all_gather(acts, model_axis, axis=0, tiled=True)
        else:
            acts = all_gather_quantized(acts, model_axis, quant)
    elif quant is not None:
        acts = fake_quantize(acts, quant)
    acts = acts[:m]                              # drop dummy-client padding
    bsz = acts.shape[1]
    if cfg.model in ("lr", "linreg"):
        return jnp.sum(acts, axis=0) + packed["top"]["b"]
    # (M,B,o) -> (B, M*o): same layout as concatenating per-client acts
    h = jnp.transpose(acts, (1, 0, 2)).reshape(bsz, m * o)
    h = jax.nn.relu(h @ packed["top"]["w1"] + packed["top"]["b1"])
    return h @ packed["top"]["w2"] + packed["top"]["b2"]


def forward_slab_eval(packed, cfg, m: int, x_slab: jnp.ndarray, *,
                      bottom_impl: str = "ref", block_b: int = 512,
                      quant: Optional[str] = None):
    """Serving/eval slab forward: the same packed-slab bottom pass as
    ``forward_slab_packed`` (the ``splitnn_bottom`` kernel), but with the
    top combination BITWISE-matching ``splitnn_forward``'s per-client
    loop.  ``forward_slab_packed`` reduces the lr/linreg client sum with
    ``jnp.sum`` over the M axis, which reassociates by ~1 ulp against
    the loop's left-folded python ``sum``; the scoring path's contract
    is bitwise equality with the legacy forward on full batches, so the
    client sum unrolls here (mlp's transpose/reshape + top GEMMs are
    already elementwise-identical to concat-then-matmul).

    With ``quant`` the scoring path applies the SAME wire rounding as
    quantized training (``fake_quantize`` after the bottom pass), so a
    model trained with ``quant=`` is served with identical numerics —
    the serve-vs-train bottom agreement contract of DESIGN.md §12."""
    from repro.kernels.splitnn_bottom.ops import splitnn_bottom

    w = packed["bw"]
    o = w.shape[2]
    b = packed.get("bb")
    if b is None:
        b = jnp.zeros((w.shape[0], o), jnp.float32)
    relu = cfg.model == "mlp"
    acts = splitnn_bottom(x_slab, w, b, relu, bottom_impl, block_b, None,
                          quant)
    if quant is not None:
        acts = fake_quantize(acts, quant)
    acts = acts[:m]                              # drop dummy-client padding
    if cfg.model in ("lr", "linreg"):
        out = acts[0]
        for i in range(1, m):
            out = out + acts[i]
        return out + packed["top"]["b"]
    bsz = acts.shape[1]
    h = jnp.transpose(acts, (1, 0, 2)).reshape(bsz, m * o)
    h = jax.nn.relu(h @ packed["top"]["w1"] + packed["top"]["b1"])
    return h @ packed["top"]["w2"] + packed["top"]["b2"]


@functools.lru_cache(maxsize=32)
def _score_step_fn(cfg, m: int, bottom_impl: str, block_b: int,
                   quant: Optional[str] = None):
    """One jitted scoring executable per (config, client-count, impl,
    block, quant) — shared by every engine/eval call with the same
    signature so repeated ``predict``/engine construction never
    recompiles.  Bounded (and clearable via ``clear_program_caches``)
    so stale executables don't accumulate for process lifetime."""
    def score(packed, x_slab):
        return forward_slab_eval(packed, cfg, m, x_slab,
                                 bottom_impl=bottom_impl, block_b=block_b,
                                 quant=quant)
    return jax.jit(score)


@functools.lru_cache(maxsize=32)
def _score_blocks_fn(cfg, m: int, bottom_impl: str, block_b: int,
                     quant: Optional[str] = None):
    """One jitted program that scores M clients' (N, d_m) feature rows
    block by block on the device: it zero-pads and stacks them into the
    (M, n_blocks * block_b, d_max) slab, then runs a ``lax.map`` whose
    body is ``_score_step_fn``'s forward on one ``block_b``-row slice,
    so every row comes out bitwise as a per-block dispatch would give it
    (int8's 8-row scale groups included).  Returns the
    (n_blocks * block_b, o) outputs.  Cached like ``_score_step_fn``;
    jit adds one executable per row count."""
    def score(packed, *xs):
        rows = round_up(xs[0].shape[0], block_b)
        d_max = packed["bw"].shape[1]
        x_slab = jnp.stack([jnp.pad(x, ((0, rows - x.shape[0]),
                                        (0, d_max - x.shape[1])))
                            for x in xs])

        def block(i):
            xb = jax.lax.dynamic_slice_in_dim(x_slab, i * block_b, block_b,
                                              axis=1)
            return forward_slab_eval(packed, cfg, m, xb,
                                     bottom_impl=bottom_impl,
                                     block_b=block_b, quant=quant)
        out = jax.lax.map(block, jnp.arange(rows // block_b))
        return out.reshape(-1, out.shape[-1])
    return jax.jit(score)


def _packed_on_device(params, feature_dims: Sequence[int]):
    """``pack_slab_params`` gathered onto the default device (see
    ``make_score_step``)."""
    return jax.device_put(pack_slab_params(params, max(feature_dims)),
                          jax.devices()[0])


def make_partition_score(params, cfg, feature_dims: Sequence[int], *,
                         bottom_impl: str = "ref", block_b: int = 512,
                         quant: Optional[str] = None):
    """``(packed, score_blocks)``: ``make_score_step``'s handoff with the
    many-block program of ``_score_blocks_fn`` in place of the one-block
    step.  ``score_blocks(packed, *xs)`` takes the M clients' (N, d_m)
    rows and returns the (N rounded up to whole blocks, o) outputs."""
    fd = tuple(int(d) for d in feature_dims)
    return _packed_on_device(params, fd), _score_blocks_fn(
        cfg, len(fd), bottom_impl, int(block_b), resolve_quant(quant))


def make_score_step(params, cfg, feature_dims: Sequence[int], *,
                    bottom_impl: str = "ref", block_b: int = 512,
                    quant: Optional[str] = None):
    """``TrainReport.params`` (model-zoo form) → ``(packed, score_step)``:
    the slab-params handoff for serving (DESIGN.md §9).

    ``packed`` reuses ``pack_slab_params``, so serving and training
    share ONE parameter layout — a checkpoint that trains under the scan
    engine scores without any re-layout.  ``score_step(packed, x_slab)``
    is jitted: ``x_slab`` is an (M, B, d_max) feature slab and the
    result is (B, o) outputs, bitwise-equal to ``splitnn_forward`` on
    the same rows (any B; one compile per distinct B).

    Scoring runs on one device: parameters trained on a mesh arrive
    sharded over it, and a jit over several devices would have to
    partition the Pallas bottom kernel, which Mosaic cannot do outside
    a ``shard_map``.  So ``packed`` is gathered onto the default device.
    """
    fd = tuple(int(d) for d in feature_dims)
    return _packed_on_device(params, fd), _score_step_fn(
        cfg, len(fd), bottom_impl, int(block_b), resolve_quant(quant))


# -------------------------------------------------------------- loss sums


def _loss_sums(out, cfg, y, w) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Unnormalized Eq.(2) pieces (Σ w·l_i, Σ w) for the local rows.

    Mirrors the ``repro.train.losses`` definitions so that
    psum(S)/psum(W) across shards equals the single-device normalized
    loss up to reassociation ulps.
    """
    out = out.astype(jnp.float32)
    if cfg.n_classes == 0:
        li = jnp.sum(jnp.square(out[:, 0:1] - y[:, None].astype(jnp.float32)),
                     axis=1)
    elif cfg.n_classes == 2 and out.shape[-1] == 1:
        logits = out[:, 0]
        lab = y.astype(jnp.float32)
        li = (jnp.maximum(logits, 0) - logits * lab
              + jnp.log1p(jnp.exp(-jnp.abs(logits))))
    else:
        logz = jax.scipy.special.logsumexp(out, axis=-1)
        gold = jnp.take_along_axis(out, y[..., None], axis=-1)[..., 0]
        li = logz - gold
    w = w.astype(jnp.float32)
    return jnp.sum(w * li), jnp.sum(w)


# ------------------------------------------------------------- scheduling


def epoch_schedule(order: np.ndarray, n: int, bs: int, steps: int,
                   padded_bs: int) -> Tuple[np.ndarray, np.ndarray]:
    """(idx (steps, padded_bs) i32, mask (steps, padded_bs) f32) for one
    epoch's permutation ``order``.  Rows past n point at row 0 with mask
    0 — they are gathered and forwarded but weighted out of every loss
    sum and gradient, which is how the remainder batch trains without a
    second program shape."""
    idx = np.zeros((steps * bs,), np.int32)
    idx[:n] = order
    mask = np.zeros((steps * bs,), np.float32)
    mask[:n] = 1.0
    idx = idx.reshape(steps, bs)
    mask = mask.reshape(steps, bs)
    if padded_bs > bs:
        pad = padded_bs - bs
        idx = np.concatenate(
            [idx, np.zeros((steps, pad), np.int32)], axis=1)
        mask = np.concatenate(
            [mask, np.zeros((steps, pad), np.float32)], axis=1)
    return idx, mask


# ------------------------------------------------------------ scan engine


def bottom_path(bottom_impl: str, fuse_gather: bool, n: int, d: int,
                quant: Optional[str] = None) -> str:
    """Which bottom pass the epoch program runs over an (M, n, d) slab,
    by the ``record_path`` name of ``splitnn_bottom``'s wrapper: the
    gather-fused kernel or its past-budget fallback
    (``gather_path``), "dense" (pallas, gather first), "ref" (the jnp
    slab oracle) or "loop" (per-client matmuls)."""
    from repro.kernels.splitnn_bottom.ops import gather_path

    if bottom_impl not in ("ref", "pallas"):
        return "loop"
    if not fuse_gather:
        return "dense" if bottom_impl == "pallas" else "ref"
    return gather_path(bottom_impl, n, d, quant)


@dataclasses.dataclass
class EpochProgram:
    """One reusable compiled epoch-step program and the sharding layout
    it was built for.

    Built (and cached) by ``make_epoch_fn``: ``jitted`` is the
    donate-carry epoch executable ``(params, opt, idx, mask, *arrays) ->
    (params, opt, mean_loss)``; the spec fields are the shard_map layout
    it was wrapped with (``None``/empty off-mesh).  ``abstract_args``
    rebuilds the exact argument avals for any (n, bs), so the SAME
    program object both trains (``train_scan``) and statically lowers
    for the census gate (``repro.analysis.check``) — the verifier can
    never audit a different program than the one the engine runs.
    """
    jitted: Any
    cfg: Any
    feature_dims: Tuple[int, ...]
    mesh: Any
    data_axis: Optional[str]
    model_axis: Optional[str]
    n_data: int
    n_model: int
    bottom_impl: str
    fuse_gather: bool
    use_slab: bool
    n_data_arrays: int
    m_pad: int
    d_eff: int                       # slab feature width the program expects
    param_shapes: Any                # eval_shape of the fresh carry
    pspec: Any = None
    ospec: Any = None
    data_specs: Tuple = ()
    quant: Optional[str] = None      # activation wire dtype (None = f32)

    def pin_carry(self, params, opt):
        if self.mesh is None:
            return jax.device_put(params), jax.device_put(opt)
        pin = lambda tree, spec: jax.tree_util.tree_map(
            lambda t, s: jax.device_put(t, NamedSharding(self.mesh, s)),
            tree, spec)
        return pin(params, self.pspec), pin(opt, self.ospec)

    def pin_arrays(self, arrays):
        if self.mesh is None:
            return tuple(jax.device_put(a) for a in arrays)
        specs = self.data_specs + (P(), P())
        return tuple(
            jax.device_put(a, NamedSharding(self.mesh, s))
            for a, s in zip(arrays, specs))

    def abstract_args(self, n: int, bs: int) -> Tuple:
        """``jax.ShapeDtypeStruct`` args for ``jitted`` at problem size
        (n, bs) — enough to ``jitted.lower(*...)`` without any data."""
        bs = min(bs, n)
        steps = -(-n // bs)
        padded_bs = padded_rows(bs, self.n_data)
        sds = jax.ShapeDtypeStruct
        idx = sds((steps, padded_bs), jnp.int32)
        mask = sds((steps, padded_bs), jnp.float32)
        if self.use_slab:
            data = (sds((self.m_pad, n, self.d_eff), jnp.float32),)
        else:
            data = tuple(sds((n, d), jnp.float32)
                         for d in self.feature_dims)
        y = sds((n,), jnp.float32 if self.cfg.n_classes == 0
                else jnp.int32)
        w = sds((n,), jnp.float32)
        opt_shapes = jax.eval_shape(adam_init, self.param_shapes)
        return (self.param_shapes, opt_shapes, idx, mask) + data + (y, w)


@functools.lru_cache(maxsize=16)
def make_epoch_fn(cfg, feature_dims: Tuple[int, ...], mesh,
                  data_axis: Optional[str], model_axis: Optional[str],
                  n_data: int, n_model: int, bottom_impl: str,
                  block_b: int, fuse_gather: bool,
                  quant: Optional[str] = None) -> EpochProgram:
    """The epoch-step program factory: every argument is hashable, so
    one jitted executable (and its XLA compile-cache entry) serves every
    ``train_scan`` call with the same (config, layout, mesh) — the
    call-time-jit recompile hazard the lint rule bans is structurally
    impossible here.  Bounded at 16 programs; ``clear_program_caches``
    releases them (and the Mesh objects their keys pin) between tests.
    """
    from repro.core import splitnn as models

    m = len(feature_dims)
    d_max = max(feature_dims)
    use_slab = bottom_impl in ("ref", "pallas")
    m_pad = padded_rows(m, n_model)
    n_data_arrays = 1 if use_slab else m
    d_eff = (round_up(d_max, 128)
             if use_slab and fuse_gather and bottom_impl == "pallas"
             else d_max)

    def fresh_shapes():
        zoo = models.init_splitnn(cfg, list(feature_dims))
        return pack_slab_params(zoo, d_max, m_pad) if use_slab else zoo
    param_shapes = jax.eval_shape(fresh_shapes)

    def batch_forward(p, ib, xs_arrays, shard_model):
        maxis = model_axis if shard_model else None
        if use_slab:
            if fuse_gather:
                return forward_slab_packed(p, cfg, m, xs_arrays[0],
                                           bottom_impl=bottom_impl,
                                           block_b=block_b, idx=ib,
                                           model_axis=maxis, quant=quant)
            return forward_slab_packed(p, cfg, m, xs_arrays[0][:, ib, :],
                                       bottom_impl=bottom_impl,
                                       block_b=block_b, model_axis=maxis,
                                       quant=quant)
        return models.splitnn_forward(p, cfg, [x[ib] for x in xs_arrays])

    def epoch_body(params, opt, idx, mask, arrays, *, sharded):
        xs_arrays = arrays[:n_data_arrays]
        y_a, w_a = arrays[n_data_arrays], arrays[n_data_arrays + 1]
        steps = idx.shape[0]

        def body(carry, sched):
            p, o_, acc = carry
            ib, mb = sched
            y = y_a[ib]
            w = w_a[ib] * mb
            if not sharded:
                loss, grads = jax.value_and_grad(
                    lambda pp: models._loss_from_out(
                        batch_forward(pp, ib, xs_arrays, False),
                        cfg, y, w))(p)
            else:
                def s_fn(pp):
                    out = batch_forward(pp, ib, xs_arrays,
                                        model_axis is not None)
                    s, wsum = _loss_sums(out, cfg, y, w)
                    if model_axis is not None:
                        # the label owner lives on model-rank 0: the
                        # other ranks' redundant copies mask to exactly
                        # 0.0, so the all-gather transpose (psum_scatter)
                        # carries no redundancy factor
                        keep = (jax.lax.axis_index(model_axis) == 0
                                ).astype(jnp.float32)
                        s, wsum = s * keep, wsum * keep
                    return s, wsum
                (s, wsum), g = jax.value_and_grad(s_fn, has_aux=True)(p)
                axes = (data_axis,) if model_axis is None else (
                    data_axis, model_axis)
                s = jax.lax.psum(s, axes)
                wtot = jnp.maximum(jax.lax.psum(wsum, axes), 1e-12)
                if model_axis is None:
                    grads = jax.tree_util.tree_map(
                        lambda t: jax.lax.psum(t, axes) / wtot, g)
                else:
                    # bottom blocks are device-resident: their grads
                    # arrive via the all-gather transpose already summed
                    # over model, so they psum over data ONLY; top
                    # params are replicated, their grads (nonzero on
                    # rank 0's rows only) psum over both axes
                    grads = {k: jax.lax.psum(v, data_axis) / wtot
                             for k, v in g.items() if k != "top"}
                    grads["top"] = jax.tree_util.tree_map(
                        lambda t: jax.lax.psum(t, axes) / wtot, g["top"])
                loss = s / wtot
            p, o_ = adam_update(p, grads, o_, lr=cfg.lr)
            return (p, o_, acc + loss), None

        (params, opt, acc), _ = jax.lax.scan(
            body, (params, opt, jnp.zeros((), jnp.float32)), (idx, mask))
        return params, opt, acc / steps

    pspec = ospec = None
    data_specs: Tuple = ()
    if mesh is not None:
        def leaf_specs(tree, shard_clients: bool):
            def one(leaf):
                if shard_clients and model_axis is not None:
                    return P(*([model_axis]
                               + [None] * (jnp.ndim(leaf) - 1)))
                return P()
            return jax.tree_util.tree_map(one, tree)

        if use_slab and model_axis is not None:
            pspec = dict(leaf_specs(
                {k: v for k, v in param_shapes.items() if k != "top"},
                True))
            pspec["top"] = leaf_specs(param_shapes["top"], False)
            data_specs = (P(model_axis),)
        else:
            pspec = leaf_specs(param_shapes, False)
            data_specs = (P(),) * n_data_arrays
        from repro.train.optimizer import AdamState
        ospec = AdamState(step=P(), mu=pspec, nu=pspec)
        in_specs = (pspec, ospec, P(None, data_axis), P(None, data_axis)) \
            + data_specs + (P(), P())
        out_specs = (pspec, ospec, P())

        def fn(params, opt, idx, mask, *arrays):
            return epoch_body(params, opt, idx, mask, arrays, sharded=True)
        fn = spec_shard_map(fn, mesh, in_specs, out_specs)
    else:
        def fn(params, opt, idx, mask, *arrays):
            return epoch_body(params, opt, idx, mask, arrays,
                              sharded=False)

    jitted = jax.jit(fn, donate_argnums=(0, 1))
    return EpochProgram(
        jitted=jitted, cfg=cfg, feature_dims=feature_dims, mesh=mesh,
        data_axis=data_axis, model_axis=model_axis, n_data=n_data,
        n_model=n_model, bottom_impl=bottom_impl,
        fuse_gather=fuse_gather, use_slab=use_slab,
        n_data_arrays=n_data_arrays, m_pad=m_pad, d_eff=d_eff,
        param_shapes=param_shapes, pspec=pspec, ospec=ospec,
        data_specs=data_specs, quant=quant)


def train_scan(partition, cfg, *, sample_weights: Optional[np.ndarray] = None,
               bandwidth: float = 10e9 / 8, latency: float = 2e-4,
               options=None, verbose: bool = False) -> TrainReport:
    """Scan-based mini-batch Adam training to the paper's convergence
    criterion — one dispatch and one host sync per EPOCH.

    Engine knobs ride on ``options=repro.config.EngineOptions(...)``
    (``train_splitnn`` is the legacy-kwarg shim layer; this internal
    engine entry takes only the config object):

    ``bottom_impl``: "ref" (block-diagonal slab oracle, one batched
    GEMM) | "pallas" (fused VMEM-resident kernel) | "loop" (legacy
    per-client matmuls inside the scan, the bitwise-parity oracle for
    the slab layout).  ``fuse_gather`` fuses the per-step schedule
    gather into the slab pass (bitwise-equal to ``False``, which keeps
    the explicit ``slab[:, idx, :]`` round trip — the parity oracle).
    ``mesh`` shards the per-step batch axis over ``data`` and, on a 2-D
    ``(data, model)`` mesh, the M-client bottom axis over ``model``
    (DESIGN.md §8); results match single-device within reassociation
    ulps either way.  ``quant`` ("int8"|"fp8", DESIGN.md §12) narrows
    the per-step activation send to a 1-byte wire dtype (int8 also runs
    the int8 bottom kernels); needs the slab bottom path.
    """
    from repro.config import EngineOptions
    from repro.core import splitnn as models

    with span("train.setup", engine="scan", rows=partition.n_samples,
              clients=partition.n_clients) as setup_sp:
        options = options or EngineOptions()
        bottom_impl = options.bottom_impl
        block_b = options.block_b
        fuse_gather = options.fuse_gather
        quant = options.quant

        n = partition.n_samples
        m = partition.n_clients
        feature_dims = [f.shape[1] for f in partition.client_features]
        d_max = max(feature_dims)

        mesh, data_axis, n_data, model_axis, n_model = resolve_train_mesh(
            options.mesh, options.shard_axis)

        use_slab = bottom_impl in ("ref", "pallas")
        if n_model > 1 and not use_slab:
            raise ValueError(
                "model-axis sharding needs the slab bottom path "
                "(bottom_impl='ref'|'pallas'), not 'loop'")
        quant = resolve_quant(quant)
        if quant is not None and not use_slab:
            raise ValueError(
                "quantized activations need the slab bottom path "
                "(bottom_impl='ref'|'pallas'), not 'loop'")

        prog = make_epoch_fn(cfg, tuple(int(d) for d in feature_dims), mesh,
                             data_axis, model_axis, n_data, n_model,
                             bottom_impl, int(block_b), bool(fuse_gather),
                             quant)
        m_pad = prog.m_pad                           # dummy clients (§8)

        def fresh_params():
            zoo = models.init_splitnn(cfg, feature_dims)
            return pack_slab_params(zoo, d_max, m_pad) if use_slab else zoo

        params = fresh_params()
        opt = adam_init(params)

        y_np = partition.labels
        y_all = jnp.asarray(y_np, jnp.float32 if cfg.n_classes == 0
                            else jnp.int32)
        w_np = (np.asarray(sample_weights, np.float32)
                if sample_weights is not None else np.ones(n, np.float32))
        w_eff = jnp.asarray(w_np)

        if use_slab:
            slab = pack_slab(partition.client_features, m_pad)
            if prog.d_eff > d_max:
                # align the slab's d to the kernel lane width ONCE, here,
                # so the per-step gather-fused pass hands the loop-invariant
                # slab straight to the kernel instead of re-padding it every
                # scan step (pad_bottom_blocks_gather no-ops on aligned f32;
                # zero columns meet zero weight rows, values unchanged)
                slab = np.concatenate(
                    [slab, np.zeros(slab.shape[:2] + (prog.d_eff - d_max,),
                                    np.float32)], axis=2)
            data: Tuple = (jnp.asarray(slab),)
        else:
            data = tuple(jnp.asarray(f, jnp.float32)
                         for f in partition.client_features)
        arrays = data + (y_all, w_eff)

        bs = min(cfg.batch_size, n)
        steps_per_epoch = -(-n // bs)
        padded_bs = padded_rows(bs, n_data)

        jitted = prog.jitted
        arrays = prog.pin_arrays(arrays)

        # compile + warm up OUTSIDE the timed region (the warm-up consumes
        # the donated carry, so re-init to the identical seeded state), then
        # keep every timed call signature-stable: committed carry in,
        # committed carry out — no mid-loop recompiles.  ``prog`` is cached:
        # a repeated call with the same (config, layout, mesh) reuses the
        # compiled executable and the warm-up is a cheap re-dispatch.
        idx0, mask0 = epoch_schedule(np.arange(n), n, bs, steps_per_epoch,
                                     padded_bs)
        params, opt = prog.pin_carry(params, opt)
        setup_sp.set(bottom_path=bottom_path(bottom_impl, fuse_gather,
                                             n, prog.d_eff, quant))
    with span("train.compile", engine="scan", bottom_impl=bottom_impl,
              steps_per_epoch=steps_per_epoch, padded_batch=padded_bs,
              mesh=(n_data, n_model), fused_gather=use_slab and fuse_gather):
        jax.block_until_ready(jitted(params, opt, idx0, mask0, *arrays))
    params = fresh_params()
    params, opt = prog.pin_carry(params, adam_init(params))

    rng = np.random.default_rng(cfg.seed)
    # per-sample traffic derives from the wire dtype; the per-row-block
    # exponent bytes of a quantized payload are per STEP (they scale
    # with row blocks, not rows) and ride in per_epoch_bytes below.
    # Both use the LOGICAL bs so the figures are mesh-invariant.
    per_sample = models.activation_bytes_per_sample(cfg, m, quant)
    width = models.activation_width(cfg)
    scale_overhead = scale_bytes_per_step(bs, m, quant)
    per_epoch_bytes = per_sample * n + steps_per_epoch * scale_overhead
    stats = EngineStats(shards=n_data, steps_per_epoch=steps_per_epoch,
                        padded_batch=padded_bs, engine="scan",
                        bottom_impl=bottom_impl, model_shards=n_model,
                        fused_gather=use_slab and fuse_gather,
                        quant=quant or "none",
                        gather_payload_bytes=payload_bytes(
                            width, bs, m, quant))
    losses: List[float] = []
    comm_bytes = 0
    total_steps = 0
    epoch = 0
    t0 = time.perf_counter()
    for epoch in range(1, cfg.max_epochs + 1):
        order = rng.permutation(n)
        idx, mask = epoch_schedule(order, n, bs, steps_per_epoch, padded_bs)
        # the epoch span brackets the ONE dispatch + ONE host sync; it
        # reads the host clock only, so the engine's dispatch/sync
        # contract is identical traced or not (tests/test_obs.py)
        with span("train.epoch", epoch=epoch, engine="scan",
                  steps=steps_per_epoch, comm_bytes=per_epoch_bytes) as sp:
            params, opt, ep_loss = jitted(params, opt, idx, mask, *arrays)
            stats.dispatches += 1
            losses.append(float(ep_loss))  # the single host sync this epoch
            stats.host_syncs += 1
            sp.set(loss=losses[-1])
        total_steps += steps_per_epoch
        comm_bytes += per_epoch_bytes   # every row trains, remainder too
        if verbose and epoch % 10 == 0:
            print(f"  epoch {epoch}: loss {losses[-1]:.5f}")
        wlen = cfg.convergence_window
        if len(losses) > wlen:
            if abs(losses[-1 - wlen] - losses[-1]) < cfg.convergence_eps:
                break
    train_seconds = time.perf_counter() - t0
    sim_comm = comm_bytes / bandwidth + latency * 2 * total_steps * m
    out_params = (unpack_slab_params(params, feature_dims) if use_slab
                  else params)
    return TrainReport(losses=losses, epochs=epoch, steps=total_steps,
                       train_seconds=train_seconds, comm_bytes=comm_bytes,
                       simulated_comm_seconds=sim_comm, params=out_params,
                       engine_stats=stats)


# ----------------------------------------------------------- legacy loop


@functools.lru_cache(maxsize=8)
def _loop_step_fn(cfg):
    """One jitted legacy-loop step per config, hoisted out of
    ``train_loop`` so repeated ``engine="loop"`` runs hit the compile
    cache instead of rebuilding a fresh ``@jax.jit`` wrapper per call
    (the call-time-jit hazard the lint rule bans).  The data arrays ride
    in as arguments rather than closures for the same reason: a closure
    over ``xs_all`` would key the compile cache on array identity."""
    def step(params, opt, idx, y_all, w_all, *xs_all):
        from repro.core import splitnn as models
        xs = [x[idx] for x in xs_all]
        y = y_all[idx]
        w = w_all[idx] if w_all is not None else None
        loss, grads = jax.value_and_grad(
            lambda p: models._loss_fn(p, cfg, xs, y, w))(params)
        params, opt = adam_update(params, grads, opt, lr=cfg.lr)
        return params, opt, loss
    return jax.jit(step)


def clear_program_caches() -> None:
    """Drop every cached jitted training/scoring program and the
    coreset's batched k-means executables (and the Mesh objects their
    keys pin).  Tests that build transient meshes call this so device
    meshes aren't held for process lifetime; the paired PSI-side hook is
    ``repro.psi.engine.clear_dispatch_cache``.
    """
    from repro.core.coreset import clear_fit_cache
    _score_step_fn.cache_clear()
    _score_blocks_fn.cache_clear()
    make_epoch_fn.cache_clear()
    _loop_step_fn.cache_clear()
    clear_fit_cache()


def train_loop(partition, cfg, *, sample_weights: Optional[np.ndarray] = None,
               bandwidth: float = 10e9 / 8, latency: float = 2e-4,
               verbose: bool = False) -> TrainReport:
    """Legacy host epoch loop: one jit dispatch + one blocking sync per
    minibatch.  Kept as the scan engine's parity oracle and the
    dispatch-overhead baseline for ``table2_e2e``.  The historical
    remainder-batch drop (``range(0, n - bs + 1, bs)``) is fixed: the
    last ``n mod bs`` rows now train as a short batch, and
    ``comm_bytes`` counts the rows actually shipped."""
    from repro.core import splitnn as models

    n = partition.n_samples
    m = partition.n_clients
    feature_dims = [f.shape[1] for f in partition.client_features]
    params = models.init_splitnn(cfg, feature_dims)
    opt = adam_init(params)

    y_np = partition.labels
    y_all = jnp.asarray(y_np, jnp.float32 if cfg.n_classes == 0
                        else jnp.int32)
    xs_all = [jnp.asarray(f, jnp.float32) for f in partition.client_features]
    w_all = (jnp.asarray(sample_weights, jnp.float32)
             if sample_weights is not None else None)

    step_fn = _loop_step_fn(cfg)

    def step(params, opt, idx):
        return step_fn(params, opt, idx, y_all, w_all, *xs_all)

    rng = np.random.default_rng(cfg.seed)
    bs = min(cfg.batch_size, n)
    # the legacy loop always communicates f32 activations (no quant
    # knob); per_sample still derives from the wire dtype (quant=None)
    per_sample = models.activation_bytes_per_sample(cfg, m, None)
    stats = EngineStats(shards=1, steps_per_epoch=-(-n // bs),
                        padded_batch=bs, engine="loop", bottom_impl="loop",
                        gather_payload_bytes=payload_bytes(
                            models.activation_width(cfg), bs, m, None))
    losses: List[float] = []
    comm_bytes = 0
    total_steps = 0
    t0 = time.perf_counter()
    epoch = 0
    for epoch in range(1, cfg.max_epochs + 1):
        order = rng.permutation(n)
        ep_loss, nb = 0.0, 0
        with span("train.epoch", epoch=epoch, engine="loop") as sp:
            for s in range(0, n, bs):
                idx = jnp.asarray(order[s:s + bs])
                params, opt, loss = step(params, opt, idx)
                stats.dispatches += 1
                ep_loss += float(loss)          # blocking sync EVERY step
                stats.host_syncs += 1
                nb += 1
                total_steps += 1
                comm_bytes += per_sample * int(idx.shape[0])
            sp.set(steps=nb)
        losses.append(ep_loss / max(nb, 1))
        if verbose and epoch % 10 == 0:
            print(f"  epoch {epoch}: loss {losses[-1]:.5f}")
        wlen = cfg.convergence_window
        if len(losses) > wlen:
            if abs(losses[-1 - wlen] - losses[-1]) < cfg.convergence_eps:
                break
    train_seconds = time.perf_counter() - t0
    sim_comm = comm_bytes / bandwidth + latency * 2 * total_steps * m
    return TrainReport(losses=losses, epochs=epoch, steps=total_steps,
                       train_seconds=train_seconds, comm_bytes=comm_bytes,
                       simulated_comm_seconds=sim_comm, params=params,
                       engine_stats=stats)
