"""Quantized activation comm + int8 bottom kernels (DESIGN.md §12).

Properties pinned here:

- pow2-exponent quantize→dequantize round trip: bounded error, scale
  symmetry (negation commutes), EXACT zeros for zero rows (pad-and-mask
  rows, dummy clients), and determinism across row-block-aligned chunks
  (quantizing a slab equals quantizing its chunks — what makes the
  fake-quantize eval path bitwise-match the mesh gather);
- the packed one-collective payload round-trips bit-exactly (fp8 rides
  an int8 bitcast) and its size meets the ≤ 0.3x f32 gate;
- ``fake_quantize`` has an identity (straight-through) gradient;
- the int8 kernel twins match the jnp oracle BITWISE, forward and
  gradient, dense and gather-fused;
- quantized serve (``forward_slab_eval``) agrees with the off-mesh
  quantized train forward;
- the engine's comm accounting derives from the wire dtype and stays
  mesh-invariant (8-device tests, skipped below 8 devices).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import make_cls_partition
from repro import quant as Q
from repro.core.splitnn import (SplitNNConfig, activation_bytes_per_sample,
                                activation_width, evaluate, train_splitnn)
from repro.kernels.splitnn_bottom.ops import splitnn_bottom

needs_8_devices = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs >=8 devices for the (data, model) mesh "
           "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")

QUANTS = ["int8"] + (["fp8"] if Q.FP8_DTYPE is not None else [])


# ------------------------------------------------------------ primitives


def test_resolve_quant():
    for alias in (None, "", "none", "f32", "fp32"):
        assert Q.resolve_quant(alias) is None
    assert Q.resolve_quant("int8") == "int8"
    with pytest.raises(ValueError):
        Q.resolve_quant("int4")


def test_pow2_exponent_exact_cases():
    amax = jnp.array([0.0, 127.0, 254.0, 1.0, 2.0 ** -10])
    e = Q.pow2_exponent(amax, "int8")
    assert e.dtype == jnp.int8
    # amax == 0 -> exponent 0 (exact-zero row); amax == qmax -> e = 0
    assert int(e[0]) == 0 and int(e[1]) == 0 and int(e[2]) == 1
    # every real amax must be representable: amax / 2^e <= qmax
    scale = jnp.exp2(e.astype(jnp.float32))
    assert bool(jnp.all(amax / scale <= 127.0))
    # and e is the TIGHTEST such pow2 (halving it would overflow)
    nz = amax[1:]
    assert bool(jnp.all(nz / (scale[1:] / 2) > 127.0))


@pytest.mark.parametrize("quant", QUANTS)
def test_row_block_round_trip_and_symmetry(rng, quant):
    acts = jnp.asarray(rng.normal(size=(3, 40, 8)).astype(np.float32))
    q, e = Q.quantize_row_blocks(acts, quant)
    deq = Q.dequantize_row_blocks(q, e)
    assert deq.shape == acts.shape
    # per-block error bound: half an LSB of the pow2 step
    step = jnp.exp2(e.astype(jnp.float32))          # (M, nb)
    nb = e.shape[1]
    pad = nb * Q.QUANT_BLOCK_ROWS - acts.shape[1]
    err = jnp.abs(deq - acts).reshape(3, -1)
    blk_err = jnp.pad(err, ((0, 0), (0, pad * 8))).reshape(3, nb, -1)
    tol = (0.5 if quant == "int8" else 32.0)        # fp8 e4m3: 4-bit mant
    assert bool(jnp.all(jnp.max(blk_err, axis=2) <= tol * step))
    # symmetric: negation commutes with the quantizer
    qn, en = Q.quantize_row_blocks(-acts, quant)
    assert bool(jnp.all(en == e))
    assert np.array_equal(np.asarray(Q.dequantize_row_blocks(qn, en)),
                          -np.asarray(deq))


@pytest.mark.parametrize("quant", QUANTS)
def test_exact_zero_rows_and_dummy_clients(rng, quant):
    acts = jnp.asarray(rng.normal(size=(4, 24, 4)).astype(np.float32))
    acts = acts.at[3].set(0.0)          # dummy client (model-axis pad)
    acts = acts.at[:, 20:, :].set(0.0)  # pad-and-mask tail rows
    q, e = Q.quantize_row_blocks(acts, quant)
    deq = Q.dequantize_row_blocks(q, e)
    assert bool(jnp.all(deq[3] == 0.0))
    assert bool(jnp.all(deq[:, 20:, :] == 0.0))
    # zero blocks carry exponent 0, so the payload is deterministic too
    assert bool(jnp.all(e[3] == 0))


@pytest.mark.parametrize("quant", QUANTS)
def test_chunked_determinism(rng, quant):
    """Quantizing a slab == quantizing block-aligned chunks: the
    property that makes single-device fake-quantize bitwise-match the
    per-shard mesh gather when B_loc % QUANT_BLOCK_ROWS == 0."""
    acts = jnp.asarray(rng.normal(size=(2, 64, 4)).astype(np.float32))
    full_q, full_e = Q.quantize_row_blocks(acts, quant)
    deq_full = Q.dequantize_row_blocks(full_q, full_e)
    half = 32                          # multiple of QUANT_BLOCK_ROWS
    parts = [Q.dequantize_row_blocks(*Q.quantize_row_blocks(c, quant))
             for c in (acts[:, :half], acts[:, half:])]
    assert np.array_equal(np.asarray(deq_full),
                          np.asarray(jnp.concatenate(parts, axis=1)))


@pytest.mark.parametrize("quant", QUANTS)
def test_pack_unpack_payload_bit_exact(rng, quant):
    acts = jnp.asarray(rng.normal(size=(3, 24, 4)).astype(np.float32))
    q, e = Q.quantize_row_blocks(acts, quant)
    payload = Q.pack_payload(q, e)
    assert payload.dtype == jnp.int8 and payload.ndim == 2
    q2, e2 = Q.unpack_payload(payload, 24, 4, quant)
    assert q2.dtype == q.dtype
    assert np.array_equal(np.asarray(e2), np.asarray(e))
    assert np.array_equal(
        np.asarray(q2).view(np.uint8), np.asarray(q).view(np.uint8))
    # the ≤ 0.3x gate, at the payload level
    assert payload.size <= 0.3 * acts[:, :, :].size * 4


def test_fake_quantize_identity_gradient(rng):
    x = jnp.asarray(rng.normal(size=(2, 16, 4)).astype(np.float32))
    g = jax.grad(lambda v: jnp.sum(jnp.sin(Q.fake_quantize(v, "int8"))))(x)
    # straight-through: the upstream cotangent passes through unchanged
    # (cos of the quantized forward, NOT cos(x) scaled by dq/dx)
    expect = jnp.cos(Q.fake_quantize(x, "int8"))
    assert np.array_equal(np.asarray(g), np.asarray(expect))


def test_payload_bytes_model():
    # lr (width 1), bs=64, 3 clients: (64*1 + ceil(64/8)) * 3 = 216
    assert Q.payload_bytes(1, 64, 3, None) == 64 * 4 * 3
    assert Q.payload_bytes(1, 64, 3, "int8") == (64 + 8) * 3
    assert Q.payload_bytes(1, 64, 3, "int8") <= \
        0.3 * Q.payload_bytes(1, 64, 3, None)
    assert Q.scale_bytes_per_step(64, 3, None) == 0
    assert Q.scale_bytes_per_step(64, 3, "int8") == 8 * 3


# ------------------------------------------------------- int8 kernel twins


def _rand_xwb(rng, m=3, b=48, d=10, o=6):
    x = jnp.asarray(rng.normal(size=(m, b, d)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(m, d, o)).astype(np.float32))
    bias = jnp.asarray(rng.normal(size=(m, o)).astype(np.float32))
    return x, w, bias


@pytest.mark.parametrize("relu", [True, False])
def test_int8_ref_vs_pallas_bitwise(rng, relu):
    x, w, b = _rand_xwb(rng)
    ref = splitnn_bottom(x, w, b, relu, "ref", 512, None, "int8")
    pal = splitnn_bottom(x, w, b, relu, "pallas", 512, None, "int8")
    assert np.array_equal(np.asarray(ref), np.asarray(pal))
    # and it tracks the f32 forward within quantization error
    f32 = splitnn_bottom(x, w, b, relu, "ref", 512, None, None)
    assert float(jnp.max(jnp.abs(ref - f32))) < 0.25


def test_int8_gather_fused_matches_unfused(rng):
    x, w, b = _rand_xwb(rng, b=64)
    idx = jnp.asarray(rng.integers(0, 64, size=32).astype(np.int32))
    fused = splitnn_bottom(x, w, b, True, "pallas", 512, idx, "int8")
    unfused = splitnn_bottom(jnp.take(x, idx, axis=1), w, b, True,
                             "pallas", 512, None, "int8")
    oracle = splitnn_bottom(x, w, b, True, "ref", 512, idx, "int8")
    assert np.array_equal(np.asarray(fused), np.asarray(unfused))
    assert np.array_equal(np.asarray(fused), np.asarray(oracle))


def test_int8_gradients_ref_vs_pallas_bitwise(rng):
    x, w, b = _rand_xwb(rng)

    def loss(impl):
        def f(args):
            out = splitnn_bottom(args[0], args[1], args[2], True, impl,
                                 512, None, "int8")
            return jnp.sum(out * out)
        return jax.grad(f)((x, w, b))

    gr, gp = loss("ref"), loss("pallas")
    for a, c in zip(gr, gp):
        assert np.array_equal(np.asarray(a), np.asarray(c))


def test_fp8_is_comm_only(rng):
    if Q.FP8_DTYPE is None:
        pytest.skip("no float8_e4m3fn in this jax build")
    x, w, b = _rand_xwb(rng)
    # fp8 keeps the f32 GEMM: kernel output must equal the f32 path
    out = splitnn_bottom(x, w, b, True, "ref", 512, None, "fp8")
    f32 = splitnn_bottom(x, w, b, True, "ref", 512, None, None)
    assert np.array_equal(np.asarray(out), np.asarray(f32))


def test_unknown_quant_rejected(rng):
    x, w, b = _rand_xwb(rng)
    with pytest.raises(ValueError):
        splitnn_bottom(x, w, b, True, "ref", 512, None, "int4")


# ------------------------------------------------- engine + serve threading


def _train(part, model="lr", quant=None, mesh=None, impl="ref"):
    cfg = SplitNNConfig(model=model, n_classes=2, lr=0.05, batch_size=64,
                        max_epochs=5)
    rep = train_splitnn(part, cfg, quant=quant, mesh=mesh,
                        bottom_impl=impl)
    return cfg, rep


@pytest.mark.parametrize("quant", QUANTS)
def test_quantized_training_and_accounting(quant):
    part = make_cls_partition(n=400)
    cfg, rep = _train(part, quant=quant)
    st = rep.engine_stats
    assert st.quant == quant
    m, n, bs = 3, part.n_samples, cfg.batch_size
    per = activation_bytes_per_sample(cfg, m, quant)
    steps = st.steps_per_epoch
    expect = rep.epochs * (per * n
                           + steps * Q.scale_bytes_per_step(bs, m, quant))
    assert rep.comm_bytes == expect
    # per-step payload shrink gate vs the f32 twin
    _, rep32 = _train(part, quant=None)
    assert rep32.engine_stats.quant == "none"
    assert st.gather_payload_bytes <= \
        0.3 * rep32.engine_stats.gather_payload_bytes
    # quantized training still learns the separable mixture
    assert evaluate(rep.params, cfg, part, quant=quant) > 0.9


def test_f32_accounting_unchanged():
    part = make_cls_partition(n=400)
    cfg, rep = _train(part, quant=None)
    per = activation_bytes_per_sample(cfg, 3, None)
    assert per == 8 * activation_width(cfg) * 3
    assert rep.comm_bytes == rep.epochs * per * part.n_samples


@pytest.mark.parametrize("quant", QUANTS)
def test_serve_matches_train_forward(quant):
    """Quantized scoring (forward_slab_eval) must agree with the
    off-mesh quantized train forward on the same batch — the train→serve
    handoff cannot change the wire numerics."""
    from repro.train.vfl import (forward_slab_eval, forward_slab_packed,
                                 make_score_step, pack_slab)
    part = make_cls_partition(n=256)
    cfg, rep = _train(part, quant=quant)
    fd = [f.shape[1] for f in part.client_features]
    packed, step = make_score_step(rep.params, cfg, fd, quant=quant)
    x_slab = jnp.asarray(pack_slab([f[:64] for f in part.client_features]))
    served = step(packed, x_slab)
    trained = forward_slab_packed(packed, cfg, 3, x_slab, quant=quant)
    evald = forward_slab_eval(packed, cfg, 3, x_slab, quant=quant)
    assert np.array_equal(np.asarray(served), np.asarray(evald))
    assert np.allclose(np.asarray(served), np.asarray(trained),
                       rtol=1e-6, atol=1e-6)


# ------------------------------------------------------------- mesh parity


@needs_8_devices
@pytest.mark.parametrize("quant", QUANTS)
def test_mesh_quant_matches_single_device(quant):
    from repro.launch.mesh import make_train_mesh
    part = make_cls_partition(n=256)
    cfg, base = _train(part, model="mlp", quant=quant, impl="pallas")
    mesh = make_train_mesh(2, 4)
    _, shrd = _train(part, model="mlp", quant=quant, mesh=mesh,
                     impl="pallas")
    # B_loc % QUANT_BLOCK_ROWS == 0 on this mesh -> per-shard row
    # blocks tile identically -> losses match to reassociation ulps
    assert abs(shrd.losses[-1] - base.losses[-1]) < 1e-5
    # counters are mesh-invariant (logical-bs accounting)
    assert shrd.comm_bytes == base.comm_bytes
    assert shrd.engine_stats.gather_payload_bytes == \
        base.engine_stats.gather_payload_bytes
    assert shrd.engine_stats.quant == quant


@needs_8_devices
def test_mesh_quant_full_pipeline():
    from repro.core.treecss import run_pipeline
    from repro.launch.mesh import make_train_mesh
    full = make_cls_partition(n=500, d=12)
    rows = np.random.default_rng(1).permutation(500)
    tr, te = full.take(rows[:380]), full.take(rows[380:])
    cfg = SplitNNConfig(model="lr", n_classes=2, lr=0.05, batch_size=64,
                        max_epochs=30)
    rep = run_pipeline(tr, te, cfg, variant="treecss",
                       clusters_per_client=8, seed=0,
                       mesh=make_train_mesh(2, 4), quant="int8")
    assert rep.train.engine_stats.quant == "int8"
    assert rep.metric > 0.85
