"""Fused block-diagonal SplitNN bottom kernel: bitwise parity with its
jnp oracle under the padding contract, and custom_vjp gradients."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.padding import pad_bottom_blocks
from repro.kernels.splitnn_bottom.kernel import splitnn_bottom_pallas
from repro.kernels.splitnn_bottom.ops import splitnn_bottom, take_rows
from repro.kernels.splitnn_bottom.ref import splitnn_bottom_ref


def _case(m=3, b=70, d=5, o=8, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(m, b, d)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(m, d, o)).astype(np.float32))
    bias = jnp.asarray(rng.normal(size=(m, o)).astype(np.float32))
    return x, w, bias


@pytest.mark.parametrize("relu", [True, False])
@pytest.mark.parametrize("shape", [(3, 70, 5, 8), (2, 130, 17, 1),
                                   (5, 64, 140, 8)])
def test_kernel_matches_ref_bitwise(relu, shape):
    m, b, d, o = shape
    x, w, bias = _case(m, b, d, o, seed=d)
    xp, wp, bp, bb = pad_bottom_blocks(x, w, bias, 512)
    got = splitnn_bottom_pallas(xp, wp, bp, relu=relu, block_b=bb,
                                interpret=True)
    exp = splitnn_bottom_ref(xp, wp, bp, relu=relu)
    assert got.dtype == exp.dtype
    assert np.array_equal(np.asarray(got), np.asarray(exp))


@pytest.mark.parametrize("block_b", [8, 32])
def test_kernel_tiling_is_invariant(block_b):
    """Output rows are independent, so shrinking the batch tile cannot
    change any value — multi-tile grid vs one-block, bitwise."""
    x, w, bias = _case(b=96, seed=7)
    xp, wp, bp, bb = pad_bottom_blocks(x, w, bias, block_b)
    assert xp.shape[1] // bb > 1             # actually multi-tile
    got = splitnn_bottom_pallas(xp, wp, bp, relu=True, block_b=bb,
                                interpret=True)
    exp = splitnn_bottom_ref(xp, wp, bp, relu=True)
    assert np.array_equal(np.asarray(got), np.asarray(exp))


@pytest.mark.parametrize("relu", [True, False])
def test_ops_matches_per_client_loop(relu):
    """The public op against the M-long loop of small GEMMs it replaces,
    computed in f32 at precision "highest".  Zero-padding d/o/B adds
    exact zeros, but the padded (d=128) and unpadded (d=9) GEMMs sum in
    an order XLA picks per shape, so the two agree to a few f32 ulps of
    the |x·w| products (≤ ~10 here), not bitwise: rtol/atol 1e-5 leaves
    ~10x headroom over that drift and still catches a wrong client,
    column or bias (errors of order 1)."""
    x, w, bias = _case(m=4, b=51, d=9, o=6, seed=11)
    with jax.default_matmul_precision("highest"):
        loop = jnp.stack([x[i] @ w[i] + bias[i] for i in range(4)])
    if relu:
        loop = jnp.maximum(loop, 0.0)
    for impl in ("ref", "pallas"):
        got = splitnn_bottom(x, w, bias, relu, impl)
        np.testing.assert_allclose(np.asarray(got), np.asarray(loop),
                                   rtol=1e-5, atol=1e-5, err_msg=impl)


@pytest.mark.parametrize("impl", ["ref", "pallas"])
@pytest.mark.parametrize("relu", [True, False])
def test_custom_vjp_matches_autodiff(impl, relu):
    x, w, bias = _case(seed=3)

    def fused(x, w, bias):
        return jnp.sum(splitnn_bottom(x, w, bias, relu, impl) ** 2)

    def plain(x, w, bias):
        a = jnp.einsum("mbd,mdo->mbo", x, w) + bias[:, None, :]
        if relu:
            a = jnp.maximum(a, 0.0)
        return jnp.sum(a ** 2)

    g_fused = jax.grad(fused, argnums=(0, 1, 2))(x, w, bias)
    g_plain = jax.grad(plain, argnums=(0, 1, 2))(x, w, bias)
    for gf, gp in zip(g_fused, g_plain):
        assert np.allclose(np.asarray(gf), np.asarray(gp),
                           rtol=1e-5, atol=1e-6)


# ------------------------------------------------- scalar-prefetch gather


@pytest.mark.parametrize("relu", [True, False])
@pytest.mark.parametrize("impl", ["ref", "pallas"])
@pytest.mark.parametrize("bsz", [12, 70, 130])     # one-tile + multi-tile
def test_gather_fused_matches_gather_then_dense(relu, impl, bsz):
    """splitnn_bottom(x, ..., idx=) over the full slab must be bitwise-
    equal to gathering slab[:, idx, :] first and running the dense pass
    — including duplicate schedule slots (the remainder batch points
    every pad slot at row 0)."""
    rng = np.random.default_rng(bsz)
    x, w, bias = _case(m=3, b=40, d=9, o=6, seed=bsz)   # b here is N rows
    idx = jnp.asarray(rng.integers(0, 40, bsz).astype(np.int32))
    idx = idx.at[-3:].set(0)                            # forced duplicates
    fused = splitnn_bottom(x, w, bias, relu, impl, 64, idx)
    dense = splitnn_bottom(x[:, idx, :], w, bias, relu, impl, 64)
    assert fused.shape == (3, bsz, 6)
    assert np.array_equal(np.asarray(fused), np.asarray(dense))


@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_gather_fused_param_grads_bitwise(impl):
    """The fused path routes through the same backward as the dense
    path, so the w/b gradients training actually consumes are bitwise-
    equal to gathering first."""
    rng = np.random.default_rng(3)
    x, w, bias = _case(m=3, b=50, d=7, o=5, seed=13)
    idx = jnp.asarray(rng.integers(0, 50, 24).astype(np.int32))

    def fused(w, bias):
        return jnp.sum(splitnn_bottom(x, w, bias, True, impl, 512, idx) ** 2)

    def dense(w, bias):
        xg = x[:, idx, :]
        return jnp.sum(splitnn_bottom(xg, w, bias, True, impl, 512) ** 2)

    gf = jax.grad(fused, argnums=(0, 1))(w, bias)
    gd = jax.grad(dense, argnums=(0, 1))(w, bias)
    for a, b in zip(gf, gd):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_gather_fused_slab_grad_scatters():
    """The slab cotangent scatter-adds the gathered-row grads back into
    the full (M, N, d) layout — duplicates accumulate — matching
    autodiff through the explicit take."""
    rng = np.random.default_rng(5)
    x, w, bias = _case(m=2, b=30, d=6, o=4, seed=21)
    idx = jnp.asarray(rng.integers(0, 30, 16).astype(np.int32))
    idx = idx.at[:4].set(idx[0])                        # heavy duplicates

    def fused(x):
        return jnp.sum(splitnn_bottom(x, w, bias, True, "ref", 512, idx) ** 2)

    def taken(x):
        return jnp.sum(splitnn_bottom(x[:, idx, :], w, bias, True,
                                      "ref", 512) ** 2)

    gf = jax.grad(fused)(x)
    gt = jax.grad(taken)(x)
    assert gf.shape == x.shape
    assert np.allclose(np.asarray(gf), np.asarray(gt), rtol=1e-6, atol=1e-6)


def test_impls_share_one_backward():
    """ref and pallas route through the same custom_vjp backward, so
    their gradients cannot diverge — bitwise."""
    x, w, bias = _case(seed=5)

    def loss(impl):
        def f(x, w, bias):
            return jnp.sum(splitnn_bottom(x, w, bias, True, impl) ** 2)
        return jax.grad(f, argnums=(0, 1, 2))(x, w, bias)

    for gr, gp in zip(loss("ref"), loss("pallas")):
        assert np.array_equal(np.asarray(gr), np.asarray(gp))


def test_take_rows_equals_the_row_axis_take():
    """The row gather of the (M*N, d) view is ``x[:, idx, :]``, repeated
    and edge rows included."""
    x, _, _ = _case(m=3, b=37, d=11, seed=3)
    idx = jnp.asarray([5, 0, 36, 5, 12, 36], jnp.int32)
    assert np.array_equal(np.asarray(take_rows(x, idx)),
                          np.asarray(jnp.take(x, idx, axis=1)))
