"""kmeans_assign kernel sweeps: shapes × dtypes vs the pure-jnp ref oracle."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.kernels.kmeans_assign import ops as km_ops, ref as km_ref

RNG = np.random.default_rng(7)


# ------------------------------------------------------------- kmeans_assign

@pytest.mark.parametrize("n,d,k", [
    (64, 8, 4), (100, 11, 8), (1000, 32, 16), (257, 7, 3), (64, 90, 32),
    (128, 128, 128), (33, 1, 2),
])
def test_kmeans_assign_matches_ref(n, d, k):
    p = jnp.asarray(RNG.normal(size=(n, d)), jnp.float32)
    c = jnp.asarray(RNG.normal(size=(k, d)), jnp.float32)
    a_ref, d_ref = km_ref.kmeans_assign(p, c)
    a_pal, d_pal = km_ops.kmeans_assign(p, c)
    assert np.array_equal(np.asarray(a_ref), np.asarray(a_pal))
    np.testing.assert_allclose(np.asarray(d_ref), np.asarray(d_pal),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kmeans_assign_dtypes(dtype):
    p = jnp.asarray(RNG.normal(size=(96, 16)), dtype)
    c = jnp.asarray(RNG.normal(size=(5, 16)), dtype)
    a_ref, _ = km_ref.kmeans_assign(p.astype(jnp.float32),
                                    c.astype(jnp.float32))
    a_pal, _ = km_ops.kmeans_assign(p, c)
    assert (np.asarray(a_ref) == np.asarray(a_pal)).mean() > 0.97
