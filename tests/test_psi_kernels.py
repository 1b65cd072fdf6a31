"""PSI kernel triplets: psi_prf and sorted_intersect vs their jnp refs
(bitwise, kernels in interpret mode on the CPU backend) and vs numpy set
semantics."""
import numpy as np
import jax.numpy as jnp
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # container image has no hypothesis
    from _propcheck import given, settings, strategies as st

from repro.kernels.psi_prf.ops import prf_tags
from repro.kernels.sorted_intersect import ref as si_ref
from repro.kernels.sorted_intersect.ops import (next_pow2, pack_keys,
                                                sorted_intersect)
from repro.kernels.sorted_intersect.ref import PAD_A, PAD_B


def _rand_lanes(n, seed=0):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.integers(0, 2**32, n).astype(np.uint32)),
            jnp.asarray(rng.integers(0, 2**32, n).astype(np.uint32)))


SEED = jnp.asarray([0xDEAD, 0xBEEF], jnp.uint32)


# ------------------------------------------------------------------ psi_prf

@pytest.mark.parametrize("n", [1, 7, 128, 1000, 5000])
def test_prf_kernel_matches_ref(n):
    hi, lo = _rand_lanes(n, seed=n)
    th_k, tl_k = prf_tags(hi, lo, SEED, impl="pallas")
    th_r, tl_r = prf_tags(hi, lo, SEED, impl="ref")
    assert np.array_equal(np.asarray(th_k), np.asarray(th_r))
    assert np.array_equal(np.asarray(tl_k), np.asarray(tl_r))


def test_prf_tag_space_is_62_bit():
    hi, lo = _rand_lanes(4096, seed=1)
    th, _ = prf_tags(hi, lo, SEED, impl="pallas")
    assert int(np.asarray(th).max()) < 2**30


def test_prf_no_collisions_on_unique_ids():
    """Feistel bijection pre-mask ⇒ unique inputs keep unique tags
    (up to the astronomically unlikely 2-bit mask collision)."""
    ids = np.unique(np.random.default_rng(2).integers(
        0, 2**62, 8000, dtype=np.int64))
    hi = jnp.asarray((ids >> 32).astype(np.uint32))
    lo = jnp.asarray((ids & 0xFFFFFFFF).astype(np.uint32))
    th, tl = prf_tags(hi, lo, SEED, impl="ref")
    t64 = (np.asarray(th, np.uint64) << np.uint64(32)) | np.asarray(tl)
    assert len(np.unique(t64)) == len(ids)


def test_prf_seed_changes_tags():
    hi, lo = _rand_lanes(256, seed=3)
    t1 = np.asarray(prf_tags(hi, lo, SEED, impl="ref")[1])
    t2 = np.asarray(prf_tags(hi, lo, jnp.asarray([1, 2], jnp.uint32),
                             impl="ref")[1])
    assert (t1 != t2).mean() > 0.99


# ---------------------------------------------------------- sorted_intersect

def _key_rows(tags64, origin):
    """Host-side mirror of the engine's packing: sorted u64 tags ->
    ascending (kh, kl) u32 key lanes."""
    key = (np.sort(tags64).astype(np.uint64) << np.uint64(1)) | np.uint64(
        origin)
    return (jnp.asarray((key >> np.uint64(32)).astype(np.uint32)),
            jnp.asarray((key & np.uint64(0xFFFFFFFF)).astype(np.uint32)))


def _intersect_via(a_tags, b_tags, impl):
    """Run the ops wrapper and decode (sel, rank) back to matched A-side
    tags using rank indexing, like the engine does."""
    a_kh, a_kl = _key_rows(a_tags, 1)
    b_kh, b_kl = _key_rows(b_tags, 0)
    sel, rank, _, _ = sorted_intersect(a_kh, a_kl, b_kh, b_kl, impl=impl)
    sel = np.asarray(sel).astype(bool)
    rank = np.asarray(rank)
    by_tag = np.sort(a_tags)
    return np.sort(by_tag[rank[sel] - 1])


@pytest.mark.parametrize("na,nb", [(0, 0), (0, 9), (5, 0), (17, 33),
                                   (64, 64), (200, 77)])
def test_intersect_matches_numpy(na, nb):
    rng = np.random.default_rng(na * 100 + nb)
    a = np.unique(rng.integers(0, 2**60, na, dtype=np.int64))
    b = np.unique(rng.integers(0, 2**60, nb, dtype=np.int64))
    k = min(len(a), len(b)) // 2
    if k:
        b = np.unique(np.concatenate([a[:k], b]))
    expect = np.intersect1d(a, b)
    for impl in ("ref", "pallas"):
        got = _intersect_via(a, b, impl)
        assert np.array_equal(got, expect), impl


def test_intersect_kernel_matches_ref_bitwise():
    rng = np.random.default_rng(7)
    a = np.unique(rng.integers(0, 2**60, 150, dtype=np.int64))
    b = np.unique(np.concatenate(
        [a[:40], rng.integers(0, 2**60, 90, dtype=np.int64)]))
    a_kh, a_kl = _key_rows(a, 1)
    b_kh, b_kl = _key_rows(b, 0)
    out_k = sorted_intersect(a_kh, a_kl, b_kh, b_kl, impl="pallas")
    out_r = sorted_intersect(a_kh, a_kl, b_kh, b_kl, impl="ref")
    for k, r in zip(out_k, out_r):
        assert np.array_equal(np.asarray(k), np.asarray(r))


def test_single_pass_row_block_exchanges_match_ref_bitwise():
    """P = 2^13 puts strides of 64+ rows inside the single-pass block,
    which the kernel runs as row-block pair exchanges (below that, as
    sublane/lane rolls): all four outputs stay bitwise equal to the ref."""
    from repro.kernels.sorted_intersect.kernel import (
        SUB_ROWS, sorted_intersect_pallas)
    rng = np.random.default_rng(8)
    a = np.unique(rng.integers(0, 2**60, 5000, dtype=np.int64))
    b = np.unique(np.concatenate(
        [a[:2000], rng.integers(0, 2**60, 4000, dtype=np.int64)]))
    p = next_pow2(max(len(a), len(b)))
    assert p >= SUB_ROWS * 128
    args = _padded_lanes(a, 1, PAD_A, p) + _padded_lanes(b, 0, PAD_B, p)
    out_k = sorted_intersect_pallas(*args, interpret=True)
    out_r = si_ref.sorted_intersect(*args)
    for k, r in zip(out_k, out_r):
        assert np.array_equal(np.asarray(k), np.asarray(r))


def test_intersect_identical_and_disjoint():
    a = np.arange(50, dtype=np.int64) * 3
    for impl in ("ref", "pallas"):
        assert np.array_equal(_intersect_via(a, a.copy(), impl), a)
        assert _intersect_via(a, a + 1, impl).size == 0


def test_merged_output_is_sorted():
    rng = np.random.default_rng(11)
    a = np.unique(rng.integers(0, 2**60, 100, dtype=np.int64))
    b = np.unique(rng.integers(0, 2**60, 60, dtype=np.int64))
    a_kh, a_kl = _key_rows(a, 1)
    b_kh, b_kl = _key_rows(b, 0)
    _, _, mkh, mkl = sorted_intersect(a_kh, a_kl, b_kh, b_kl,
                                      impl="pallas")
    m = (np.asarray(mkh, np.uint64) << np.uint64(32)) | np.asarray(mkl)
    assert (m[:-1] <= m[1:]).all()


def test_pack_keys_layout():
    th = jnp.asarray([0, 1, 2**29], jnp.uint32)
    tl = jnp.asarray([0, 2**31, 5], jnp.uint32)
    kh, kl = pack_keys(th, tl, 1)
    key = (np.asarray(kh, np.uint64) << np.uint64(32)) | np.asarray(kl)
    tag = (np.asarray(th, np.uint64) << np.uint64(32)) | np.asarray(tl)
    assert np.array_equal(key, (tag << np.uint64(1)) | np.uint64(1))


def test_next_pow2():
    assert [next_pow2(n) for n in (0, 1, 8, 9, 100, 128)] == \
        [8, 8, 8, 16, 128, 128]


def test_pad_sentinels_above_real_keys():
    for pad in (PAD_A, PAD_B):
        assert pad[0] >= si_ref.VALID_LIMIT
    assert PAD_A != PAD_B
    # top bit of kh clear for any real key: tag < 2^62 ⇒ kh < 2^31
    assert ((((2**62 - 1) << 1) | 1) >> 32) < si_ref.VALID_LIMIT


@settings(max_examples=15, deadline=None)
@given(st.sets(st.integers(0, 2**61), max_size=40),
       st.sets(st.integers(0, 2**61), max_size=40))
def test_property_intersect_set_semantics(sa, sb):
    a = np.asarray(sorted(sa), np.int64)
    b = np.asarray(sorted(sb), np.int64)
    expect = np.asarray(sorted(sa & sb), np.int64)
    got = _intersect_via(a, b, "pallas")
    assert np.array_equal(got, expect)


# ------------------------------------------------------ tiled multi-pass merge

def _padded_lanes(tags64, origin, pad, p):
    key = (np.sort(tags64).astype(np.uint64) << np.uint64(1)) | np.uint64(
        origin)
    kh = np.full((p,), pad[0], np.uint32)
    kl = np.full((p,), pad[1], np.uint32)
    kh[:len(key)] = (key >> np.uint64(32)).astype(np.uint32)
    kl[:len(key)] = (key & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    return jnp.asarray(kh), jnp.asarray(kl)


@pytest.mark.parametrize("na,nb,chunk_p,tile", [
    (3000, 2500, 512, 1024),    # several cross passes + one-tile chunks
    (9000, 7000, 1024, 1024),   # deeper cross/tile split
    (5, 3, 512, 1024),          # chunk covers everything: zero cross passes
    (1500, 1500, 1024, 1024),   # one cross pass, tile < chunk
])
def test_tiled_merge_bitwise_matches_ref(na, nb, chunk_p, tile):
    """The multi-pass grid schedule runs the identical compare-exchange
    network, so its four outputs are bitwise equal to the jnp ref at ANY
    chunk/tile split (shrunk here, down to one (8, 128) tile, so small
    inputs exercise several cross passes)."""
    from repro.kernels.sorted_intersect.kernel import sorted_intersect_tiled
    rng = np.random.default_rng(na + nb)
    a = np.unique(rng.integers(0, 2**60, na, dtype=np.int64))
    b = np.unique(rng.integers(0, 2**60, max(nb, 1), dtype=np.int64))[:nb]
    k = min(len(a), len(b)) // 2
    if k:
        b = np.unique(np.concatenate([a[:k], b]))
    p = next_pow2(max(len(a), len(b)))
    a_kh, a_kl = _padded_lanes(a, 1, PAD_A, p)
    b_kh, b_kl = _padded_lanes(b, 0, PAD_B, p)
    out_t = sorted_intersect_tiled(a_kh, a_kl, b_kh, b_kl,
                                   interpret=True, chunk_p=chunk_p,
                                   tile=tile)
    out_r = si_ref.sorted_intersect(a_kh, a_kl, b_kh, b_kl)
    for t, r in zip(out_t, out_r):
        assert np.array_equal(np.asarray(t), np.asarray(r))


@pytest.mark.slow
def test_ops_dispatches_tiled_past_vmem_bound():
    """P > 2^19 must run the tiled kernel (no jnp-ref fallback) and
    still match the ref bitwise — the acceptance bar for retiring the
    fallback."""
    from unittest import mock

    from repro.kernels.sorted_intersect import kernel as si_kernel
    from repro.kernels.sorted_intersect import ops as si_ops

    n = 600_000                  # next_pow2 -> 2^20 > SINGLE_PASS_MAX_P
    rng = np.random.default_rng(0)
    universe = rng.choice(4 * n, size=2 * n, replace=False).astype(np.int64)
    a = np.sort(universe[:n])
    b = np.sort(universe[n // 2: n // 2 + n])
    p = next_pow2(n)
    assert p > si_kernel.SINGLE_PASS_MAX_P
    a_kh, a_kl = _padded_lanes(a, 1, PAD_A, p)
    b_kh, b_kl = _padded_lanes(b, 0, PAD_B, p)
    with mock.patch.object(si_kernel, "sorted_intersect_pallas",
                           side_effect=AssertionError(
                               "single-block kernel past its VMEM bound")), \
         mock.patch.object(si_ops, "sorted_intersect_pallas",
                           side_effect=AssertionError(
                               "single-block kernel past its VMEM bound")):
        out_t = si_ops.sorted_intersect.__wrapped__(
            a_kh, a_kl, b_kh, b_kl, impl="pallas")
    out_r = si_ref.sorted_intersect(a_kh, a_kl, b_kh, b_kl)
    for t, r in zip(out_t, out_r):
        assert np.array_equal(np.asarray(t), np.asarray(r))
    # and the decoded intersection is the numpy set intersection
    sel = np.asarray(out_t[0]).astype(bool)
    rank = np.asarray(out_t[1])
    got = np.sort(np.sort(a)[rank[sel] - 1])
    assert np.array_equal(got, np.intersect1d(a, b))
