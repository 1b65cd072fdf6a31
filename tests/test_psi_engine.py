"""Batched PSI round executor: parity with numpy set intersection over
ragged pair batches, both kernel impls and both sort modes."""
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # container image has no hypothesis
    from _propcheck import given, settings, strategies as st

from repro.psi import engine


def _pairs(seed, npairs=3, max_n=90):
    rng = np.random.default_rng(seed)
    senders, receivers, seeds, expect = [], [], [], []
    for _ in range(npairs):
        a = np.unique(rng.integers(0, 2**55, rng.integers(0, max_n),
                                   dtype=np.int64))
        b = np.unique(rng.integers(0, 2**55, rng.integers(0, max_n),
                                   dtype=np.int64))
        k = min(len(a), len(b)) // 2
        if k:
            b = np.unique(np.concatenate([a[:k], b]))
        senders.append(a)
        receivers.append(b)
        seeds.append((int(rng.integers(0, 2**32)),
                      int(rng.integers(0, 2**32))))
        expect.append(np.intersect1d(a, b))
    return senders, receivers, seeds, expect


@pytest.mark.parametrize("impl", ["ref", "pallas"])
@pytest.mark.parametrize("sort", ["host", "device"])
def test_oprf_round_matches_numpy(impl, sort):
    senders, receivers, seeds, expect = _pairs(seed=1)
    rnd = engine.oprf_round(senders, receivers, seeds, impl=impl,
                            sort=sort)
    assert rnd.dispatches == (1 if sort == "device" else 2)
    for got, exp in zip(rnd.intersections, expect):
        assert got.dtype == np.int64
        assert np.array_equal(got, exp)


@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_match_round_matches_numpy(impl):
    senders, receivers, _, expect = _pairs(seed=2)
    r_tags = [ids & engine.TAG_MASK for ids in receivers]
    s_tags = [ids & engine.TAG_MASK for ids in senders]
    rnd = engine.match_round(r_tags, receivers, s_tags, impl=impl)
    assert rnd.dispatches == 1
    for got, exp in zip(rnd.intersections, expect):
        assert np.array_equal(got, exp)


def test_empty_sets_and_empty_batch():
    empty = np.array([], np.int64)
    rnd = engine.oprf_round([empty], [empty], [(1, 2)])
    assert rnd.intersections[0].size == 0
    rnd = engine.oprf_round([empty], [np.arange(5, dtype=np.int64)],
                            [(1, 2)])
    assert rnd.intersections[0].size == 0
    rnd = engine.oprf_round([], [], [])
    assert rnd.intersections == [] and rnd.dispatches == 0
    rnd = engine.match_round([], [], [])
    assert rnd.intersections == [] and rnd.dispatches == 0


def test_seed_independence():
    """Different session seeds must not change the intersection."""
    senders, receivers, _, expect = _pairs(seed=3, npairs=2)
    for seeds in ([(0, 0), (1, 1)], [(123, 456), (789, 12)]):
        rnd = engine.oprf_round(senders, receivers, seeds, impl="ref")
        for got, exp in zip(rnd.intersections, expect):
            assert np.array_equal(got, exp)


def test_ragged_pair_sizes_share_one_batch():
    """Pairs of very different sizes pad to one (B, P) dispatch."""
    rng = np.random.default_rng(4)
    senders = [np.unique(rng.integers(0, 2**50, n, dtype=np.int64))
               for n in (3, 200)]
    receivers = [np.unique(rng.integers(0, 2**50, n, dtype=np.int64))
                 for n in (150, 7)]
    receivers = [np.unique(np.concatenate([s[:2], r]))
                 for s, r in zip(senders, receivers)]
    rnd = engine.oprf_round(senders, receivers, [(5, 6), (7, 8)],
                            impl="pallas")
    for got, s, r in zip(rnd.intersections, senders, receivers):
        assert np.array_equal(got, np.intersect1d(s, r))


def test_tag_words_is_62_bit():
    assert engine.tag_words(2**64 - 1) == 2**62 - 1
    assert engine.tag_words(12345) == 12345


def test_default_sort_keys_off_platform(monkeypatch):
    """The sort-mode default follows the ACTUAL backend, not the Pallas
    interpreter flag: real CPU gets numpy's radix-class sort (XLA's CPU
    multi-operand sort is ~30× slower), accelerators get lax.sort."""
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert engine._default_sort(None) == "host"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert engine._default_sort(None) == "device"
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    assert engine._default_sort(None) == "device"
    # an explicit mode always wins
    assert engine._default_sort("host") == "host"
    assert engine._default_sort("device") == "device"


def test_default_sort_independent_of_interpret_flag(monkeypatch):
    """Regression: the default used to key off the kernels' interpret
    switch, so a real (non-interpret) CPU run silently got the slow
    lax.sort path.  It keys off the backend alone."""
    import jax

    from repro.kernels import padding

    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    for interpret in (True, False):
        monkeypatch.setattr(padding, "interpret", lambda: interpret)
        assert engine._default_sort(None) == "host"


@settings(max_examples=10, deadline=None)
@given(st.sets(st.integers(0, 5000), max_size=50),
       st.sets(st.integers(0, 5000), max_size=50),
       st.integers(0, 2**31))
def test_property_oprf_round_set_semantics(sa, sb, seed_word):
    a = np.asarray(sorted(sa), np.int64)
    b = np.asarray(sorted(sb), np.int64)
    rnd = engine.oprf_round([a], [b], [(seed_word, seed_word ^ 0xABC)],
                            impl="pallas")
    assert np.array_equal(rnd.intersections[0],
                          np.asarray(sorted(sa & sb), np.int64))
