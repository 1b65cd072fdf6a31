"""Synthetic data, vertical partitioning, HLO collective parsing."""
import numpy as np
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # container image has no hypothesis
    from _propcheck import given, settings, strategies as st

from repro.analysis.hlo import parse_hlo_collectives
from repro.data.synthetic import DATASETS, make_dataset, make_id_universe
from repro.data.vertical import partition_features


# ------------------------------------------------------------------- data

def test_dataset_signatures_match_table1():
    expect = {"BA": (10_000, 11, 2), "MU": (8_000, 22, 2),
              "RI": (18_000, 11, 2), "HI": (100_000, 32, 2),
              "BP": (13_000, 11, 4), "YP": (510_000, 90, 0)}
    for name, (n, d, c) in expect.items():
        spec = DATASETS[name]
        assert (spec.n_instances, spec.n_features, spec.n_classes) == (n, d, c)


def test_make_dataset_shapes():
    x, y = make_dataset(DATASETS["BA"], seed=0, n_override=500)
    assert x.shape == (500, 11) and y.shape == (500,)
    assert set(np.unique(y)) <= {0, 1}
    x, y = make_dataset(DATASETS["YP"], seed=0, n_override=300)
    assert y.dtype == np.float32          # regression


@settings(max_examples=15, deadline=None)
@given(st.integers(2, 8), st.integers(20, 200), st.floats(0.1, 0.95),
       st.integers(0, 99))
def test_property_id_universe(m, n, overlap, seed):
    sets, core = make_id_universe(m, n, overlap, seed=seed)
    assert len(sets) == m
    core_set = set(core.tolist())
    for s in sets:
        assert len(s) == n
        assert core_set <= set(s.tolist())
    inter = set(sets[0].tolist())
    for s in sets[1:]:
        inter &= set(s.tolist())
    assert inter == core_set              # EXACT intersection == core
    assert len(core) == int(round(n * overlap))


def test_vertical_partition_covers_features():
    x = np.arange(40.0, dtype=np.float32).reshape(4, 10)
    y = np.zeros(4, np.int64)
    part = partition_features(x, y, 3)
    rebuilt = np.concatenate(part.client_features, axis=1)
    np.testing.assert_array_equal(rebuilt, x)
    sizes = [f.shape[1] for f in part.client_features]
    assert max(sizes) - min(sizes) <= 1   # equal split


# ------------------------------------------------------------ HLO analysis

def test_collective_parser():
    hlo = """
ENTRY %main {
  %ag = bf16[16,512]{1,0} all-gather(%x), replica_groups=...
  %ar = f32[1024]{0} all-reduce(%y), to_apply=%sum
  %aa = (f32[8,4]{1,0}, f32[8,4]{1,0}) all-to-all(%a, %b)
}
"""
    out = parse_hlo_collectives(hlo)
    assert out["all-gather"]["bytes"] == 16 * 512 * 2
    assert out["all-reduce"]["bytes"] == 1024 * 4 * 2   # counted 2x
    assert out["all-to-all"]["bytes"] == 2 * 8 * 4 * 4
