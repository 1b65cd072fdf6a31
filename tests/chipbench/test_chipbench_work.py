"""Work counts and the peak table of the chip benchmark, against counts
made by hand."""
import pytest

import _tiny  # noqa: F401  (puts the benchmark on the path)
from chipbench import peaks
from chipbench.work import (kmeans_update, sorted_intersect, splitnn_bottom,
                            splitnn_model)


def test_sorted_intersect_counts_real_keys_by_hand():
    # 3 + 5 real keys: 8 compares; 8 B read and 8 B written per key
    assert sorted_intersect.count(8) == (8, 8 * 16)


@pytest.mark.parametrize("n_per_side", [70_000, 500_000])
def test_sorted_intersect_work_per_key_is_the_same_single_pass_or_tiled(
        n_per_side):
    # 70K per side merges at P = 2^17 in one pass, 500K at P = 2^19 on
    # the tiled path: the count sees only real keys, so work per key is
    # the same whatever implements the merge
    ops, nbytes = sorted_intersect.count(2 * n_per_side)
    assert ops / (2 * n_per_side) == 1
    assert nbytes / (2 * n_per_side) == 16


def test_kmeans_update_by_hand():
    n, d, k = 10, 3, 2
    flops = 2 * n * k * d + 2 * n * k * d + 4 * n * k      # 320
    nbytes = 4 * (n * d + k * d) + 4 * (2 * n + k * d + k)  # 144 + 112
    assert kmeans_update.count(n, d, k) == (flops, nbytes) == (320, 256)


def test_splitnn_bottom_by_hand():
    # 4 rows, parties of widths 2 and 3, 5 outputs each
    flops = 2 * 4 * (2 + 3) * 5                                  # 200
    nbytes = 4 * (4 * 5 + 5 * 5 + 2 * 5 + 4 * 2 * 5)            # 380
    assert splitnn_bottom.count(4, [2, 3], 5) == (flops, nbytes) == (200,
                                                                      380)


def test_split_model_flops_per_row_by_hand():
    # HI over 3 parties (11, 11, 10), bottom 8, hidden 64, one logit
    bottom = 2 * 32 * 8                      # 512
    top = 2 * 24 * 64 + 2 * 64 * 1           # 3200
    assert splitnn_model.forward_flops([11, 11, 10], 8, 64, 1) == 3712
    assert splitnn_model.train_flops([11, 11, 10], 8, 64, 1) == \
        2 * bottom + 3 * top == 10624


def test_peaks_of_v5e_and_roofline_bound():
    assert peaks.ops_peak("TPU v5 lite", "bfloat16") == 197e12
    assert peaks.ops_peak("TPU v5 lite", "int8") == 393e12
    assert peaks.device("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    # 819 MB in 2 ms is half of HBM's bound; its flops bound is far less
    share, bound = peaks.roofline(1e6, 819e6, 2e-3, "TPU v5 lite", "float32")
    assert bound == "hbm" and share == pytest.approx(50.0)
    share, bound = peaks.roofline(197e9, 1.0, 2e-3, "TPU v5 lite",
                                  "bfloat16")
    assert bound == "compute" and share == pytest.approx(50.0)
    # a merge is held to its bytes alone: 819 MB in 4 ms is a quarter
    assert peaks.hbm_roofline(819e6, 4e-3, "TPU v5 lite") == \
        pytest.approx(25.0)


def test_unknown_device_kind_raises():
    with pytest.raises(peaks.UnknownDevice):
        peaks.device("TPU v9 imaginary")
    with pytest.raises(peaks.UnknownDevice):
        peaks.ops_peak("cpu", "float32")
