"""The trace reduction against values computed by hand on a small
hand-made trace, and on a trace recorded on the chip."""
import gzip
import json
from pathlib import Path

import pytest

import _tiny  # noqa: F401  (puts the benchmark on the path)
from chipbench import trace_reduce as tr
from chipbench.trace_reduce import Event, Trace


def hand_trace() -> Trace:
    # window 100..200 ns; device 0 ops overlap at 120-140 and 130-150,
    # one op straddles the window's end; device 1 is busy 100-110
    # op events carry their HLO instruction's name: kernel, then suffix
    d0 = [Event("fusion.1", 90, 105), Event("kmeans_update.3", 120, 140),
          Event("kmeans_update.12", 130, 150),
          Event("splitnn_bottom_gather.1", 170, 180),
          Event("splitnn_bottom.2", 195, 230)]
    d1 = [Event("fusion.2", 100, 110)]
    host = [Event("bench.job", 100, 200), Event("pipeline.run", 101, 199),
            Event("pipeline.align", 101, 160), Event("align.round", 150, 158),
            Event("pipeline.train", 160, 199)]
    return Trace({0: d0, 1: d1}, host)


def test_busy_union_by_hand():
    red = tr.reduce_trace(hand_trace(), n_devices=1)
    assert (red.lo, red.hi) == (100, 200)
    # [100,105] + [120,150] + [170,180] + [195,200] = 5 + 30 + 10 + 5
    assert red.busy_s == pytest.approx(50e-9)
    assert red.window_s == pytest.approx(100e-9)
    two = tr.reduce_trace(hand_trace(), n_devices=2)
    assert two.busy_s == pytest.approx((50e-9 + 10e-9) / 2)


def test_kernel_time_by_name_by_hand():
    red = tr.reduce_trace(hand_trace(), n_devices=1)
    assert red.kernel_seconds(["kmeans_update"]) == pytest.approx(40e-9)
    # the straddling op lies outside the window and is not counted
    assert red.kernel_seconds(["splitnn_bottom"], prefix=True) == \
        pytest.approx(10e-9)
    assert red.kernel_seconds(["splitnn_bottom"]) == 0.0


def test_idle_gaps_labelled_by_host_span_by_hand():
    red = tr.reduce_trace(hand_trace(), n_devices=1)
    gaps = [(label, s, n) for label, s, n in red.gaps()]
    # idle: 105-120 (align), 150-170 (mid 160: align ends at 160, train
    # starts there; train started last), 180-195 (train)
    assert gaps == [("pipeline.align", 105, 15), ("pipeline.train", 150, 20),
                    ("pipeline.train", 180, 15)]
    top = dict(red.top_gaps(10))
    assert top == pytest.approx({"pipeline.train": 35e-9,
                                 "pipeline.align": 15e-9})
    assert red.top_ops(2)[0] == ["kmeans_update", pytest.approx(40e-9)]


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce_trace(Trace({0: []}, [Event("pipeline.run", 0, 1)]))



# ---------------------------------------------------- a trace from the chip
# One whole hi.treecss job traced on a TPU v5e (``--trace 1``), cut by
# ``chipbench/testdata/trim_trace.py``.  The expected values were
# computed by hand from the events: a sweep over the sorted op intervals
# for the busy union, sums of the named kernels' events, and the host
# spans open at the middle of the longest idle gaps.
CHIP = Path(__file__).resolve().parents[2] / "chipbench" / "testdata" / \
    "hi_treecss_job.json.gz"


@pytest.fixture(scope="module")
def chip():
    with gzip.open(CHIP, "rt") as f:
        return tr.reduce_trace(Trace.from_json(json.load(f)), n_devices=1)


def test_chip_trace_busy_union(chip):
    assert chip.window_s == pytest.approx(0.99233495)
    assert chip.busy_s == pytest.approx(40117384e-9)
    idle = sum(n for _, _, n in chip.gaps())
    assert idle * 1e-9 == pytest.approx(chip.window_s - chip.busy_s)


@pytest.mark.parametrize("kernel,seconds", [
    ("kmeans_update", 14027246e-9),       # 25 Lloyd iterations
    ("kmeans_assign", 528637e-9),
    ("sorted_intersect", 69252e-9),       # the 2^17 single-pass merge
    # the training forward under autodiff (jvp_splitnn_bottom_gather_)
    # and the scoring kernel (splitnn_bottom)
    ("splitnn_bottom", 95318e-9),
])
def test_chip_trace_kernel_time_by_name(chip, kernel, seconds):
    assert chip.kernel_seconds([kernel], prefix=True) == \
        pytest.approx(seconds)


def test_chip_trace_idle_gaps_labelled_by_host_span(chip):
    longest = sorted(chip.gaps(), key=lambda g: -g[2])[:3]
    # 550 ms without a device op while k-means is made (coreset.fit),
    # then the coreset's selection and the alignment's HE broadcast
    assert [(label, s, n) for label, s, n in longest] == [
        ("coreset.fit", 109820291.0, 549878555.0),
        ("coreset.select", 678154243.0, 88817472.0),
        ("align.broadcast", 50302169.0, 56039746.0)]
    assert chip.top_gaps(1)[0][0] == "coreset.fit"


def test_chip_trace_top_ops_leave_loops_out(chip):
    names = [n for n, _ in chip.top_ops(10)]
    assert "while" not in names
    assert dict(chip.top_ops(10))["kmeans_update"] == \
        pytest.approx(14027246e-9)
