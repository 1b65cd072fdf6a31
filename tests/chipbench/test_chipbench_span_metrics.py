"""The spans and counters the per-layer metrics read, on tiny inputs on
the CPU: where Tree-MPSI, the coreset fit and training open them, the
padding counts every ``align.dispatch`` carries against hand
arithmetic, and each metric reader against hand values on a made-up
``Context``."""
import numpy as np
import pytest

import _tiny  # noqa: F401  (puts the benchmark on the path)
from chipbench.context import Context
from chipbench.registry import Registry
from chipbench.trace_reduce import SPAN_NAME
from conftest import make_cls_partition
from repro.config import AlignOptions, EngineOptions
from repro.core.coreset import cluster_coreset
from repro.core.mpsi import tree_mpsi
from repro.core.splitnn import SplitNNConfig, train_splitnn
from repro.obs import Span, Tracer, use_tracer

NEW_SPANS = ("align.ids", "align.canonical", "align.pack", "align.recover",
             "coreset.pack", "coreset.compile", "coreset.weights",
             "train.setup")


def five_parties():
    """Five parties around a core of 100 ids, each with ids of its own:
    110, 130, 160, 250 and 300 ids."""
    core = np.arange(100)
    extra = [10, 30, 60, 150, 200]
    start = np.cumsum([1000] + extra[:-1])
    return [np.concatenate([core, s + np.arange(e)])
            for s, e in zip(start, extra)]


# Volume-aware OPRF Tree-MPSI over five_parties(): round 1 pairs
# (110, 250) and (130, 300) at P = 512, 160 passes; round 2 pairs the
# 100-id core with the 160 at P = 256; round 3 the two cores at P = 128.
# keys are both sides' real ids, slots 2 x rows x P.
ROUNDS = [(110 + 250 + 130 + 300, 2 * 2 * 512),
          (100 + 160, 2 * 1 * 256),
          (100 + 100, 2 * 1 * 128)]


def traced_tree_mpsi(sort):
    tracer = Tracer()
    with use_tracer(tracer):
        st = tree_mpsi(five_parties(), options=AlignOptions(
            protocol="oprf", psi_backend="device", sort=sort, impl="ref"))
    np.testing.assert_array_equal(st.intersection, np.arange(100))
    return tracer.finished()


def parent_of(spans, sp):
    return next(s for s in spans if s.sid == sp.parent)


@pytest.mark.parametrize("sort,per_round", [("device", 1), ("host", 2)])
def test_tree_mpsi_spans_and_padding_counts_by_hand(sort, per_round):
    spans = traced_tree_mpsi(sort)
    names = [s.name for s in spans]
    assert names.count("align.canonical") == 1
    assert names.index("align.canonical") < names.index("align.round")
    for name in ("align.pack", "align.recover", "align.dispatch"):
        assert names.count(name) == 3 * per_round
        for sp in spans:
            if sp.name == name:
                assert parent_of(spans, sp).name == "align.round"
    dispatches = [(s.attrs["keys"], s.attrs["slots"]) for s in spans
                  if s.name == "align.dispatch"]
    assert dispatches == [r for r in ROUNDS for _ in range(per_round)]
    keys = sum(k for k, _ in dispatches)
    assert keys == per_round * 1250
    assert sum(s for _, s in dispatches) == per_round * 2816
    if sort == "host":
        for sp in spans:
            if sp.name == "align.host_sort":
                assert parent_of(spans, sp).name == "align.pack"


def test_coreset_fit_and_train_setup_spans():
    part = make_cls_partition(n=96, d=12, seed=3)
    tracer = Tracer()
    cfg = SplitNNConfig(model="lr", n_classes=2, lr=0.05, batch_size=32,
                        max_epochs=2)
    with use_tracer(tracer):
        cluster_coreset(part, 4, seed=0, kmeans_impl="ref")
        train_splitnn(part, cfg, options=EngineOptions(train_engine="scan"))
    spans = tracer.finished()
    names = [s.name for s in spans]
    fit = [s for s in spans if s.name == "coreset.fit"]
    assert len(fit) == 1
    steps = [s for s in spans if s.parent == fit[0].sid]
    assert [s.name for s in steps] == ["coreset.pack", "coreset.compile",
                                       "coreset.weights"]
    (setup,), (compile_,) = (
        [s for s in spans if s.name == n]
        for n in ("train.setup", "train.compile"))
    assert setup.t1 <= compile_.t0
    assert names.index("train.setup") < names.index("train.compile")


def test_align_ids_under_pipeline_align_and_every_new_name_kept():
    from repro.core import run_pipeline

    tr = make_cls_partition(n=120, d=9, seed=0)
    te = make_cls_partition(n=45, d=9, seed=5)
    cfg = SplitNNConfig(model="lr", n_classes=2, lr=0.05, batch_size=32,
                        max_epochs=2)
    tracer = Tracer()
    run_pipeline(tr, te, cfg, variant="treecss", clusters_per_client=4,
                 align=AlignOptions(protocol="oprf", psi_backend="device"),
                 options=EngineOptions(trace=tracer))
    spans = tracer.finished()
    (ids,) = [s for s in spans if s.name == "align.ids"]
    assert parent_of(spans, ids).name == "pipeline.align"
    assert set(NEW_SPANS) <= {s.name for s in spans}
    # the trace reduction labels idle gaps only by names of this form
    for name in NEW_SPANS:
        assert SPAN_NAME.match(name), name
    for sp in spans:
        assert SPAN_NAME.match(sp.name), sp.name


# ------------------------------------------------------- metric readers

def span(name, secs, **attrs):
    return Span(name=name, t0=1.0, t1=1.0 + secs, attrs=attrs)


def ctx_of(spans, n_jobs=2):
    return Context(config={}, device_kind="TPU v5 lite", spans=spans,
                   trace=None, jobs=[{}] * n_jobs, compile_s=0.0)


@pytest.mark.parametrize("metric,name", [
    ("align_canonical_s", "align.canonical"),
    ("align_pack_s", "align.pack"),
    ("align_recover_s", "align.recover"),
    ("coreset_compile_s", "coreset.compile"),
    ("train_setup_s", "train.setup")])
def test_span_seconds_per_job_by_hand(metric, name):
    read = Registry().metric_reader(metric)
    spans = [span("bench.job", 9.0), span(name, 0.25), span(name, 0.5),
             span(name, 0.75), span("align.dispatch", 4.0)]
    assert read(ctx_of(spans)) == pytest.approx(1.5 / 2)
    assert read(ctx_of([span("bench.job", 9.0)])) is None


def test_align_pad_share_by_hand():
    read = Registry().metric_reader("align_pad_share")
    # the fig7-10p alignment: 5 pairs of 500K at P = 2^19, 2 pairs of
    # 350K, then 1 pair twice: 7,800,000 keys in 9,437,184 slots
    rounds = [(5_000_000, 2 * 5 * 2**19), (1_400_000, 2 * 2 * 2**19),
              (700_000, 2 * 1 * 2**19), (700_000, 2 * 1 * 2**19)]
    spans = [span("align.dispatch", 0.1, kind="single", keys=k, slots=s)
             for k, s in rounds]
    share = read(ctx_of(spans + spans))
    assert share == pytest.approx(100 * (1 - 7_800_000 / 9_437_184))
    assert round(share, 2) == 17.35
    # dispatch spans without the counts read nothing
    assert read(ctx_of([span("align.dispatch", 0.1, kind="single")])) \
        is None
    assert read(ctx_of([])) is None


def test_new_metrics_are_read_in_the_cells_that_report_what_they_move():
    reg = Registry()
    align = {m["name"] for m in reg.per_layer("fig7.align10")}
    pipeline = {m["name"] for m in reg.per_layer("hi.treecss")}
    new_align = {"align_canonical_s", "align_pack_s", "align_recover_s",
                 "align_pad_share"}
    new_pipeline = {"coreset_compile_s", "train_setup_s"}
    assert new_align <= align and not new_align & pipeline
    assert new_pipeline <= pipeline and not new_pipeline & align


def test_trace_cost_times_each_setup_in_turn(tmp_path):
    from chipbench import trace_cost

    class Job:
        def __init__(self):
            self.tracers = []

        def run(self):
            from repro.obs.trace import active_tracer, span
            self.tracers.append(active_tracer())
            with span("align.round"):
                pass

    job = Job()
    times = trace_cost.measure(job, 2, tmp_path / "trace")
    assert set(times) == set(trace_cost.MODES)
    assert all(len(v) == 2 and min(v) >= 0 for v in times.values())
    kinds = [None if t is None else t.jax_profiler for t in job.tracers]
    assert kinds == [None, False, True] * 2
    assert [len(t.spans) for t in job.tracers if t is not None] == [1] * 4
    assert not (tmp_path / "trace").exists()
