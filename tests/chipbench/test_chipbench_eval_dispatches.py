"""The ``eval_dispatches`` metric: its reader against hand values on a
made-up ``Context``, the cells it is read in, and one ``serve.dispatch``
per pipeline job scoring its test rows, on tiny inputs on the CPU."""
import _tiny  # noqa: F401  (puts the benchmark on the path)
from chipbench.context import Context
from chipbench.registry import Registry
from conftest import make_cls_partition
from repro.config import EngineOptions
from repro.core.splitnn import SplitNNConfig
from repro.obs import Span, Tracer


def span(name, secs, **attrs):
    return Span(name=name, t0=1.0, t1=1.0 + secs, attrs=attrs)


def ctx_of(spans, n_jobs=2):
    return Context(config={}, device_kind="TPU v5 lite", spans=spans,
                   trace=None, jobs=[{}] * n_jobs, compile_s=0.0)


def parent_of(spans, sp):
    return next(s for s in spans if s.sid == sp.parent)


def test_eval_dispatches_by_hand():
    read = Registry().metric_reader("eval_dispatches")
    serve = [span("bench.job", 9.0), span("pipeline.serve", 0.7),
             span("pipeline.serve", 0.7)]
    # yp.treecss scored block by block: 299 blocks of 512 rows a job
    per_block = [span("serve.dispatch", 0.002, rows=512)] * 598
    assert read(ctx_of(serve + per_block)) == 299
    # the whole partition in one dispatch a job
    whole = [span("serve.dispatch", 0.05, rows=153_000, blocks=299)] * 2
    assert read(ctx_of(serve + whole)) == 1
    # jobs that never scored read nothing
    assert read(ctx_of([span("bench.job", 9.0)] + whole)) is None


def test_eval_dispatches_is_read_in_every_cell_that_scores():
    reg = Registry()
    for cell in ("hi.treecss", "yp.treecss"):
        assert "eval_dispatches" in {m["name"] for m in reg.per_layer(cell)}
    assert "eval_dispatches" not in {m["name"] for m in
                                     reg.per_layer("fig7.align10")}


def test_pipeline_scores_its_test_rows_in_one_dispatch():
    from repro.core import run_pipeline

    tr = make_cls_partition(n=120, d=9, seed=0)
    te = make_cls_partition(n=45, d=9, seed=5)
    cfg = SplitNNConfig(model="lr", n_classes=2, lr=0.05, batch_size=32,
                        max_epochs=2)
    tracer = Tracer()
    run_pipeline(tr, te, cfg, variant="treecss", clusters_per_client=4,
                 options=EngineOptions(trace=tracer, block_b=16))
    spans = tracer.finished()
    (dispatch,) = [s for s in spans if s.name == "serve.dispatch"]
    assert parent_of(spans, dispatch).name == "pipeline.serve"
    assert dispatch.attrs["blocks"] == 3                  # ceil(45 / 16)
    assert dispatch.attrs["rows"] == 45
    read = Registry().metric_reader("eval_dispatches")
    assert read(ctx_of(spans, n_jobs=1)) == 1
