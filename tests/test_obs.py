"""Observability layer (repro.obs, DESIGN.md §10): span nesting and
attributes, Chrome-trace export schema + validator, metrics registry
typing/threading/merge, StatsMixin surface, and the zero-overhead
regression — tracing enabled leaves every engine/scheduler counter
unchanged, tracing disabled costs a singleton no-op."""
import json
import threading

import numpy as np
import pytest

from conftest import make_cls_partition
from repro.core import SplitNNConfig, run_pipeline
from repro.core import splitnn as models
from repro.core.splitnn import train_splitnn
from repro.obs import (Counter, Gauge, Histogram, MetricsRegistry,
                       StatsMixin, TraceValidationError, Tracer,
                       chrome_trace, span, use_tracer,
                       validate_chrome_trace, write_chrome_trace)
from repro.obs.trace import NULL_SPAN, active_tracer
from repro.serve.vfl import (ScoreRequest, ServeStats, VFLScoringEngine,
                             simulate_trace)


# ------------------------------------------------------------ span tracing

def test_span_nesting_and_attrs():
    """Nested spans record parent sid / depth, late .set() attrs land on
    the finished record, and finished() is start-ordered."""
    tracer = Tracer()
    with use_tracer(tracer):
        with span("pipeline.run", variant="treecss") as outer:
            with span("train.epoch", epoch=0) as inner:
                inner.set(loss=0.5)
            outer.set(comm_bytes=128)
    spans = tracer.finished()
    assert [s.name for s in spans] == ["pipeline.run", "train.epoch"]
    by_name = {s.name: s for s in spans}
    run, ep = by_name["pipeline.run"], by_name["train.epoch"]
    assert ep.parent == run.sid and run.parent == -1
    assert (run.depth, ep.depth) == (0, 1)
    assert ep.attrs == {"epoch": 0, "loss": 0.5}
    assert run.attrs == {"variant": "treecss", "comm_bytes": 128}
    assert run.t0 <= ep.t0 and ep.t1 <= run.t1
    assert run.duration >= ep.duration >= 0.0


def test_disabled_span_is_shared_noop_singleton():
    """With no active tracer, span() is one global load + is-None check:
    the SAME no-op object every time, swallowing everything."""
    assert active_tracer() is None
    s1 = span("train.epoch", epoch=0)
    s2 = span("serve.dispatch")
    assert s1 is s2 is NULL_SPAN
    with s1 as h:
        h.set(anything=1)
    assert s1.duration == 0.0


def test_use_tracer_restores_previous():
    outer, inner = Tracer(), Tracer()
    with use_tracer(outer):
        assert active_tracer() is outer
        with use_tracer(inner):
            assert active_tracer() is inner
        assert active_tracer() is outer
        with use_tracer(None):      # pass-through, no-op
            assert active_tracer() is outer
    assert active_tracer() is None


def test_threads_get_independent_nesting_one_timeline():
    """Open-span stacks are per-thread (parentage can't cross threads)
    while all finished spans land on the one tracer."""
    tracer = Tracer()
    barrier = threading.Barrier(4)      # hold all alive: idents stay unique

    def work(i):
        barrier.wait()
        with tracer.span("serve.admit", worker=i):
            with tracer.span("serve.dispatch", worker=i):
                pass
        barrier.wait()

    threads = [threading.Thread(target=work, args=(i,)) for i in range(4)]
    with use_tracer(tracer):
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    spans = tracer.finished()
    assert len(spans) == 8
    for sp in spans:
        if sp.name == "serve.dispatch":
            parent = next(s for s in spans if s.sid == sp.parent)
            assert parent.name == "serve.admit"
            assert parent.tid == sp.tid       # nesting never crosses lanes
    assert len({s.tid for s in spans}) == 4


# ------------------------------------------------------------ trace export

def _toy_tracer():
    tracer = Tracer()
    with use_tracer(tracer):
        with span("pipeline.run"):
            for cat in ("align", "coreset", "train", "serve"):
                with span(f"{cat}.step", comm_bytes=64, mesh=(2, 4)):
                    pass
    return tracer


def test_chrome_trace_schema_and_validator():
    doc = chrome_trace(_toy_tracer())
    n = validate_chrome_trace(
        doc, require_cats=("align", "coreset", "train", "serve"))
    assert n == 5
    ev = doc["traceEvents"][0]
    assert set(ev) == {"name", "cat", "ph", "ts", "dur", "pid", "tid",
                       "args"}
    assert ev["ph"] == "X" and ev["ts"] >= 0 and ev["dur"] >= 0
    # attrs fold to JSON-native values (mesh tuple -> "2x4")
    args = next(e["args"] for e in doc["traceEvents"]
                if e["name"] == "train.step")
    assert args == {"comm_bytes": 64, "mesh": "2x4"}
    # the document is pure-JSON serializable as written
    json.loads(json.dumps(doc))


def test_validator_rejects_malformed():
    with pytest.raises(TraceValidationError):
        validate_chrome_trace({"events": []})
    doc = chrome_trace(_toy_tracer())
    with pytest.raises(TraceValidationError, match="required stage"):
        validate_chrome_trace(doc, require_cats=("nonexistent",))
    bad = {"traceEvents": [{"name": "x", "ph": "B", "ts": 0, "dur": 0,
                            "pid": 1, "tid": 1}]}
    with pytest.raises(TraceValidationError, match="ph"):
        validate_chrome_trace(bad)
    bad = {"traceEvents": [{"name": "x", "ph": "X", "ts": -5, "dur": 0,
                            "pid": 1, "tid": 1}]}
    with pytest.raises(TraceValidationError, match="ts"):
        validate_chrome_trace(bad)
    # partial overlap within one lane = corrupted nesting
    bad = {"traceEvents": [
        {"name": "a", "ph": "X", "ts": 0, "dur": 10, "pid": 1, "tid": 1},
        {"name": "b", "ph": "X", "ts": 5, "dur": 10, "pid": 1, "tid": 1}]}
    with pytest.raises(TraceValidationError, match="overlap"):
        validate_chrome_trace(bad)


def test_export_files_and_view_cli(tmp_path):
    from repro.obs.view import view
    tracer = _toy_tracer()
    trace_path = str(tmp_path / "trace.json")
    doc = write_chrome_trace(tracer, trace_path)
    with open(trace_path) as f:
        assert json.load(f) == json.loads(json.dumps(doc))
    assert {e["name"] for e in doc["traceEvents"]} == {
        "pipeline.run", "align.step", "coreset.step", "train.step",
        "serve.step"}
    # the CI gate: view() exits 0 on a good trace, 1 on schema violations
    assert view(trace_path, require_cats=("align", "serve")) == 0
    assert view(trace_path, require_cats=("nonexistent",)) == 1
    bad_path = str(tmp_path / "bad.json")
    with open(bad_path, "w") as f:
        json.dump({"traceEvents": [{"name": "x"}]}, f)
    assert view(bad_path) == 1


# ------------------------------------------------------------ registry

def test_registry_typed_get_or_create():
    reg = MetricsRegistry()
    c = reg.counter("train.dispatches")
    assert reg.counter("train.dispatches") is c
    with pytest.raises(TypeError):
        reg.gauge("train.dispatches")
    c.inc(3)
    reg.gauge("train.loss").set(0.25)
    reg.histogram("serve.svc_s").observe(2e-3)
    snap = reg.snapshot()
    assert snap["train.dispatches"] == 3
    assert snap["train.loss"] == 0.25
    assert snap["serve.svc_s"]["count"] == 1
    with pytest.raises(ValueError):
        c.inc(-1)


def test_histogram_nearest_rank_percentiles():
    h = Histogram("t")
    for v in (1.0, 2.0, 3.0, 4.0):
        h.observe(v)
    assert h.percentile(50) == 2.0      # ceil(0.5*4) = 2nd sample
    assert h.percentile(99) == 4.0
    assert h.percentile(1) == 1.0
    assert Histogram("empty").percentile(50) == 0.0
    s = h.snapshot()
    assert s == {"count": 4, "sum": 10.0, "min": 1.0, "max": 4.0,
                 "p50": 2.0, "p99": 4.0}


def test_registry_exact_under_threads_and_merge():
    """8 threads × 1000 incs lose nothing; per-thread registries fold
    with counters adding, gauges last-write, histograms concatenating."""
    shared = MetricsRegistry()

    def work():
        for _ in range(1000):
            shared.counter("hits").inc()

    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert shared.snapshot()["hits"] == 8000

    a, b = MetricsRegistry(), MetricsRegistry()
    a.counter("n").inc(2)
    b.counter("n").inc(3)
    a.gauge("g").set(1.0)
    b.gauge("g").set(9.0)
    a.histogram("h").observe(1.0)
    b.histogram("h").observe(2.0)
    a.merge(b)
    snap = a.snapshot()
    assert snap["n"] == 5 and snap["g"] == 9.0
    assert snap["h"]["count"] == 2 and snap["h"]["sum"] == 3.0


def test_stats_mixin_surface():
    import dataclasses

    @dataclasses.dataclass
    class S(StatsMixin):
        dispatches: int = 7
        wall_s: float = 1.5
        fused: bool = True
        engine: str = "scan"
        samples: list = dataclasses.field(default_factory=list)
        CONTRACT_FIELDS = ("dispatches",)

    s = S()
    assert s.to_dict() == {"dispatches": 7, "wall_s": 1.5, "fused": 1,
                           "engine": "scan"}
    assert s.as_row(S.CONTRACT_FIELDS) == {"dispatches": 7}
    assert s.as_row(("dispatches",), prefix="train.") == {
        "train.dispatches": 7}
    reg = MetricsRegistry()
    s.emit(reg, "train.")
    snap = reg.snapshot()
    assert snap["train.dispatches"] == 7
    assert snap["train.wall_s"] == 1.5
    assert snap["train.fused"] == 1
    assert "train.engine" not in snap       # strings don't emit
    assert "train.samples" not in snap


def test_contract_fields_live_on_the_dataclasses():
    """The CI gate imports its serve field list from the dataclass —
    assert the declarations it pins exist and stay scalar."""
    from benchmarks.check_contract import SERVE_FIELDS
    from repro.train.vfl import EngineStats
    assert SERVE_FIELDS is ServeStats.CONTRACT_FIELDS
    st = ServeStats()
    assert set(ServeStats.CONTRACT_FIELDS) <= set(st.to_dict())
    es = EngineStats()
    assert set(EngineStats.CONTRACT_FIELDS) <= set(es.to_dict())


# ------------------------------------------------ zero-overhead regression

def _train(tracer):
    tr = make_cls_partition(n=192, d=12, seed=0)
    cfg = SplitNNConfig(model="lr", n_classes=2, lr=0.05, batch_size=64,
                        max_epochs=4)
    with use_tracer(tracer):
        rep = train_splitnn(tr, cfg, engine="scan")
    return rep


def _align(tracer, monkeypatch):
    """A tiny device-backend Tree-MPSI; returns its stats and the number
    of blocking host syncs the PSI engine made."""
    import jax

    from repro.config import AlignOptions
    from repro.core.mpsi import tree_mpsi
    from repro.data.synthetic import make_id_universe

    sets, _ = make_id_universe(5, 300, 0.7, seed=4)
    syncs = []
    block = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: syncs.append(1) or block(x))
    with use_tracer(tracer):
        st = tree_mpsi(sets, options=AlignOptions(
            protocol="oprf", psi_backend="device", impl="ref"))
    monkeypatch.undo()
    return st, len(syncs)


def test_tracing_leaves_engine_contract_unchanged(monkeypatch):
    """The scan engine's ONE-dispatch + ONE-host-sync-per-epoch contract
    holds bit-for-bit with tracing on, and the traced run's span counts
    line up with the counters; the set-up, packing and recovery spans
    leave the training and PSI engines' dispatches and host syncs as
    they are."""
    base = _train(None)
    tracer = Tracer()
    traced = _train(tracer)
    es0, es1 = base.engine_stats, traced.engine_stats
    assert es0.to_dict() == es1.to_dict()
    assert es1.dispatches == es1.host_syncs == traced.epochs
    assert np.allclose(base.losses, traced.losses)
    epochs = tracer.by_name("train.epoch")
    assert len(epochs) == traced.epochs
    assert len(tracer.by_name("train.compile")) == 1
    assert len(tracer.by_name("train.setup")) == 1
    # per-epoch attrs carry the modeled comm volume and the loss
    assert all(s.attrs["comm_bytes"] > 0 and "loss" in s.attrs
               for s in epochs)

    _align(None, monkeypatch)            # every bucket compiled once
    st0, syncs0 = _align(None, monkeypatch)
    tracer = Tracer()
    st1, syncs1 = _align(tracer, monkeypatch)
    assert syncs0 == syncs1 > 0
    assert st0.device_dispatches == st1.device_dispatches == st1.rounds
    np.testing.assert_array_equal(st0.intersection, st1.intersection)
    assert len(tracer.by_name("align.dispatch")) == syncs1
    assert len(tracer.by_name("align.recover")) == syncs1


def test_tracing_leaves_serve_counters_unchanged():
    """Scheduler counters are bitwise-identical traced vs untraced, and
    serve.dispatch spans match the dispatch counter."""
    part = make_cls_partition(n=60, d=12, seed=1)
    cfg = SplitNNConfig(model="lr", n_classes=2)
    params = models.init_splitnn(
        cfg, [f.shape[1] for f in part.client_features])
    rng = np.random.default_rng(0)
    t, trace = 0.0, []
    for rid in range(30):
        t += float(rng.exponential(0.004))
        idx = rng.integers(0, part.n_samples, size=int(rng.integers(1, 4)))
        trace.append(ScoreRequest(
            rid=rid, arrival=t,
            features=[f[idx] for f in part.client_features]))

    def run(tracer):
        eng = VFLScoringEngine(params, cfg, slots=8)
        with use_tracer(tracer):
            return simulate_trace(eng, trace, policy="continuous",
                                  service_seconds=2e-3)

    base = run(None)
    tracer = Tracer()
    traced = run(tracer)
    assert base.stats.as_row(ServeStats.CONTRACT_FIELDS) == \
        traced.stats.as_row(ServeStats.CONTRACT_FIELDS)
    assert base.latencies == traced.latencies
    dispatch_spans = tracer.by_name("serve.dispatch")
    assert len(dispatch_spans) == traced.stats.dispatches
    assert sum(s.attrs["rows"] for s in dispatch_spans) == \
        traced.stats.occupancy_sum


# -------------------------------------------- satellites: walls + hists

def test_serve_service_histograms():
    """simulate_trace keeps BOTH distributions: the virtual-clock
    service times (deterministic — every sample the fixed value) and
    the measured per-dispatch wall times (no longer discarded)."""
    part = make_cls_partition(n=60, d=12, seed=1)
    cfg = SplitNNConfig(model="lr", n_classes=2)
    params = models.init_splitnn(
        cfg, [f.shape[1] for f in part.client_features])
    rng = np.random.default_rng(2)
    t, trace = 0.0, []
    for rid in range(20):
        t += float(rng.exponential(0.004))
        idx = rng.integers(0, part.n_samples, size=2)
        trace.append(ScoreRequest(
            rid=rid, arrival=t,
            features=[f[idx] for f in part.client_features]))
    eng = VFLScoringEngine(params, cfg, slots=8)
    sim = simulate_trace(eng, trace, policy="continuous",
                         service_seconds=2e-3)
    n = sim.stats.dispatches
    assert sim.service_hist.count == n == sim.wall_hist.count
    assert sim.service_hist.percentile(50) == 2e-3
    assert sim.service_hist.percentile(99) == 2e-3
    assert sim.wall_hist.sum > 0.0          # real measured slab forwards
    assert sim.wall_hist.snapshot()["min"] > 0.0


def test_pipeline_walls_and_trace(tmp_path):
    """One traced run_pipeline emits all four stage categories on a
    valid Chrome trace; the new coreset/train wall fields are measured;
    emit_metrics snapshot agrees with the dataclasses."""
    tr = make_cls_partition(n=120, d=9, seed=0)
    te = make_cls_partition(n=45, d=9, seed=5)
    cfg = SplitNNConfig(model="lr", n_classes=2, lr=0.05, batch_size=32,
                        max_epochs=3)
    tracer = Tracer()
    rep = run_pipeline(tr, te, cfg, variant="treecss",
                       clusters_per_client=6, protocol="oprf",
                       trace=tracer)
    assert rep.tracer is tracer
    assert rep.coreset_wall_seconds > 0.0
    assert rep.train_wall_seconds > 0.0
    assert rep.align_wall_seconds > 0.0
    cats = {s.name.split(".", 1)[0] for s in tracer.finished()}
    assert {"pipeline", "align", "coreset", "train", "serve"} <= cats
    doc = write_chrome_trace(tracer, str(tmp_path / "t.json"))
    validate_chrome_trace(
        doc, require_cats=("align", "coreset", "train", "serve"))
    reg = MetricsRegistry()
    rep.emit_metrics(reg)
    snap = reg.snapshot()
    assert snap["train.dispatches"] == rep.train.engine_stats.dispatches
    assert snap["pipeline.n_train"] == rep.n_train
    assert snap["pipeline.coreset_wall_seconds"] == rep.coreset_wall_seconds
    assert snap["coreset.n_coreset"] == rep.n_train
    # untraced: no tracer attached, walls still measured off now()
    rep2 = run_pipeline(tr, te, cfg, variant="starall", protocol="oprf")
    assert rep2.tracer is None
    assert rep2.coreset_wall_seconds == 0.0     # ALL variant: no coreset
    assert rep2.train_wall_seconds > 0.0
