"""Continuous-batching VFL scoring engine (repro.serve.vfl, DESIGN.md
§9): scheduler admission/occupancy properties, streamed-vs-oneshot
scoring parity against a precision-"highest" f32 forward (bitwise only
where the construction guarantees it: rows scored by the same program
shape are independent of the other rows in their batch), out-of-order
completion bookkeeping, ServeStats counters, and the trace simulator's
continuous-vs-blocking tail-latency property."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import make_cls_partition
from repro.core import splitnn as models
from repro.core.splitnn import SplitNNConfig, evaluate, predict, train_splitnn
from repro.serve.vfl import (ScoreRequest, ServeStats, VFLScoringEngine,
                             score_partition, simulate_trace)


def _setup(model="mlp", n_classes=4, n=96, d=11, seed=1):
    part = make_cls_partition(n=n, d=d, classes=max(n_classes, 2), seed=seed)
    cfg = SplitNNConfig(model=model, n_classes=n_classes)
    fd = [f.shape[1] for f in part.client_features]
    params = models.init_splitnn(cfg, fd)
    return part, cfg, params


def _oneshot(params, cfg, part):
    """The plain reference: the zoo forward in f32, matmuls at precision
    "highest"."""
    xs = [jnp.asarray(f, jnp.float32) for f in part.client_features]
    with jax.default_matmul_precision("highest"):
        return np.asarray(models.splitnn_forward(params, cfg, xs))


# Served scores vs the reference: both are f32, but each batch shape
# sums its ≤ 64-term dot products in an order XLA picks for that shape,
# so logits of magnitude ~1 drift by a few ulps (observed ≤ 2e-7).
# 1e-5 leaves ~50x headroom and still fails on a wrong row, client,
# parameter or padding leak, which move a logit by 1e-2 or more.
RTOL = ATOL = 1e-5


def _close(got, ref):
    np.testing.assert_allclose(got, ref, rtol=RTOL, atol=ATOL)


# ------------------------------------------------------------ score parity

@pytest.mark.parametrize("model,n_classes", [("lr", 2), ("lr", 3),
                                             ("mlp", 4), ("linreg", 0)])
@pytest.mark.parametrize("bottom_impl", ["ref", "pallas"])
def test_score_partition_bitwise(model, n_classes, bottom_impl):
    """Fixed-shape batched scoring (full batches AND the zero-padded
    remainder) matches the one-dispatch reference forward, and rows are
    bitwise independent of the batch they share: scoring the first 128
    rows alone (no remainder) gives the identical bits."""
    part, cfg, params = _setup(model, n_classes, n=150)
    ref = _oneshot(params, cfg, part)
    out = score_partition(params, cfg, part, block_b=64,
                          bottom_impl=bottom_impl)
    _close(out, ref)
    head = score_partition(params, cfg, part.take(np.arange(128)),
                           block_b=64, bottom_impl=bottom_impl)
    assert np.array_equal(head, out[:128])


def test_predict_evaluate_routed_through_batches():
    """predict/evaluate produce identical results through the batched
    path, at any block size."""
    part, cfg, params = _setup("mlp", 4, n=130)
    ref = _oneshot(params, cfg, part).argmax(axis=1)
    for bb in (32, 512):
        assert np.array_equal(predict(params, cfg, part, block_b=bb), ref)
    acc_ref = float(np.mean(ref == part.labels))
    assert evaluate(params, cfg, part, block_b=32) == acc_ref


def test_streamed_matches_oneshot_bitwise():
    """Rows streamed through the slot engine one request at a time come
    back equal to the one-shot reference forward (full batches: 96 rows,
    16 slots), and bitwise equal to the same rows submitted as whole
    16-row requests (same program shape, row independence)."""
    part, cfg, params = _setup("mlp", 4, n=96)
    ref = _oneshot(params, cfg, part)
    eng = VFLScoringEngine(params, cfg, slots=16)
    res = eng.score_requests(
        [(i, [f[i] for f in part.client_features]) for i in range(96)])
    out = np.stack([res[i][0] for i in range(96)])
    _close(out, ref)
    assert eng.stats.dispatches == 6          # 96 rows / 16 slots, all full
    assert eng.stats.padded_slots == 0
    assert eng.stats.mean_occupancy == 16.0
    whole = VFLScoringEngine(params, cfg, slots=16).score_requests(
        [(i, [f[16 * i:16 * i + 16] for f in part.client_features])
         for i in range(6)])
    assert np.array_equal(np.concatenate([whole[i] for i in range(6)]), out)


def test_partial_batch_outputs_independent_of_occupancy():
    """An occupied slot's output is bitwise-identical whether the batch
    is full or nearly empty (row independence makes partial dispatches
    exact, not approximate)."""
    part, cfg, params = _setup("lr", 2, n=40)
    eng = VFLScoringEngine(params, cfg, slots=16)
    eng.submit(1, [f[:16] for f in part.client_features])
    (_, full), = eng.step()                    # occupancy 16 of 16
    eng.submit(0, [f[:3] for f in part.client_features])
    (rid, out), = eng.step()                   # occupancy 3 of 16
    assert rid == 0
    assert np.array_equal(out, full[:3])
    _close(out, _oneshot(params, cfg, part)[:3])


# -------------------------------------------------------------- scheduler

def test_admission_occupancy_counters():
    part, cfg, params = _setup("lr", 2, n=40)
    eng = VFLScoringEngine(params, cfg, slots=8)
    for i in range(5):
        eng.submit(i, [f[i] for f in part.client_features])
    done = eng.step()
    assert sorted(r for r, _ in done) == [0, 1, 2, 3, 4]
    st = eng.stats
    assert (st.dispatches, st.admitted_rows, st.occupancy_sum,
            st.padded_slots, st.requests, st.completed) == (1, 5, 5, 3, 5, 5)
    # a second wave fills 8 + 8 + 4: two full batches and one partial
    for i in range(5, 25):
        eng.submit(i, [f[i % 40] for f in part.client_features])
    while eng.has_work:
        eng.step()
    assert st.dispatches == 4
    assert st.admitted_rows == 25
    assert st.padded_slots == 3 + 4
    assert st.completed == 25


def test_out_of_order_completion_bookkeeping():
    """FIFO-with-backfill: when the head request does not fit the free
    slots, a later smaller request jumps in and completes FIRST; every
    output still lands on its own request."""
    part, cfg, params = _setup("mlp", 4, n=40)
    ref = _oneshot(params, cfg, part)
    eng = VFLScoringEngine(params, cfg, slots=4, max_defer=10)
    eng.submit(0, [f[0:3] for f in part.client_features])   # A: 3 rows
    eng.submit(1, [f[3:6] for f in part.client_features])   # B: 3 rows
    eng.submit(2, [f[6:8] for f in part.client_features])   # C: 2 rows
    eng.submit(3, [f[8:9] for f in part.client_features])   # D: 1 row
    d1 = eng.step()       # A whole + D backfills the last slot
    d2 = eng.step()       # B whole (C still does not fit)
    d3 = eng.step()       # C
    assert sorted(r for r, _ in d1) == [0, 3]   # D (last in) beats B and C
    assert [r for r, _ in d2] == [1]
    assert [r for r, _ in d3] == [2]
    got = dict(d1 + d2 + d3)
    _close(np.concatenate([got[r] for r in range(4)]), ref[:9])


def test_oversized_request_streams_across_dispatches():
    part, cfg, params = _setup("mlp", 4, n=40)
    ref = _oneshot(params, cfg, part)
    eng = VFLScoringEngine(params, cfg, slots=4)
    eng.submit(7, [f[:9] for f in part.client_features])    # 9 rows > 4 slots
    outs = []
    while eng.has_work:
        outs += eng.step()
    assert [r for r, _ in outs] == [7]
    _close(outs[0][1], ref[:9])
    assert eng.stats.dispatches == 3                        # 4 + 4 + 1


def test_forced_split_bounds_deferral():
    """A request deferred max_defer times splits across dispatches
    instead of starving behind a stream of backfills."""
    part, cfg, params = _setup("lr", 2, n=40)
    ref = _oneshot(params, cfg, part)
    eng = VFLScoringEngine(params, cfg, slots=4, max_defer=1)
    for rid, (s, e) in enumerate([(0, 3), (3, 6), (6, 9), (9, 12)]):
        eng.submit(rid, [f[s:e] for f in part.client_features])
    res = {}
    while eng.has_work:
        res.update(eng.step())
    assert eng.stats.forced_splits >= 1
    for r in range(4):
        _close(res[r], ref[3 * r:3 * r + 3])


def test_submit_validates_shapes():
    part, cfg, params = _setup("lr", 2, n=10)
    eng = VFLScoringEngine(params, cfg, slots=4)
    with pytest.raises(ValueError):
        eng.submit(0, [part.client_features[0][:2]])        # wrong M
    with pytest.raises(ValueError):
        eng.submit(0, [f[:2, :1] for f in part.client_features])  # wrong d


def test_serve_stats_mean_occupancy():
    st = ServeStats()
    assert st.mean_occupancy == 0.0
    st.dispatches, st.occupancy_sum = 4, 10
    assert st.mean_occupancy == 2.5


# ------------------------------------------------------- train handoff

def test_engine_from_train_report():
    """TrainReport.params hand straight to the engine (shared
    pack_slab_params layout) and score like the reference forward and
    evaluate's batched path."""
    part, cfg0, _ = _setup("mlp", 4, n=80)
    cfg = SplitNNConfig(model="mlp", n_classes=4, max_epochs=2)
    report = train_splitnn(part, cfg)
    eng = VFLScoringEngine.from_report(report, cfg, slots=16)
    res = eng.score_requests(
        [(i, [f[i] for f in part.client_features]) for i in range(80)])
    out = np.stack([res[i][0] for i in range(80)])
    _close(out, _oneshot(report.params, cfg, part))
    assert np.array_equal(out.argmax(axis=1), predict(report.params, cfg,
                                                      part))


# -------------------------------------------------------- trace simulator

def _trace(part, n_requests=40, mean_gap=0.004, seed=0):
    rng = np.random.default_rng(seed)
    t, trace = 0.0, []
    for rid in range(n_requests):
        t += float(rng.exponential(mean_gap))
        idx = rng.integers(0, part.n_samples, size=int(rng.integers(1, 4)))
        trace.append(ScoreRequest(
            rid=rid, arrival=t,
            features=[f[idx] for f in part.client_features]))
    return trace


def test_continuous_beats_blocking_tail_latency():
    """At partial load the work-conserving policy ships partial batches
    instead of waiting for slots to fill: p99 latency drops, and both
    policies score every request bitwise-identically."""
    part, cfg, params = _setup("mlp", 2, n=60)
    trace = _trace(part)
    sims = {}
    for policy in ("continuous", "blocking"):
        eng = VFLScoringEngine(params, cfg, slots=8)
        sims[policy] = simulate_trace(eng, trace, policy=policy,
                                      service_seconds=2e-3)
    assert len(sims["continuous"].latencies) == len(trace)
    assert len(sims["blocking"].latencies) == len(trace)
    assert (sims["continuous"].percentile(99)
            < sims["blocking"].percentile(99))
    assert (sims["continuous"].stats.dispatches
            > sims["blocking"].stats.dispatches)
    for rid in sims["continuous"].results:
        assert np.array_equal(sims["continuous"].results[rid],
                              sims["blocking"].results[rid])


def test_simulate_counters_deterministic():
    """Scheduler counters are a pure function of (trace, slots, policy,
    service model) — the property the CI contract gate relies on."""
    part, cfg, params = _setup("lr", 2, n=60)
    trace = _trace(part, seed=3)
    runs = []
    for _ in range(2):
        eng = VFLScoringEngine(params, cfg, slots=8)
        sim = simulate_trace(eng, trace, policy="continuous",
                             service_seconds=2e-3)
        st = sim.stats
        runs.append((st.dispatches, st.admitted_rows, st.padded_slots,
                     st.occupancy_sum, st.completed, st.forced_splits,
                     tuple(sorted(sim.latencies.items()))))
    assert runs[0] == runs[1]


def test_simulate_rejects_unknown_policy():
    part, cfg, params = _setup("lr", 2, n=10)
    eng = VFLScoringEngine(params, cfg, slots=4)
    with pytest.raises(ValueError):
        simulate_trace(eng, [], policy="fifo")
