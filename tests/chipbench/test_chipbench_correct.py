"""``correct`` on the CPU at a tiny size: a run with the timed path
broken underneath reads false, once for each fault its cell can have,
and the control, put in the program's place, fails a number."""
import functools

import numpy as np
import pytest

from _tiny import run_cell, tiny_bench

SEED = 2**31 + 29


@pytest.fixture
def bench(tmp_path):
    return tiny_bench(tmp_path)


@pytest.fixture
def fresh_programs():
    """Faults planted in traced code need programs traced after them."""
    from repro.psi.engine import clear_dispatch_cache
    from repro.train.vfl import clear_program_caches

    clear_program_caches()
    clear_dispatch_cache()
    yield
    clear_program_caches()
    clear_dispatch_cache()


def _alter_first_id(fn):
    @functools.wraps(fn)
    def wrapped(*args, **kw):
        out = fn(*args, **kw)
        first = out.intersections[0]
        out.intersections[0] = np.concatenate([first[:1] + 1, first[1:]])
        return out
    return wrapped


def test_sound_tiny_runs_read_correct(bench):
    assert run_cell(bench, "hi.treecss", SEED)["correct"]


def test_aligned_id_altered_where_produced_fails(bench, monkeypatch):
    from repro.psi import engine

    monkeypatch.setattr(engine, "oprf_round",
                        _alter_first_id(engine.oprf_round))
    out = run_cell(bench, "hi.treecss", SEED)
    assert not out["correct"]
    assert out["checks"]["align_ids_wrong"]["value"] > 0


def test_round_output_altered_fails_the_alignment_cell(bench, monkeypatch):
    from repro.psi import engine

    monkeypatch.setattr(engine, "oprf_round",
                        _alter_first_id(engine.oprf_round))
    out = run_cell(bench, "fig7.align10", SEED)
    assert not out["correct"]
    assert out["checks"]["align_rounds_wrong"]["value"] > 0


def test_coreset_row_altered_where_produced_fails(bench, monkeypatch):
    from repro.core import coreset

    orig = coreset.select_coreset

    def shifted(*args, **kw):
        idx, w, groups = orig(*args, **kw)
        idx = idx.copy()
        idx[0] = (idx[0] + 1) if idx[0] + 1 not in idx else idx[0] - 1
        return idx, w, groups
    monkeypatch.setattr(coreset, "select_coreset", shifted)
    out = run_cell(bench, "hi.treecss", SEED)
    assert not out["correct"]
    assert out["checks"]["coreset_rows_wrong"]["value"] > 0


def test_kmeans_fit_that_leaves_its_centroids_unmoved_fails(bench,
                                                           monkeypatch):
    from repro.core import coreset

    orig = coreset.kmeans_fit

    def no_iterations(key, pts, k, *, iters, impl, n_valid=None):
        return orig(key, pts, k, iters=0, impl=impl, n_valid=n_valid)
    monkeypatch.setattr(coreset, "kmeans_fit", no_iterations)
    out = run_cell(bench, "hi.treecss", SEED)
    assert not out["correct"]
    assert out["checks"]["kmeans_lloyd_gap"]["value"] > \
        out["checks"]["kmeans_lloyd_gap"]["limit"]


def test_points_moved_to_another_cluster_where_assigned_fail(bench,
                                                            monkeypatch):
    from repro.core import coreset

    orig = coreset.kmeans_fit

    def moved(key, pts, k, *, iters, impl, n_valid=None):
        cents, assign, sqd = orig(key, pts, k, iters=iters, impl=impl,
                                  n_valid=n_valid)
        return cents, assign.at[::100].set((assign[::100] + 1) % k), sqd
    monkeypatch.setattr(coreset, "kmeans_fit", moved)
    out = run_cell(bench, "hi.treecss", SEED)
    assert not out["correct"]
    for name in ("kmeans_assign_gap", "kmeans_sq_dist_gap"):
        assert out["checks"][name]["value"] > out["checks"][name]["limit"]


@pytest.mark.parametrize("cell", ["hi.treecss", "hi.treeall"])
def test_train_step_that_returns_its_state_unchanged_fails(
        bench, monkeypatch, fresh_programs, cell):
    from repro.train import vfl

    monkeypatch.setattr(vfl, "adam_update",
                        lambda p, g, o, **kw: (p, o))
    out = run_cell(bench, cell, SEED)
    assert not out["correct"]
    assert out["checks"]["train_change_gap"]["value"] >= 0.99


@pytest.mark.parametrize("cell", ["hi.treecss", "hi.treeall"])
def test_half_the_batch_left_out_fails(bench, monkeypatch, fresh_programs,
                                       cell):
    from repro.train import vfl

    orig = vfl.epoch_schedule

    def half(order, n, bs, steps, padded_bs):
        idx, mask = orig(order, n, bs, steps, padded_bs)
        mask = mask.copy()
        mask[:, bs // 2:] = 0.0       # the mean is taken over the rest
        return idx, mask
    monkeypatch.setattr(vfl, "epoch_schedule", half)
    out = run_cell(bench, cell, SEED)
    assert not out["correct"]


def test_scores_altered_where_produced_fail(bench, monkeypatch):
    from repro.serve import vfl

    orig = vfl.score_partition
    monkeypatch.setattr(vfl, "score_partition",
                        lambda *a, **kw: -orig(*a, **kw))
    out = run_cell(bench, "hi.treeall", SEED)
    assert not out["correct"]
    assert out["checks"]["test_accuracy_gap"]["value"] > 0.2


@pytest.mark.parametrize("cell", ["hi.treecss", "hi.treeall"])
def test_bfloat16_control_fails_a_training_number(bench, cell):
    from chipbench.registry import Registry

    reg = Registry(bench, home=bench / "chipbench")
    w = reg.workload(cell)
    job = reg.job(reg.config(w["config"]), reg.traffic(w["traffic"]), SEED)
    job.setup()
    rec = job.run()
    assert all(n.ok for n in job.check([rec]))
    assert not all(n.ok for n in job.control([rec]))


def test_short_hash_control_fails_the_alignment(bench):
    # at 3000 ids a 32-bit hash collides too rarely to show; 16 bits do
    from chipbench.registry import Registry

    reg = Registry(bench, home=bench / "chipbench")
    w = reg.workload("fig7.align10")
    job = reg.job(reg.config(w["config"]), reg.traffic(w["traffic"]), SEED)
    job.setup()
    try:
        rec = job.run()
        assert all(n.ok for n in job.check([rec]))
        assert not all(n.ok for n in job.control([rec], bits=16))
    finally:
        job.teardown()
