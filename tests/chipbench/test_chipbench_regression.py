"""The ``regression`` job kind (cell ``yp.treecss``) on the CPU at a tiny
size: a sound run reads ``correct``, each fault planted where the
program produces its answer fails its own number, the bfloat16 control
fails a training number, the work counts describe the split linear
model, and ``coreset_group_s`` reads the ``coreset.group`` spans."""
import json

import pytest

from _tiny import run_cell, tiny_bench

SEED = 2**31 + 41
# YP cut to 3,000 rows (2,100 train ids per party, 1,470 aligned, a
# coreset of ~1,200 rows), trained 6 epochs; every other key is yp-3p's
TINY_YP = {"n_instances": 3000}
TINY_EPOCHS = 6


@pytest.fixture
def bench(tmp_path):
    root = tiny_bench(tmp_path)
    home = root / "chipbench"
    cfg_path = home / "configs" / "yp-3p.json"
    cfg = json.loads(cfg_path.read_text())
    cfg["dataset"].update(TINY_YP)
    cfg_path.write_text(json.dumps(cfg))
    mix_path = home / "traffic" / "yp_treecss.json"
    mix = json.loads(mix_path.read_text())
    mix["epochs"] = TINY_EPOCHS
    mix_path.write_text(json.dumps(mix))
    return root


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


def _job(bench):
    from chipbench.registry import Registry

    reg = Registry(bench, home=bench / "chipbench")
    w = reg.workload("yp.treecss")
    job = reg.job(reg.config(w["config"]), reg.traffic(w["traffic"]), SEED)
    job.setup()
    return job


def test_tiny_yp_treecss_runs_correct(bench):
    out = run_cell(bench, "yp.treecss", SEED)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0
    assert out["metrics"]["pipeline_s"]["value"] > 0
    assert out["metrics"]["setup_s"]["value"] > 0
    assert set(out["checks"]) == {
        "align_ids_wrong", "coreset_rows_wrong", "kmeans_assign_gap",
        "kmeans_sq_dist_gap", "kmeans_lloyd_gap", "train_loss_gap",
        "train_change_gap", "test_mse_gap"}


def _half_batch(monkeypatch):
    from repro.train import vfl

    orig = vfl.epoch_schedule

    def half(order, n, bs, steps, padded_bs):
        idx, mask = orig(order, n, bs, steps, padded_bs)
        mask = mask.copy()
        mask[:, bs // 2:] = 0.0       # the mean is taken over the rest
        return idx, mask
    monkeypatch.setattr(vfl, "epoch_schedule", half)


def _predictions_negated(monkeypatch):
    from repro.serve import vfl

    orig = vfl.score_partition
    monkeypatch.setattr(vfl, "score_partition",
                        lambda *a, **kw: -orig(*a, **kw))


def _points_moved(monkeypatch):
    from repro.core import coreset

    orig = coreset.kmeans_fit

    def moved(key, pts, k, *, iters, impl, n_valid=None):
        cents, assign, sqd = orig(key, pts, k, iters=iters, impl=impl,
                                  n_valid=n_valid)
        return cents, assign.at[::100].set((assign[::100] + 1) % k), sqd
    monkeypatch.setattr(coreset, "kmeans_fit", moved)


def _coreset_row_shifted(monkeypatch):
    from repro.core import coreset

    orig = coreset.select_coreset

    def shifted(*args, **kw):
        idx, w, groups = orig(*args, **kw)
        idx = idx.copy()
        idx[0] = (idx[0] + 1) if idx[0] + 1 not in idx else idx[0] - 1
        return idx, w, groups
    monkeypatch.setattr(coreset, "select_coreset", shifted)


@pytest.mark.parametrize("plant,numbers", [
    (_half_batch, ["train_loss_gap", "train_change_gap"]),
    (_predictions_negated, ["test_mse_gap"]),
    (_points_moved, ["kmeans_assign_gap", "kmeans_sq_dist_gap"]),
    (_coreset_row_shifted, ["coreset_rows_wrong"])],
    ids=["half_batch", "predictions_negated", "points_moved",
         "coreset_row_shifted"])
def test_each_planted_fault_fails_its_number(bench, monkeypatch,
                                             fresh_programs, plant,
                                             numbers):
    plant(monkeypatch)
    out = run_cell(bench, "yp.treecss", SEED)
    assert not out["correct"]
    for name in numbers:
        check = out["checks"][name]
        assert check["value"] > check["limit"], (name, check)


def test_bfloat16_control_fails_a_training_number(bench):
    job = _job(bench)
    rec = job.run()
    assert all(n.ok for n in job.check([rec]))
    control = {n.name: n for n in job.control([rec])}
    assert not all(control[n].ok for n in ("train_loss_gap",
                                           "train_change_gap",
                                           "test_mse_gap"))
    # and the faults the kind reads in the reference's place fail too
    faults = job.faults([rec])
    lim = {n.name: n.limit for n in job.check([rec])}
    for fault, numbers in faults.items():
        assert any(v > lim[n] for n, v in numbers.items()), fault


def test_work_counts_describe_the_split_linear_model(bench):
    from chipbench.work import splitnn_bottom, splitnn_model

    job = _job(bench)
    rec = job.run()
    work = job.work(rec)
    assert (work["bottom"], work["hidden"], work["n_out"]) == (1, 0, 1)
    assert work["widths"] == [30, 30, 30]
    assert work["n_train"] == rec["n_train"] and work["epochs"] == 6
    assert work["kmeans"] == {"k": 12, "iters": 25}
    # forward 2 x 90 x 1, and the weights' gradient as much again
    assert splitnn_model.train_flops(work["widths"], 1, 0, 1) == 360
    flops, nbytes = splitnn_bottom.count(1000, work["widths"], 1)
    assert flops == 180_000
    assert nbytes == 4 * (1000 * 90 + 90 + 3 + 1000 * 3)


def test_coreset_group_s_reads_the_group_spans():
    from chipbench.context import Context
    from chipbench.registry import Registry
    from repro.obs import Span

    reg = Registry()
    read = reg.metric_reader("coreset_group_s")

    def ctx(spans):
        return Context(config={}, device_kind="TPU v5 lite", spans=spans,
                       trace=None, jobs=[{}, {}], compile_s=0.0)
    spans = [Span(name=n, t0=1.0, t1=1.0 + s) for n, s in (
        ("coreset.select", 2.0), ("coreset.group", 0.25),
        ("coreset.group", 0.75), ("coreset.bin", 0.5))]
    assert read(ctx(spans)) == pytest.approx(0.5)
    assert read(ctx(spans[:1])) is None
    # read wherever pipeline_s is reported, and nowhere else
    for cell in ("hi.treecss", "yp.treecss"):
        assert "coreset_group_s" in {m["name"] for m in reg.per_layer(cell)}
    assert "coreset_group_s" not in {m["name"] for m in
                                     reg.per_layer("fig7.align10")}
