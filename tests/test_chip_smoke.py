"""chip_smoke.py's phases at a small size on the CPU (kernels in
interpret mode): the same entry points, checks and request mix the chip
run uses, so a wrong path, argument or check fails here first.  The run
itself refuses the CPU."""
import importlib.util
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    sys.path.insert(0, str(ROOT))
    try:
        spec.loader.exec_module(mod)
        yield mod
    finally:
        sys.path.remove(str(ROOT))


@pytest.fixture(scope="module")
def hi_small(smoke):
    return smoke.hi_partitions(3000)


def test_refuses_without_a_tpu(smoke, capsys):
    assert smoke.main([]) == 2
    out, err = capsys.readouterr()
    assert "no TPU chip found" in err
    assert '"ok"' not in out


@pytest.fixture(scope="module", params=[None, "int8"], ids=["None", "int8"])
def trained(request, smoke, hi_small):
    tr, te = hi_small
    cfg, rep = smoke.pipeline_run(tr, te, variant="treecss",
                                  quant=request.param, epochs=2)
    return request.param, cfg, rep


def test_pipeline_and_serve_phases(smoke, hi_small, trained):
    tr, te = hi_small
    quant, cfg, rep = trained
    smoke.check_pipeline(rep, tr)
    stats = smoke.serve(rep, cfg, te, quant=quant)
    assert stats.admitted_rows == 300
    assert stats.dispatches >= 300 // 64


def test_serve_check_catches_a_wrong_row(smoke, hi_small, trained,
                                         monkeypatch):
    """One request scored from another row's features (of the other
    class) fails the served-vs-evaluate() check, at the f32 and at the
    looser int8 tolerance alike."""
    from repro.serve.vfl import VFLScoringEngine

    _, te = hi_small
    quant, cfg, rep = trained
    other = int(np.flatnonzero(te.labels != te.labels[0])[0])
    submit = VFLScoringEngine.submit

    def planted(self, rid, features, row_ids=None):
        if rid == 0:                       # the one-row request: row 0
            features = [f[other:other + 1] for f in te.client_features]
        return submit(self, rid, features, row_ids)

    monkeypatch.setattr(VFLScoringEngine, "submit", planted)
    with pytest.raises(smoke.CheckFailed, match="batched scores"):
        smoke.serve(rep, cfg, te, quant=quant)


def test_tiled_alignment_phase(smoke, monkeypatch):
    """Shrink the single-pass bound so small sets take the tiled merge
    (the paths are chosen at trace time: drop traced programs first)."""
    import jax

    from repro.kernels.padding import KERNEL_PATHS
    from repro.kernels.sorted_intersect import ops
    from repro.psi.engine import clear_dispatch_cache

    monkeypatch.setattr(ops, "SINGLE_PASS_MAX_P", 1 << 10)
    jax.clear_caches()
    clear_dispatch_cache()
    before = KERNEL_PATHS.snapshot().get("sorted_intersect.tiled", 0)
    stats = smoke.tiled_alignment(n_ids=5000, n_parties=4)
    jax.clear_caches()
    clear_dispatch_cache()
    assert KERNEL_PATHS.snapshot()["sorted_intersect.tiled"] > before
    assert stats.intersection.size == int(round(5000 * 0.7))


def test_failed_check_raises(smoke):
    with pytest.raises(smoke.CheckFailed, match="never"):
        smoke.check(False, "never")
    smoke.check(np.array_equal([1], [1]), "passes")
