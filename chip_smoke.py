#!/usr/bin/env python3
"""Bring-up smoke run of the VFL main path on TPU: align -> coreset ->
train -> serve, through the public entry points, at the paper's sizes.

    python3 chip_smoke.py             # one chip: pipeline, tiled-alignment
                                      # and serve phases
    python3 chip_smoke.py --chips 4   # four chips: the (data, model) mesh
                                      # pipeline against mesh=None, only

Everything runs in this one process.  The run stops with a non-zero exit
when JAX finds no TPU (it never carries on on the CPU), when any check
fails, or when any phase raises.  Each phase prints the device, its
wall and compile seconds, the kernel path each stage took and the
Mosaic kernels its programs contain; the last line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
These are smoke timings of a bring-up run, not benchmark numbers.
"""
from __future__ import annotations

import argparse
import functools
import json
import re
import shutil
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
IR_DIR = ROOT / ".jax_dump"      # lowered programs, read for kernel checks

HI_ROWS = 100_000                # paper Table 1, HI
TILED_IDS = 1 << 19              # ids per party: past SINGLE_PASS_MAX_P
CHANCE = 0.5                     # HI is a balanced binary task


class CheckFailed(AssertionError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise CheckFailed(what)
    print(f"  check ok: {what}", flush=True)


# --------------------------------------------------------------- probes

class Probe:
    """What one phase did: wall and compile seconds, kernel paths traced
    (``repro.kernels.padding.KERNEL_PATHS``), and the Mosaic kernels in
    the programs it lowered (``tpu_custom_call`` in the lowered text)."""

    device = ""
    compile_s = 0.0
    cache_hits = 0
    kernels_seen: set = set()
    _files: set = set()

    @classmethod
    def install(cls) -> None:
        import jax
        from jax import monitoring

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                cls.compile_s += secs

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                cls.cache_hits += 1

        monitoring.register_event_duration_secs_listener(on_duration)
        monitoring.register_event_listener(on_event)
        shutil.rmtree(IR_DIR, ignore_errors=True)
        IR_DIR.mkdir(parents=True)
        jax.config.update("jax_dump_ir_to", str(IR_DIR))

    @classmethod
    def scan_kernels(cls) -> set:
        """Kernel names of the tpu_custom_calls in newly lowered text."""
        new = set()
        for f in sorted(IR_DIR.iterdir()):
            if f in cls._files:
                continue
            cls._files.add(f)
            for line in f.read_text(errors="replace").splitlines():
                if "@tpu_custom_call" in line:
                    new.update(re.findall(r'kernel_name = "([^"]+)"', line))
        cls.kernels_seen |= new
        return new

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        from repro.kernels.padding import KERNEL_PATHS
        self._paths0 = KERNEL_PATHS.snapshot()
        self._c0, self._h0 = Probe.compile_s, Probe.cache_hits
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if exc[0] is not None:
            return False
        from repro.kernels.padding import KERNEL_PATHS
        self.wall_s = time.perf_counter() - self._t0
        self.compile_s = Probe.compile_s - self._c0
        now = KERNEL_PATHS.snapshot()
        self.paths = {k: v - self._paths0.get(k, 0) for k, v in now.items()
                      if v - self._paths0.get(k, 0)}
        self.kernels = Probe.scan_kernels()
        print(f"[{self.name}] device={Probe.device} "
              f"wall_s={self.wall_s:.3f} "
              f"compile_s={self.compile_s:.3f} "
              f"cache_hits={Probe.cache_hits - self._h0}", flush=True)
        print(f"[{self.name}] kernel paths traced: "
              f"{json.dumps(self.paths, sort_keys=True)}", flush=True)
        print(f"[{self.name}] mosaic kernels lowered: "
              f"{sorted(self.kernels)}", flush=True)
        return False


def expect_kernels(names) -> None:
    """Every kernel the phase was meant to use is in a lowered program
    (this phase's, or an earlier one's that it re-used from cache)."""
    missing = set(names) - Probe.kernels_seen
    check(not missing, f"tpu_custom_call present for {sorted(names)}"
          + (f" (missing {sorted(missing)})" if missing else ""))


# --------------------------------------------------------------- phases

def hi_partitions(n_rows: int = HI_ROWS):
    """The paper's HI deployment: 100K x 32, three parties, 70/30."""
    from benchmarks.common import dataset_partitions
    return dataset_partitions("HI", quick=False, n_override=n_rows)


def pipeline_run(tr, te, *, variant: str, quant, epochs: int, mesh=None):
    """run_pipeline with every stage on its Pallas kernel."""
    from repro.config import AlignOptions, EngineOptions
    from repro.core.splitnn import SplitNNConfig
    from repro.core.treecss import run_pipeline

    cfg = SplitNNConfig(model="mlp", n_classes=2, max_epochs=epochs)
    report = run_pipeline(
        tr, te, cfg, variant=variant, kmeans_impl="pallas", seed=0,
        options=EngineOptions(bottom_impl="pallas", fuse_gather=True,
                              quant=quant, mesh=mesh),
        align=AlignOptions(protocol="oprf", psi_backend="device",
                           impl="pallas"))
    return cfg, report


def expected_alignment(n_parties: int, n_ids: int, seed: int):
    from repro.data.synthetic import make_id_universe
    sets, _ = make_id_universe(n_parties, n_ids, 0.7, seed=seed)
    return sets, functools.reduce(np.intersect1d, sets)


def check_pipeline(report, tr) -> None:
    _, want = expected_alignment(tr.n_clients, tr.n_samples, seed=0)
    check(np.array_equal(report.mpsi.intersection, want),
          f"aligned ids == numpy intersection ({want.size} ids)")
    check(bool(np.isfinite(report.metric)) and report.metric > CHANCE + 0.1,
          f"metric {report.metric:.4f} finite and above chance {CHANCE}")


def tiled_alignment(n_ids: int = TILED_IDS, n_parties: int = 4):
    """OPRF Tree-MPSI on the device backend, sets past the single-pass
    merge bound, checked exactly against numpy."""
    from repro.config import AlignOptions
    from repro.psi import run_psi

    sets, want = expected_alignment(n_parties, n_ids, seed=1)
    stats = run_psi(sets, topology="tree", options=AlignOptions(
        protocol="oprf", psi_backend="device", impl="pallas"))
    check(np.array_equal(stats.intersection, want),
          f"tiled-merge alignment == numpy intersection ({want.size} ids, "
          f"{n_parties} parties x {n_ids})")
    return stats


# Served scores vs the batched scorer evaluate() runs, as a share of the
# logit scale.  f32: same params, rows and kernels, only the batch shape
# differs (engine slots vs block_b), which reorders f32 accumulation —
# a few ulps; 1e-3 is far above that and far below a wrong row or
# parameter.  int8: the activation wire shares one int8 scale per
# 8-row block (quant.QUANT_BLOCK_ROWS), and a row's block-mates differ
# between the engine's slots and evaluate()'s batches, so an activation
# may round one int8 step (1/127 ~ 8e-3 of its block's max) apart,
# which the top MLP carries into the logits (3.4e-3 of the logit scale
# on HI on a v5e); 2e-2, about two int8 steps, bounds it.  One row
# scored from another row's features misses it by ~50x (3.63 against
# 7.4e-2 at 3000 HI rows on the CPU; tests/test_chip_smoke.py).
SERVE_TOL = {None: 1e-3, "int8": 2e-2}
# Served f32 scores vs the plain jnp forward at precision "highest": the
# TPU's default f32 matmul is one bf16 pass (8 mantissa bits), so logits
# move by ~2^-8 relative per product; 3e-2 of the logit scale bounds it.
REF_TOL = 3e-2


def serve(report, cfg, te, *, quant, n_rows: int = 300, slots: int = 64):
    """VFLScoringEngine off the train report, driven by submit/step with
    requests of several sizes, one larger than the slot batch."""
    import jax
    import jax.numpy as jnp

    from repro.core import splitnn as models
    from repro.core.splitnn import evaluate
    from repro.serve.vfl import VFLScoringEngine, score_partition

    rows = te.take(np.arange(n_rows))
    sizes = [1, 7, 64, slots + 86]
    sizes.append(n_rows - sum(sizes))
    assert sum(sizes) == n_rows and max(sizes) > slots
    eng = VFLScoringEngine.from_report(report.train, cfg, slots=slots,
                                       bottom_impl="pallas", quant=quant)
    bounds = np.cumsum([0] + sizes)
    for rid, (s, e) in enumerate(zip(bounds[:-1], bounds[1:])):
        eng.submit(rid, [f[s:e] for f in rows.client_features])
    done = {}
    while eng.has_work:
        done.update(eng.step())
    served = np.concatenate([done[r] for r in range(len(sizes))])
    check(served.shape[0] == n_rows and np.isfinite(served).all(),
          f"{len(sizes)} requests ({sizes}) served, finite scores")
    batched = score_partition(report.train.params, cfg, rows,
                              bottom_impl="pallas", quant=quant)
    tol = SERVE_TOL[quant] * max(1.0, float(np.abs(batched).max()))
    err = float(np.max(np.abs(served - batched)))
    check(err <= tol, f"served == evaluate()'s batched scores (max |diff| "
          f"{err:.2e} <= {tol:.2e})")
    if served.shape[1] == 1:
        pred, margin = served[:, 0] > 0, np.abs(batched[:, 0])
    else:
        pred = served.argmax(axis=1)
        top2 = np.sort(batched, axis=1)[:, -2:]
        margin = top2[:, 1] - top2[:, 0]
    acc = float(np.mean(pred == rows.labels))
    ev = evaluate(report.train.params, cfg, rows, bottom_impl="pallas",
                  quant=quant)
    near = int(np.sum(margin <= 2 * tol))   # rows a tol-sized move can flip
    check(round(abs(acc - ev) * n_rows) <= near,
          f"served accuracy {acc:.4f} == evaluate() {ev:.4f} "
          f"(up to {near} rows within the tolerance of the boundary)")
    if quant is None:
        xs = [jnp.asarray(f) for f in rows.client_features]
        with jax.default_matmul_precision("highest"):
            ref = np.asarray(models.splitnn_forward(report.train.params,
                                                    cfg, xs))
        err = float(np.max(np.abs(served - ref)))
        check(err <= REF_TOL * max(1.0, float(np.abs(ref).max())),
              f"served == precision-highest jnp forward "
              f"(max |diff| {err:.2e})")
    return eng.stats


def one_chip() -> None:
    tr, te = hi_partitions()
    print(f"HI: {tr.n_samples} train / {te.n_samples} test rows, "
          f"party widths {[f.shape[1] for f in tr.client_features]}",
          flush=True)
    reports = {}
    for label, variant, quant in [("f32", "treecss", None),
                                  ("int8", "treecss", "int8")]:
        with Probe(f"pipeline {variant} {label}") as pr:
            cfg, rep = pipeline_run(tr, te, variant=variant, quant=quant,
                                    epochs=5)
        print(f"  n_align={rep.mpsi.intersection.size} "
              f"n_train={rep.n_train} epochs={rep.train.epochs} "
              f"metric={rep.metric:.4f} align_wall_s="
              f"{rep.align_wall_seconds:.3f} coreset_wall_s="
              f"{rep.coreset_wall_seconds:.3f} train_wall_s="
              f"{rep.train_wall_seconds:.3f}", flush=True)
        check_pipeline(rep, tr)
        fused = "int8_gather_fused" if quant else "gather_fused"
        check(pr.paths.get(f"splitnn_bottom.{fused}", 0) > 0,
              f"train bottom took the fused gather ({fused})")
        expect_kernels(["psi_prf", "sorted_intersect_merge",
                        "kmeans_update", "kmeans_assign",
                        "splitnn_bottom_int8_gather" if quant
                        else "splitnn_bottom_gather",
                        "splitnn_bottom_int8" if quant
                        else "splitnn_bottom"])
        reports[label] = (cfg, rep)

    # all ~49K aligned rows, no coreset: the f32 slab (~25 MB) is past
    # the gather VMEM budget, so the train bottom must take the dense
    # fallback; the int8 slab (~6 MB, packed 4 rows to an i32 word) is
    # under it, so the fused int8 gather runs at full size
    for label, quant, path, what in [
            ("f32", None, "gather_fallback",
             "past the VMEM budget took the dense fallback"),
            ("int8", "int8", "int8_gather_fused",
             "took the fused int8 gather over the full slab")]:
        with Probe(f"pipeline treeall {label}") as pr:
            cfg, rep = pipeline_run(tr, te, variant="treeall", quant=quant,
                                    epochs=2)
        print(f"  n_train={rep.n_train} epochs={rep.train.epochs} "
              f"metric={rep.metric:.4f} train_wall_s="
              f"{rep.train_wall_seconds:.3f}", flush=True)
        check_pipeline(rep, tr)
        check(pr.paths.get(f"splitnn_bottom.{path}", 0) > 0,
              f"train bottom ({rep.n_train} rows) {what}")
    expect_kernels(["splitnn_bottom_int8_gather"])

    with Probe("tiled alignment") as pr:
        tiled_alignment()
    check(pr.paths.get("sorted_intersect.tiled", 0) > 0,
          "merge took the tiled multi-pass path")
    expect_kernels(["psi_prf", "sorted_intersect_cross",
                    "sorted_intersect_merge"])

    for label, quant in [("f32", None), ("int8", "int8")]:
        cfg, rep = reports[label]
        with Probe(f"serve {label}"):
            st = serve(rep, cfg, te, quant=quant)
        print(f"  dispatches={st.dispatches} admitted_rows="
              f"{st.admitted_rows} mean_occupancy={st.mean_occupancy:.2f}",
              flush=True)


# A (data, model) mesh shards the per-step batch and the client axis:
# the psum over data and the activation all_gather reorder f32 sums,
# and the TPU's bf16-pass matmuls carry that through the epochs.  The
# drift does not shrink with the loss (5 epochs take it from 0.43 to
# 0.016), so each epoch's gap is held to a share of the FIRST epoch's
# loss.  Measured gaps: at most 5.8e-4 of it on four v5e chips (2.5e-4
# against 0.43, int8; f32 1.3e-4), and 7.8e-4 on a 2x2 CPU mesh at
# 3000 rows (int8; f32 exact).  Dropping the data psum of the bottom
# gradients in that CPU run gives 6.1e-3 (f32) and 8.0e-3 (int8), with
# the metric unchanged; 2.5e-3 sits between the two.
MESH_LOSS_TOL = 2.5e-3
MESH_METRIC_TOL = 0.02


def four_chips() -> None:
    from repro.launch.mesh import make_train_mesh

    tr, te = hi_partitions()
    mesh = make_train_mesh(2, 2)
    print(f"mesh: {dict(mesh.shape)}", flush=True)
    for label, quant in [("f32", None), ("int8", "int8")]:
        with Probe(f"mesh=None {label}"):
            _, base = pipeline_run(tr, te, variant="treecss", quant=quant,
                                   epochs=5)
        with Probe(f"mesh 2x2 {label}"):
            _, shrd = pipeline_run(tr, te, variant="treecss", quant=quant,
                                   epochs=5, mesh=mesh)
        check_pipeline(shrd, tr)
        check(np.array_equal(base.mpsi.intersection,
                             shrd.mpsi.intersection),
              f"{label}: aligned set identical on the mesh")
        check(np.array_equal(base.coreset.indices, shrd.coreset.indices),
              f"{label}: coreset indices identical on the mesh")
        lb = np.asarray(base.train.losses)
        ls = np.asarray(shrd.train.losses)
        tol = MESH_LOSS_TOL * lb[0]
        gap = float(np.max(np.abs(ls - lb))) if lb.shape == ls.shape else -1
        check(lb.shape == ls.shape and gap <= tol,
              f"{label}: per-epoch losses agree (max gap {gap:.3e} <= "
              f"{MESH_LOSS_TOL} x first-epoch loss = {tol:.3e}; "
              f"{lb.tolist()} vs {ls.tolist()})")
        check(abs(base.metric - shrd.metric) <= MESH_METRIC_TOL,
              f"{label}: metric {shrd.metric:.4f} vs {base.metric:.4f} "
              f"within {MESH_METRIC_TOL}")
    expect_kernels(["psi_prf", "sorted_intersect_merge", "kmeans_update",
                    "splitnn_bottom_gather", "splitnn_bottom_int8_gather"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the (data, model) mesh phase")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax

    from repro.launch.cache import enable_compile_cache

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU chip found: JAX's device is "
              f"{dev.platform!r} ({dev.device_kind}); this run needs a "
              f"TPU and never falls back to the CPU", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"chips, found {len(devices)}", file=sys.stderr)
        return 2
    cache = enable_compile_cache()
    Probe.device = f"{dev.platform}:{dev.device_kind}"
    Probe.install()
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"compile cache {cache}", flush=True)
    try:
        four_chips() if args.chips == 4 else one_chip()
    finally:
        shutil.rmtree(IR_DIR, ignore_errors=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
