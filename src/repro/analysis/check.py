"""Static engine-contract verifier — the CI gate over LOWERED programs.

    python -m repro.analysis.check                 # lint + census gate
    python -m repro.analysis.check --write         # regenerate contract
    python -m repro.analysis.check --lint-only --src PATH
    python -m repro.analysis.check --census-only --census-csv out.csv

Two layers (DESIGN.md §11), complementary to the *dynamic* perf
contract (``benchmarks/check_contract.py``, which proves counters by
running the engines on the two CI mesh shapes):

**Census** — every compiled engine program (PSI ``_dispatch``
executables, ``train_scan``'s epoch step via the same cached
``make_epoch_fn`` the engine itself uses, ``make_score_step``'s scoring
step, the k-means fit) is traced and lowered — never executed — across
a mesh matrix that includes shapes dynamic CI never runs (``4x2``), and
its jaxpr is walked (``repro.analysis.census``) for collectives,
callbacks, f64, loop structure and donation.  The counters are pinned
in ``experiments/bench/static_contract.json``; on top of the pinned
values, HARD invariants are enforced even under ``--write``:

- train epoch step: zero host callbacks, zero f64, and exactly ONE
  all_gather inside the scan body if and only if the mesh has a model
  axis (the paper's client→server activation send, DESIGN.md §8) —
  for the quantized variants (int8/fp8, DESIGN.md §12) ALSO that the
  lowered all_gather output is ≤ 0.3x the f32 twin's bytes;
- PSI / scoring / k-means programs: zero collectives, zero callbacks
  (alignment's real communication is protocol-level, not in-program);
- every Pallas kernel's BlockSpec footprint fits VMEM
  (``repro.analysis.blocks``).

**Lint** — pure-AST repo rules over ``src/`` (``repro.analysis.lint``):
host syncs in traced code, call-time ``jax.jit``, unbounded
``lru_cache``, reassociating reductions in bitwise paths.  Findings are
suppressed inline (``# lint-ok: <rule>``) or accepted in the JSON
baseline; anything else fails the gate.

Exit status: 0 clean, 1 violations, 2 environment/usage errors.  The
module sets ``XLA_FLAGS`` for 8 virtual devices BEFORE importing jax
(main() only); when imported into a process whose jax already has fewer
devices, mesh census rows are skipped and reported as such.
"""
from __future__ import annotations

import argparse
import csv
import json
import os
import sys
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

DEFAULT_CONTRACT = os.path.join("experiments", "bench",
                                "static_contract.json")
DEFAULT_BASELINE = os.path.join("experiments", "bench",
                                "lint_baseline.json")
DEFAULT_SRC = "src"

KEY = ("engine", "mesh")

# mesh-name -> (data, model); model=0 means the plain 1-D data mesh.
# "4x2" is deliberately a shape the dynamic CI contract never runs.
MESH_SHAPES: Dict[str, Optional[Tuple[int, int]]] = {
    "1": None, "8": (8, 0), "2x4": (2, 4), "4x2": (4, 2)}

_PSI_MESHES = ("1", "8")
_TRAIN_MESHES = ("1", "8", "2x4", "4x2")


def _ensure_virtual_devices() -> None:
    """Give the process 8 virtual CPU devices — must run BEFORE the
    first jax import, so only ``main()`` calls it."""
    if "jax" in sys.modules:
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()


def available_meshes() -> Dict[str, Any]:
    """The buildable subset of ``MESH_SHAPES`` (mesh rows need 8
    devices; the "1" row always builds)."""
    import jax
    from repro.launch.mesh import make_data_mesh, make_train_mesh
    out: Dict[str, Any] = {"1": None}
    if len(jax.devices()) >= 8:
        for name, shape in MESH_SHAPES.items():
            if shape is None:
                continue
            data, model = shape
            out[name] = (make_data_mesh(data) if model == 0
                         else make_train_mesh(data, model))
    return out


# -------------------------------------------------------- program matrix


def _psi_programs(meshes):
    """(key, census) per PSI dispatch executable per mesh."""
    import jax
    import jax.numpy as jnp
    from repro.analysis.census import census_program
    from repro.config import AlignOptions
    from repro.psi.engine import _dispatch, dispatch_key

    sds = jax.ShapeDtypeStruct
    b, p = 8, 2048
    z = sds((b, p), jnp.uint32)
    n = sds((b,), jnp.int32)
    seeds = sds((b, 2), jnp.uint32)
    shapes = {"prf": (z, z, z, z, seeds), "merge": (z, z, z, z),
              "single": (z, z, n, z, z, n, seeds),
              "union": (z, z, z, z)}
    for mesh_name in _PSI_MESHES:
        if mesh_name not in meshes:
            continue
        key, _ = dispatch_key(AlignOptions(impl="pallas",
                                           mesh=meshes[mesh_name]))
        for kind, args in shapes.items():
            fn = _dispatch(kind, key)
            yield (f"psi.{kind}", mesh_name), census_program(fn, args)


def _train_programs(meshes):
    """(key, census, has_model_axis, quant, base_tag) per epoch-step
    program per mesh — built by the SAME ``make_epoch_fn`` the engine
    runs, so the census can never audit a different program than
    training executes.  Quantized variants ride the same matrix: their
    lowered programs must keep the ONE-gather/zero-f64 invariants AND
    shrink the model-axis all_gather payload to ≤ 0.3x the f32 twin
    lowered alongside (the ratio gate in ``run_census``)."""
    from repro.analysis.census import census_program
    from repro.core.splitnn import SplitNNConfig
    from repro.quant import FP8_DTYPE
    from repro.sharding import resolve_train_mesh
    from repro.train.vfl import make_epoch_fn

    fd = (3, 4, 5)
    variants = [
        ("lr", SplitNNConfig("lr", 2, batch_size=64), "ref", None),
        ("mlp", SplitNNConfig("mlp", 2, batch_size=64), "pallas", None),
        ("lr-int8", SplitNNConfig("lr", 2, batch_size=64), "ref",
         "int8"),
        ("mlp-int8", SplitNNConfig("mlp", 2, batch_size=64), "pallas",
         "int8"),
    ]
    if FP8_DTYPE is not None:
        variants.append(
            ("lr-fp8", SplitNNConfig("lr", 2, batch_size=64), "ref",
             "fp8"))
    for mesh_name in _TRAIN_MESHES:
        if mesh_name not in meshes:
            continue
        for tag, cfg, impl, quant in variants:
            mesh, data_axis, n_data, model_axis, n_model = \
                resolve_train_mesh(meshes[mesh_name])
            prog = make_epoch_fn(cfg, fd, mesh, data_axis, model_axis,
                                 n_data, n_model, impl, 512, True,
                                 quant)
            args = prog.abstract_args(n=256, bs=64)
            yield ((f"train.epoch.{tag}+{impl}", mesh_name),
                   census_program(prog.jitted, args),
                   model_axis is not None, quant,
                   f"{tag.split('-')[0]}+{impl}")


def _serve_programs():
    """(key, census) per scoring-step program (single device — serving
    shards by replication, not in-program collectives)."""
    import jax
    import jax.numpy as jnp
    from repro.analysis.census import census_program
    from repro.core import splitnn as models
    from repro.core.splitnn import SplitNNConfig
    from repro.train.vfl import _score_step_fn, pack_slab_params

    fd = (3, 4, 5)
    d_max = max(fd)
    for tag, cfg, impl, quant in (
            ("lr", SplitNNConfig("lr", 2), "ref", None),
            ("mlp", SplitNNConfig("mlp", 2), "pallas", None),
            ("lr-int8", SplitNNConfig("lr", 2), "ref", "int8"),
            ("mlp-int8", SplitNNConfig("mlp", 2), "pallas", "int8")):
        packed = jax.eval_shape(lambda c=cfg: pack_slab_params(
            models.init_splitnn(c, list(fd)), d_max))
        x_slab = jax.ShapeDtypeStruct((len(fd), 64, d_max), jnp.float32)
        fn = _score_step_fn(cfg, len(fd), impl, 512, quant)
        yield (f"serve.score.{tag}+{impl}", "1"), \
            census_program(fn, (packed, x_slab))


def _kmeans_programs():
    import functools
    import jax
    import jax.numpy as jnp
    from repro.analysis.census import census_program
    from repro.core.kmeans import kmeans_fit

    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    pts = jax.ShapeDtypeStruct((256, 8), jnp.float32)
    for impl in ("ref", "pallas"):
        fn = functools.partial(kmeans_fit, k=4, iters=5, impl=impl)
        yield (f"kmeans.fit+{impl}", "1"), \
            census_program(fn, (key, pts), count_donation=False)


def run_census(meshes) -> Tuple[Dict[Tuple[str, str], Dict[str, Any]],
                                List[str]]:
    """All program counters plus every HARD-invariant violation."""
    rows: Dict[Tuple[str, str], Dict[str, Any]] = {}
    hard: List[str] = []

    def check_zero_comm(key, census):
        if census.total_collectives():
            hard.append(f"{key}: program contains collectives "
                        f"({census.collectives}) — must be zero")

    def check_common(key, census):
        if census.callbacks:
            hard.append(f"{key}: {census.callbacks} host callback(s) in "
                        "lowered program — zero-host-sync contract")
        if census.f64_values or census.f64_widenings:
            hard.append(f"{key}: f64 in lowered program "
                        f"({census.f64_values} values, "
                        f"{census.f64_widenings} widenings)")

    for key, census in _psi_programs(meshes):
        rows[key] = census.counters()
        check_common(key, census)
        check_zero_comm(key, census)

    ag_bytes: Dict[Tuple[str, str], Dict[str, int]] = {}
    for key, census, has_model, quant, base in _train_programs(meshes):
        rows[key] = census.counters()
        check_common(key, census)
        ag = census.collectives_in_loop.get("all_gather", 0)
        want = 1 if has_model else 0
        if ag != want:
            why = ("one activation send per step over model" if want
                   else "no gathers without a model axis")
            hard.append(
                f"{key}: {ag} all_gather(s) inside the scan body, "
                f"contract requires exactly {want} ({why})")
        if has_model:
            ag_bytes.setdefault((base, key[1]), {})[quant or "f32"] = \
                census.collective_bytes.get("all_gather", 0)

    # payload-shrink gate over LOWERED bytes: on every model-axis mesh,
    # the quantized epoch program's all_gather output must be ≤ 0.3x
    # the f32 twin's (the wire really narrowed — not just the counter)
    for (base, mesh_name), by_quant in sorted(ag_bytes.items()):
        f32 = by_quant.get("f32", 0)
        for quant in sorted(q for q in by_quant if q != "f32"):
            b = by_quant[quant]
            if not f32:
                hard.append(f"train.epoch.{base}@{mesh_name}: no f32 "
                            f"twin to ratio quant={quant} against")
            elif b > 0.3 * f32:
                hard.append(
                    f"train.epoch.{base}@{mesh_name}: quant={quant} "
                    f"all_gather payload {b}B > 0.3x f32 twin "
                    f"({f32}B) — wire did not narrow")

    for key, census in _serve_programs():
        rows[key] = census.counters()
        check_common(key, census)
        check_zero_comm(key, census)

    for key, census in _kmeans_programs():
        rows[key] = census.counters()
        check_common(key, census)
        check_zero_comm(key, census)

    return rows, hard


def run_blocks() -> Tuple[List[Dict[str, Any]], List[str]]:
    from repro.analysis.blocks import vmem_report
    reports = [r.as_row() for r in vmem_report()]
    fails = [f"vmem: {r['kernel']} [{r['shape']}]: resident "
             f"{r['resident_bytes']}B exceeds {r['budget']}B budget"
             for r in reports if not r["ok"]]
    return reports, fails


def run_lint(src: str, baseline_path: str):
    from repro.analysis.lint import (iter_source_files, lint_paths,
                                     load_baseline, split_baselined)
    root = Path(src)
    if not root.exists():
        return None, [f"lint: source path {src!r} does not exist"]
    findings = lint_paths(iter_source_files(root))
    baseline = load_baseline(Path(baseline_path))
    new, accepted = split_baselined(findings, baseline)
    fails = [f.render() for f in new]
    return {"new": [f.as_dict() for f in new],
            "accepted": [f.as_dict() for f in accepted]}, fails


def write_census_csv(rows, path: str) -> None:
    from repro.analysis.census import CENSUS_FIELDS
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w", newline="") as f:
        wr = csv.writer(f)
        wr.writerow(list(KEY) + list(CENSUS_FIELDS))
        for key in sorted(rows):
            counters = rows[key]
            wr.writerow(list(key) + [
                ";".join(str(x) for x in counters[c])
                if isinstance(counters[c], list) else counters[c]
                for c in CENSUS_FIELDS])


def main(argv=None) -> int:
    _ensure_virtual_devices()
    ap = argparse.ArgumentParser(
        prog="repro.analysis.check",
        description="static engine-contract gate: jaxpr/StableHLO "
                    "census + repo-specific AST lint")
    ap.add_argument("--contract", default=DEFAULT_CONTRACT)
    ap.add_argument("--baseline", default=DEFAULT_BASELINE)
    ap.add_argument("--src", default=DEFAULT_SRC,
                    help="source tree the lint layer audits")
    ap.add_argument("--write", action="store_true",
                    help="regenerate the static contract from the "
                         "current programs (hard invariants and lint "
                         "still gate)")
    ap.add_argument("--report", default=None,
                    help="write the full JSON report (census rows, "
                         "lint findings, vmem table) to this path")
    ap.add_argument("--lint-only", action="store_true")
    ap.add_argument("--census-only", action="store_true")
    ap.add_argument("--census-csv", default=None,
                    help="also emit the census counters as CSV")
    args = ap.parse_args(argv)
    if args.lint_only and args.census_only:
        print("error: --lint-only and --census-only are exclusive")
        return 2

    failures: List[str] = []
    report: Dict[str, Any] = {}

    if not args.census_only:
        lint_report, lint_fails = run_lint(args.src, args.baseline)
        failures += lint_fails
        report["lint"] = lint_report
        n_new = len(lint_fails)
        n_ok = len(lint_report["accepted"]) if lint_report else 0
        print(f"lint: {n_new} unbaselined finding(s), "
              f"{n_ok} baselined")

    if not args.lint_only:
        import jax  # after _ensure_virtual_devices

        meshes = available_meshes()
        skipped = [m for m in MESH_SHAPES if m not in meshes]
        if skipped:
            print(f"census: {len(jax.devices())} device(s) — skipping "
                  f"mesh shapes {skipped} (need 8)")
        rows, hard = run_census(meshes)
        failures += hard
        report["census"] = {f"{e}@{m}": c for (e, m), c in
                            sorted(rows.items())}
        print(f"census: {len(rows)} program(s) across "
              f"{len(meshes)} mesh shape(s); "
              f"{len(hard)} hard-invariant violation(s)")

        blocks, block_fails = run_blocks()
        failures += block_fails
        report["vmem"] = blocks
        print(f"vmem: {len(blocks)} kernel/shape row(s), "
              f"{len(block_fails)} over budget")

        if args.census_csv:
            write_census_csv(rows, args.census_csv)
            print(f"census csv -> {args.census_csv}")

        from repro.analysis.contracts import (diff_rows, load_contract,
                                              rows_to_doc,
                                              write_contract)
        if args.write:
            doc = {
                "source": "python -m repro.analysis.check --write",
                "note": "STATIC program-census invariants (lowered, "
                        "never executed); the dynamic runtime "
                        "counterpart is engine_contract.json. "
                        "Regenerate after an intentional engine "
                        "change.",
                "mesh_shapes": {k: v for k, v in MESH_SHAPES.items()},
                "rows": rows_to_doc(rows, KEY),
            }
            if not failures:
                write_contract(args.contract, doc)
                print(f"wrote {len(rows)} census row(s) -> "
                      f"{args.contract}")
        elif os.path.exists(args.contract):
            contract = load_contract(args.contract, KEY)
            # compare only rows whose mesh this process can build
            usable = {k: v for k, v in contract.items()
                      if k[1] in meshes}
            diff_rows(usable, rows, "lowered programs", failures)
        else:
            failures.append(
                f"static contract {args.contract} missing — generate "
                f"it with --write")

    if args.report:
        os.makedirs(os.path.dirname(args.report) or ".", exist_ok=True)
        with open(args.report, "w") as f:
            json.dump({"failures": failures, **report}, f, indent=2)
            f.write("\n")
        print(f"report -> {args.report}")

    if failures:
        print(f"STATIC CONTRACT VIOLATED ({len(failures)} finding(s)):")
        for f_ in failures:
            print(f"  - {f_}")
        return 1
    print("static contract OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
