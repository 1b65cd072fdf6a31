"""A tiny copy of the benchmark for the CPU: the repository's
configurations and mixes with their sizes cut down, in a temporary
directory, driven through ``chipbench.run.execute``.  The copy also has
a cell ``hi.treeall``: the pipeline kind's TREEALL variant (every
aligned row trained), which no cell of the benchmark runs, so that the
kind's code for it stays tested."""
import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:          # the benchmark sits beside src/
    sys.path.insert(0, str(ROOT))

# sizes a test run can hold; every other key is the configuration's own
TINY = {"hi-3p": {"dataset": {"n_instances": 1500}},
        "fig7-10p": {"ids_per_party": 3000}}
# the training limits for that size: at 1,050 train rows the float32
# program on the CPU reads ~1e-7 on both numbers and the bfloat16
# control 2.0e-3..5.2e-3 (loss) and 6.1e-3..6.7e-2 (change)
TINY_LIMITS = {"train_loss_gap": 1e-3, "train_change_gap": 3e-3}
TREEALL_MIX = {"job": "pipeline", "variant": "treeall", "epochs": 7,
               "metric": "pipeline_s", "trace_jobs": 2}
TREEALL_CELL = {"name": "hi.treeall", "config": "hi-3p",
                "traffic": "treeall", "chips": 1,
                "why": "TREEALL: every aligned row trained"}


def _merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = _merge(base[k], v) if isinstance(v, dict) else v
    return out


def tiny_bench(tmp: Path) -> Path:
    """Copy BENCHMARK.json and chipbench/ under ``tmp`` with tiny sizes;
    returns the copy's root."""
    root = tmp / "bench"
    shutil.copytree(ROOT / "chipbench", root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["workloads"].append(TREEALL_CELL)
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "hi.treecss" in m.get("workloads", []):
            m["workloads"].append("hi.treeall")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    (root / "chipbench" / "traffic" / "treeall.json").write_text(
        json.dumps(dict(TREEALL_MIX, limits={})))
    for name, over in TINY.items():
        p = root / "chipbench" / "configs" / f"{name}.json"
        p.write_text(json.dumps(_merge(json.loads(p.read_text()), over)))
    for p in (root / "chipbench" / "traffic").glob("*.json"):
        t = json.loads(p.read_text())
        if "limits" in t:
            t["limits"].update(TINY_LIMITS)
        p.write_text(json.dumps(t))
    return root


def run_cell(root: Path, cell: str, seed: int, seconds: float = 0.2):
    """One run of ``cell`` on the CPU, past the harness's look for a
    chip; returns the result object."""
    import jax

    from chipbench import run
    from chipbench.registry import Registry

    reg = Registry(root, home=root / "chipbench")
    compiles = run.Compiles()
    return run.execute(reg, cell, seed, seconds, False, jax.devices(),
                       compiles, time.perf_counter())
