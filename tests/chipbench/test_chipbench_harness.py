"""The chip benchmark's harness on the CPU: it refuses to measure
without a TPU, finds every piece by name, and runs each mix's job at a
tiny size through its internal entry."""
import hashlib
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from _tiny import ROOT, run_cell, tiny_bench


def _run(cwd: Path, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "hi.treecss",
         "--seed", str(2**31 + 3), "--seconds", "1", "--trace", "0", *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_cpu_backend_exits_nonzero_and_prints_no_result():
    p = _run(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_checkout_without_the_program_exits_nonzero(tmp_path):
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def _digests(root: Path):
    return {str(p.relative_to(root)): hashlib.sha1(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()
            and "__pycache__" not in p.parts}


def test_config_mix_and_metric_added_as_files_are_found_by_name(tmp_path):
    from chipbench.context import Context
    from chipbench.registry import Registry

    root = tmp_path / "bench"
    shutil.copytree(ROOT / "chipbench", root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    before = _digests(root / "chipbench")
    home = root / "chipbench"
    cfg = json.loads((home / "configs" / "hi-3p.json").read_text())
    cfg["parties"] = 4
    (home / "configs" / "hi-4p.json").write_text(json.dumps(cfg))
    (home / "traffic" / "treecss_k6.json").write_text(json.dumps(
        {"job": "pipeline", "variant": "treecss", "clusters_per_client": 6,
         "epochs": 3, "metric": "pipeline_s", "trace_jobs": 1}))
    (home / "metrics" / "jobs_traced.py").write_text(
        "def read(ctx):\n    return float(ctx.n_jobs) or None\n")
    (home / "kinds" / "count.py").write_text(
        "from chipbench import jobs\n\n\nclass Job(jobs.Job):\n"
        "    def run(self):\n        return {'seed': self.seed}\n\n"
        "    def end_to_end(self, seconds, records):\n"
        "        return {'jobs_per_s': len(records) / seconds}\n")
    (home / "traffic" / "count.json").write_text(json.dumps({"job": "count"}))
    bench["configs"].append({"name": "hi-4p", "source": "x",
                             "file": "chipbench/configs/hi-4p.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "hi4.k6", "config": "hi-4p",
                               "traffic": "treecss_k6", "chips": 1,
                               "why": "x"})
    bench["end_to_end"][0]["workloads"].append("hi4.k6")
    bench["per_layer"].append({"name": "jobs_traced", "unit": "1",
                               "better": "higher", "source": "program_span",
                               "layer": "x", "moves": "pipeline_s"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    reg = Registry(root, home=home)
    cell = reg.workload("hi4.k6")
    assert reg.config(cell["config"])["parties"] == 4
    assert reg.traffic(cell["traffic"])["clusters_per_client"] == 6
    names = [m["name"] for m in reg.per_layer("hi4.k6")]
    assert "jobs_traced" in names
    assert "jobs_traced" in [m["name"] for m in reg.per_layer("hi.treecss")]
    assert "jobs_traced" not in [m["name"] for m in
                                 reg.per_layer("fig7.align10")]
    ctx = Context(config=cfg, device_kind="TPU v5 lite", spans=[],
                  trace=None, jobs=[{}, {}], compile_s=0.0)
    assert reg.metric_reader("jobs_traced")(ctx) == 2.0
    job = reg.job(cfg, reg.traffic("count"), 5)
    assert job.end_to_end(2.0, [job.run()] * 3) == {"jobs_per_s": 1.5}
    assert job.run() == {"seed": 5}
    after = _digests(home)
    assert {k: v for k, v in after.items() if k in before} == before
    assert set(after) - set(before) == {"configs/hi-4p.json",
                                        "traffic/treecss_k6.json",
                                        "metrics/jobs_traced.py",
                                        "kinds/count.py",
                                        "traffic/count.json"}


@pytest.mark.parametrize("cell", ["hi.treecss", "hi.treeall",
                                  "fig7.align10"])
def test_each_mix_runs_tiny_on_the_cpu_and_checks_correct(tmp_path, cell):
    out = run_cell(tiny_bench(tmp_path), cell, seed=2**31 + 17)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert out["device"]["platform"] == "cpu"
    metric = "align_s" if cell.startswith("fig7") else "pipeline_s"
    assert out["metrics"][metric]["value"] > 0
    assert out["metrics"]["setup_s"]["value"] > 0
    assert list(out)[-1] == "checks"
