"""Finds every piece of the benchmark by the name ``BENCHMARK.json`` gives:

- a cell by its ``name`` in ``workloads``;
- its configuration as ``configs/<config>.json``;
- its traffic mix as ``traffic/<traffic>.json``, data whose ``job`` key
  names its job kind;
- each job kind as ``kinds/<job>.py``, a module with a class ``Job``
  (``chipbench.jobs``);
- each per-layer metric as ``metrics/<metric>.py``, a module with a
  ``read(ctx)`` that returns the number or ``None`` when it finds
  nothing to read.

Adding a configuration, a mix, a job kind or a metric adds files and
entries; no file here changes.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import Callable, Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


class Registry:
    def __init__(self, root: Path = ROOT, home: Optional[Path] = None):
        self.root = Path(root)
        self.home = Path(home) if home is not None else HERE
        self.bench = json.loads((self.root / "BENCHMARK.json").read_text())

    def workload(self, name: str) -> dict:
        for w in self.bench["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{[w['name'] for w in self.bench['workloads']]}")

    def config(self, name: str) -> dict:
        return json.loads((self.home / "configs" / f"{name}.json"
                           ).read_text())

    def traffic(self, name: str) -> dict:
        return json.loads((self.home / "traffic" / f"{name}.json"
                           ).read_text())

    def _module(self, folder: str, name: str):
        path = self.home / folder / f"{name}.py"
        spec = importlib.util.spec_from_file_location(
            f"chipbench_{folder}_" + name.replace(".", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def metric_reader(self, name: str) -> Callable:
        return self._module("metrics", name).read

    def job(self, config: dict, traffic: dict, seed: int):
        """A job of the mix's kind on the configuration, from ``seed``."""
        return self._module("kinds", traffic["job"]).Job(config, traffic,
                                                         seed)

    def end_to_end(self, cell: str):
        """The cell's end-to-end metric entries."""
        return [m for m in self.bench["end_to_end"]
                if cell in m.get("workloads", [cell])]

    def per_layer(self, cell: str):
        """The cell's per-layer metric entries: those that list it, and
        those without a list whose end-to-end metric the cell reports."""
        e2e = {m["name"] for m in self.end_to_end(cell)}
        return [m for m in self.bench["per_layer"]
                if cell in m.get("workloads", [])
                or ("workloads" not in m and m["moves"] in e2e)]
