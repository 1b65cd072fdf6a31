#!/usr/bin/env python3
"""Readings for the limits of ``correct``: on each seed, one job of a
cell through the program, the numbers it reads against the plain
reference, and the numbers the control reads in its place.

    python3 chipbench/control.py --workload hi.treecss --seeds 1,2,3

The control is the reference computed one step lower in precision than
the configuration states: the split MLP trained in bfloat16 for the
float32 pipeline cell (whose reference trains in float32 at the stated
matrix-product precision), and 32-bit id hashes in place of the 62-bit
PRF tags for the alignment cell.  Each seed prints one JSON line
``{"seed", "program": {name: value}, "control": {name: value},
"faults": {fault: {name: value}}}``; the lower reading of a number is
the largest the program gives, the upper the smallest the control (or,
for a training cell, a fault) gives.  The benchmark's own runs never run
this; it needs a TPU, like them.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def readings(reg, name: str, seed: int) -> dict:
    cell = reg.workload(name)
    job = reg.job(reg.config(cell["config"]), reg.traffic(cell["traffic"]),
                  seed)
    job.setup()
    t0 = time.perf_counter()
    rec = job.run()
    t_job = time.perf_counter() - t0
    prog = {n.name: n.value for n in job.check([rec])}
    ctl = {n.name: n.value for n in job.control([rec])}
    faults = job.faults([rec])
    job.teardown()
    return {"seed": seed, "job_s": t_job, "program": prog, "control": ctl,
            "faults": faults}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, one job each")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from chipbench import run
    from chipbench.registry import Registry

    reg = Registry(ROOT)
    devices, err = run.find_devices(reg.workload(args.workload)["chips"])
    if err:
        print(f"control: {err}", file=sys.stderr)
        return run.EXIT_NO_CHIP
    run.configure_jax()
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(readings(reg, args.workload, seed)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
