#!/usr/bin/env python3
"""What the program's tracing costs per job in one cell, on the chip.

    python3 chipbench/trace_cost.py --workload fig7.align10 --seed 7 --rounds 6

Sets the cell's job up as ``run.py`` does (the compile cache in the
checkout, one warm-up job), then runs ``--rounds`` rounds of three
jobs, one per set-up, in turn: untraced; under ``repro.obs.Tracer()``;
and under ``Tracer(jax_profiler=True)`` inside a JAX profiler session,
as a ``--trace 1`` run has it.  Prints one JSON line with each set-up's
job seconds, their median, and that median over the untraced one.
Like ``run.py`` it refuses to run without a TPU.  Nothing reads its
numbers as a benchmark metric.
"""
from __future__ import annotations

import argparse
import json
import shutil
import statistics
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
MODES = ("untraced", "tracer", "profiler")


def measure(job, rounds: int, trace_dir: Path) -> dict:
    """Seconds of each job, by set-up; the set-ups take turns, so drift
    over the run falls on all three alike."""
    import jax

    from repro.obs.trace import Tracer, now, use_tracer

    times = {m: [] for m in MODES}
    for _ in range(rounds):
        for mode in MODES:
            tracer = (None if mode == "untraced"
                      else Tracer(jax_profiler=mode == "profiler"))
            if mode == "profiler":
                jax.profiler.start_trace(str(trace_dir))
            with use_tracer(tracer):
                t0 = now()
                job.run()
                times[mode].append(now() - t0)
            if mode == "profiler":
                jax.profiler.stop_trace()
                shutil.rmtree(trace_dir, ignore_errors=True)
    return times


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rounds", type=int, default=6)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

    from chipbench import run
    from chipbench.registry import Registry

    reg = Registry(ROOT)
    cell = reg.workload(args.workload)
    devices, err = run.find_devices(cell["chips"])
    if err:
        run.log(f"trace_cost: {err}")
        return run.EXIT_NO_CHIP
    run.configure_jax()
    job = reg.job(reg.config(cell["config"]), reg.traffic(cell["traffic"]),
                  args.seed)
    job.setup()
    try:
        job.run()                                   # warm-up: every shape
        times = measure(job, args.rounds, run.TRACE_DIR)
    finally:
        job.teardown()
    median = {m: statistics.median(v) for m, v in times.items()}
    print(json.dumps({
        "workload": args.workload, "seed": args.seed,
        "device": devices[0].device_kind, "rounds": args.rounds,
        "job_s": times, "median_s": median,
        "over_untraced": {m: median[m] / median["untraced"] for m in MODES},
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
