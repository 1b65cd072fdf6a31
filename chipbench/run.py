#!/usr/bin/env python3
"""Run one cell of the chip benchmark and print its result line.

    python3 chipbench/run.py --workload hi.treecss --seed 7 --seconds 30 --trace 0

One process, one cell.  It finds the cell, its configuration and its
traffic by name (``chipbench.registry``), makes the deployment from
``--seed``, runs one warm-up job (set-up ends there: ``setup_s`` is the
time from process start), then starts jobs back to back while the window
of ``--seconds`` is open.  Each end-to-end metric is the whole time of
the window's jobs over their number.  With ``--trace 1`` it instead runs
the traffic's ``trace_jobs`` whole jobs under the JAX profiler and the
program's spans and reports the cell's per-layer metrics.  After the
window it reads the peak device memory, then checks what the jobs
produced against the plain reference (the job kind's ``check``).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown``
when traced), and last ``checks``, each compared number with its limit;
the same numbers close standard error.  Without a TPU, with fewer chips
than the cell asks for, or on a device the peak table does not know, it
exits non-zero and prints no result; so it does when a metric the cell
lists reads nothing.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
TRACE_DIR = ROOT / ".chipbench_trace"     # fixed, inside the checkout
CACHE_DIR = ROOT / ".jax_cache"           # fixed, inside the checkout

EXIT_NO_CHIP = 3
EXIT_UNKNOWN_DEVICE = 4


class MissingMetric(RuntimeError):
    """A metric the cell lists came out empty: its reader or the job kind
    found nothing where there had to be something (a kernel name that
    the trace does not carry, a span that did not run)."""

    def __init__(self, name: str):
        super().__init__(f"metric {name!r} is listed for this cell but read "
                         f"nothing")


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


class Compiles:
    """Seconds JAX spends making programs (tracing, lowering, compiling,
    or loading from the persistent cache), from ``jax.monitoring``."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        self.seconds = 0.0
        self.count = 0
        self.compiled = 0

    def install(self) -> None:
        from jax import monitoring

        def on_duration(event, secs, **_):
            if event in self.EVENTS:
                self.seconds += secs
                self.count += 1
                if event == self.EVENTS[2]:
                    self.compiled += 1
        monitoring.register_event_duration_secs_listener(on_duration)

    def snapshot(self):
        return self.seconds, self.count, self.compiled


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def find_devices(chips: int):
    """The accelerator devices, or an error message when there are none
    or too few.  Never falls back to the CPU."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        return None, (f"no TPU: JAX's device is {devices[0].platform!r} "
                      f"({devices[0].device_kind}); the benchmark never "
                      f"runs on the CPU")
    if len(devices) < chips:
        return None, f"the cell needs {chips} chips, JAX found {len(devices)}"
    return devices[:chips], None


def configure_jax() -> str:
    """The persistent compile cache at a fixed directory inside the
    checkout, handed to the program through ``JAX_COMPILATION_CACHE_DIR``
    (which ``repro.launch.cache`` honours), with the size and time
    thresholds off so that the jobs' small programs are cached too (by
    default a program that compiles in under a second is not written,
    and every run compiles it again)."""
    import os

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax

    from repro.launch.cache import enable_compile_cache

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    cache = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache


def memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def run_window(job, seconds: float, compiles: Compiles):
    """Jobs back to back while the window is open; every job that starts
    is waited for.  Returns (records, attempted, failed, seconds of the
    completed jobs, job times, compile seconds and count in the window)."""
    from repro.obs.trace import now

    records, times = [], []
    failed = attempted = 0
    c0 = compiles.snapshot()
    t0 = now()
    t_end = t0 + seconds
    while now() < t_end:
        attempted += 1
        j0 = now()
        try:
            records.append(job.run())
            times.append(now() - j0)
        except Exception:          # a failed job is counted, not fatal
            failed += 1
            log(traceback.format_exc())
    total = now() - t0
    c1 = compiles.snapshot()
    return (records, attempted, failed, total, times,
            c1[0] - c0[0], c1[1] - c0[1], c1[2] - c0[2])


def run_traced(job, n_jobs: int, compiles: Compiles):
    """``n_jobs`` whole jobs under the JAX profiler and the program's
    spans (``Tracer(jax_profiler=True)``: every span is also a
    ``TraceAnnotation`` on the profiler's clock)."""
    import jax

    from repro.obs.trace import Tracer, now, span, use_tracer

    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    tracer = Tracer(jax_profiler=True)
    records = []
    failed = 0
    c0 = compiles.snapshot()
    with jax.profiler.trace(str(TRACE_DIR)):
        t0 = now()
        with use_tracer(tracer):
            for i in range(n_jobs):
                with span("bench.job", index=i):
                    try:
                        records.append(job.run())
                    except Exception:
                        failed += 1
                        log(traceback.format_exc())
        total = now() - t0
    c1 = compiles.snapshot()
    return (records, n_jobs, failed, total, tracer,
            c1[0] - c0[0], c1[1] - c0[1])


def execute(reg, name: str, seed: int, seconds: float, trace: bool,
            devices, compiles: Compiles, t_start: float) -> dict:
    """Set-up, warm-up, window and check of one cell on ``devices``;
    returns the result object.  ``main`` finds the devices; tests call
    this directly on the CPU with the timed path broken underneath."""
    cell = reg.workload(name)
    config = reg.config(cell["config"])
    traffic = reg.traffic(cell["traffic"])
    kind = devices[0].device_kind
    job = reg.job(config, traffic, seed)
    job.setup()
    try:
        job.run()                                   # warm-up: every shape
        c_setup = compiles.snapshot()
        setup_s = time.perf_counter() - t_start
        log(f"chipbench: set-up {setup_s:.3f} s (compile/load "
            f"{c_setup[0]:.3f} s over {c_setup[1]} events, "
            f"{c_setup[2]} backend compiles)")

        device = {"platform": devices[0].platform, "kind": kind,
                  "count": len(devices)}
        result = {}
        if trace:
            (records, attempted, failed, total, tracer, comp_s,
             comp_n) = run_traced(job, int(traffic["trace_jobs"]), compiles)
            device["memory_peak_bytes"] = memory_peak(devices)
            from chipbench import trace_reduce
            from chipbench.context import Context

            tr = trace_reduce.reduce_dir(TRACE_DIR, n_devices=len(devices))
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            ctx = Context(config=config, device_kind=kind,
                          spans=tracer.finished(), trace=tr,
                          jobs=[job.work(r) for r in records],
                          compile_s=comp_s)
            metrics = {}
            for m in reg.per_layer(name):
                value = reg.metric_reader(m["name"])(ctx)
                if value is None and name in m.get("workloads", []):
                    # listed for this cell, so there was something to read
                    raise MissingMetric(m["name"])
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
            device["busy_s"] = tr.busy_s
            device["window_s"] = tr.window_s
            result["breakdown"] = {"device_ops": tr.top_ops(10),
                                   "idle_gaps": tr.top_gaps(10)}
            log(f"chipbench: traced {len(records)} jobs in {total:.3f} s; busy "
                f"{tr.busy_s:.4f} s of {tr.window_s:.4f} s; compile/load "
                f"{comp_s:.3f} s over {comp_n} events")
        else:
            (records, attempted, failed, total, times, comp_s, comp_n,
             comp_b) = run_window(job, seconds, compiles)
            device["memory_peak_bytes"] = memory_peak(devices)
            log(f"chipbench: window {total:.3f} s, {len(records)} jobs "
                f"({attempted} started, {failed} failed); job seconds "
                f"{[round(t, 4) for t in times]}; compile/load in window "
                f"{comp_s:.3f} s over {comp_n} events, {comp_b} backend "
                f"compiles")
            values = job.end_to_end(total, records) if records else {}
            values["setup_s"] = setup_s
            metrics = {}
            for m in reg.end_to_end(name):
                if m["name"] in values:
                    metrics[m["name"]] = {"value": values[m["name"]],
                                          "unit": m["unit"]}
                elif records:
                    raise MissingMetric(m["name"])

        numbers = job.check(records) if records else []
    finally:
        job.teardown()
    correct = bool(records) and failed == 0 and all(n.ok for n in numbers)
    for n in numbers:
        log(f"check {n.name} = {n.value!r} (limit {n.limit!r}) "
            f"{'ok' if n.ok else 'FAILED'}")
    log(f"correct {correct}")
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    out.update(result)
    out["checks"] = {n.name: {"value": n.value if n.value < float("inf")
                              else str(n.value), "limit": n.limit}
                     for n in numbers}
    return out


def main(argv=None) -> int:
    args = parse(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import repro  # noqa: F401  (the system under test must be present)

    from chipbench import peaks
    from chipbench.registry import Registry

    reg = Registry(ROOT)
    cell = reg.workload(args.workload)
    devices, err = find_devices(cell["chips"])
    if err:
        log(f"chipbench: {err}")
        return EXIT_NO_CHIP
    try:
        peaks.device(devices[0].device_kind)
    except peaks.UnknownDevice as e:
        log(f"chipbench: {e}")
        return EXIT_UNKNOWN_DEVICE
    cache = configure_jax()
    compiles = Compiles()
    compiles.install()
    log(f"chipbench: {args.workload} seed={args.seed} on "
        f"{devices[0].platform} {devices[0].device_kind} x{len(devices)}; "
        f"compile cache {cache}")
    out = execute(reg, args.workload, args.seed, args.seconds,
                  bool(args.trace), devices, compiles, T_START)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
