#!/usr/bin/env python3
"""Device idle time of a batch cell, by what the program's host code was doing.

    python3 bench/spans.py --workload <cell> --seed <n> [--jobs 2]

Sets the cell up as ``bench/run.py`` does (its warm job runs with the
program's tracer off), then runs ``--jobs`` whole jobs, one at a time
through the driver's window, under the JAX profiler and with the process
tracer of ``repro.obs`` enabled. Each program span is then a host event on
the device trace's clock, and every idle stretch of the device is
credited to the innermost span open on the host (``idle_by_span``). The
last line of standard output is one JSON object:

* ``staging_bound_s``: device-idle seconds per job under ``stream.stall``,
  the part of ``copy_stall_s`` that costs ``job_s``;
* ``host_bound_s``: device-idle seconds per job under any other program
  span (``render.png``, ``stream.fill``, ``biggraphvis.fetch``, ...);
* ``bench_idle_s``: device-idle seconds per job under the benchmark's own
  annotations (``bench.window``, ``job.*``) or under none;
* ``compiles_per_job``: mean of the jobs' ``jit_compile_count`` deltas;
* ``idle_by_span`` (seconds per job), ``idle_gaps`` (the longest gaps,
  each labelled by its innermost span), ``jobs`` (each job's ``job_s``,
  ``compiles`` and ``copy_stall_s``), ``busy_s``, ``window_s``, ``device``.

Without a device trace (the CPU backend writes none) the device numbers
are None. Without a TPU the run exits with code 3, as ``bench/run.py``.
"""
import argparse
import json
import shutil
import sys
import time
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(CHECKOUT), str(CHECKOUT / "src")]

from bench import harness, tracereduce  # noqa: E402

STALL = "stream.stall"


def idle_by_span(trace, t0: float, t1: float) -> dict:
    """Device-idle seconds inside the window [t0, t1), split by the name of
    the innermost host span open at each instant (``"none"`` where none
    is), averaged over the device planes. The parts sum to window − busy."""
    planes = list(trace.device_ops.values())
    if not planes:
        return {}
    spans = trace.host_spans
    bounds = sorted({t0, t1} | {t for sp in spans for t in (sp.start_ns, sp.end_ns)
                                if t0 < t < t1})
    labels = [tracereduce.label_at(spans, (a + b) / 2)
              for a, b in zip(bounds, bounds[1:])]
    out: dict = {}
    for evs in planes:
        k = 0
        for s, e in tracereduce.idle_gaps(evs, t0, t1):
            while bounds[k + 1] <= s:
                k += 1
            j = k
            while j < len(labels) and bounds[j] < e:
                ns = min(e, bounds[j + 1]) - max(s, bounds[j])
                out[labels[j]] = out.get(labels[j], 0.0) + ns / 1e9 / len(planes)
                j += 1
    return out


def breakdown(trace, t0: float, t1: float, program: set, jobs: int,
              min_gap_s: float = 0.005, top: int = 40) -> dict:
    """The per-job idle split and the longest gaps of one traced window
    ({} without a device plane)."""
    idle = idle_by_span(trace, t0, t1)
    if not idle:
        return {}
    planes = list(trace.device_ops.values())
    busy_s = sum(tracereduce.busy_ns(evs, t0, t1) for evs in planes) / len(planes) / 1e9
    gaps = sorted(tracereduce.idle_gaps(planes[0], t0, t1), key=lambda g: g[0] - g[1])
    return {
        "staging_bound_s": idle.get(STALL, 0.0) / jobs,
        "host_bound_s": sum(v for k, v in idle.items()
                            if k in program and k != STALL) / jobs,
        "bench_idle_s": sum(v for k, v in idle.items() if k not in program) / jobs,
        "idle_by_span": {k: v / jobs for k, v in
                         sorted(idle.items(), key=lambda kv: -kv[1])},
        "idle_gaps": [[tracereduce.label_at(trace.host_spans, (s + e) / 2),
                       (e - s) / 1e9] for s, e in gaps[:top]
                      if (e - s) / 1e9 >= min_gap_s],
        "busy_s": busy_s,
        "window_s": (t1 - t0) / 1e9,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--jobs", type=int, default=2)
    args = ap.parse_args(argv)

    from bench import run

    wl = harness.workload(args.workload)
    if not run.accelerator_ok(wl):
        return 3
    import jax

    from repro import obs
    from repro.kernels.compat import enable_compile_cache

    enable_compile_cache()
    cfg = harness.config(wl["config"])
    mix = harness.traffic(wl["traffic"])
    driver = harness.load_module("drivers", mix["driver"]).Driver(
        wl, cfg, mix, args.seed)
    driver.setup()

    trace_dir = harness.cache_dir() / "trace" / f"{wl['name']}.spans"
    shutil.rmtree(trace_dir, ignore_errors=True)
    compiles = []
    obs.jit_compile_count()  # the listener counts from here on
    tracer = obs.enable_tracing()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # host annotations only, no Python calls
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(args.jobs):
                c0, t0 = obs.jit_compile_count(), time.perf_counter()
                driver.window(0.0)  # exactly one job
                if driver.failed:
                    break
                compiles.append(obs.jit_compile_count() - c0)
                driver.jobs[-1].update(job_s=time.perf_counter() - t0,
                                       compiles=compiles[-1])
    finally:
        jax.profiler.stop_trace()
        obs.set_tracer(None)
    program = tracer.span_names()

    out = {"staging_bound_s": None, "host_bound_s": None, "bench_idle_s": None}
    path = tracereduce.find_xplane(str(trace_dir))
    if path is not None:
        names = harness.ANNOTATIONS + ("bench.window",) + tuple(sorted(program))
        trace = tracereduce.load(path, names)
        window = [s for s in trace.host_spans if s.name == "bench.window"]
        if window:
            out.update(breakdown(trace, window[-1].start_ns, window[-1].end_ns,
                                 program, len(driver.jobs)))
    shutil.rmtree(trace_dir, ignore_errors=True)
    result = {
        **out,
        "compiles_per_job": sum(compiles) / len(compiles) if compiles else None,
        "jobs": [{k: j[k] for k in ("job_s", "compiles", "copy_stall_s")}
                 for j in driver.jobs],
        "program_spans": sorted(program),
        "errors": driver.errors,
        "device": run.device_line(wl["chips"]),
    }
    print(json.dumps(result), flush=True)
    return 0 if not driver.failed else 1


if __name__ == "__main__":
    sys.exit(main())
