#!/usr/bin/env python3
"""Run one benchmark cell on the accelerator this machine holds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``bench/configs``) and a traffic mix (``bench/traffic``), whose
``driver`` (``bench/drivers``) sets up, warms up and drives the window.
Set-up counts from the start of this process to the end of the warm-up.
After the window the program's device state is freed and the driver
compares what the window produced with the plain reference; each number
compared is printed beside its limit on standard error and under
``checks`` in the result line. With ``--trace 0`` the result holds the
cell's end-to-end metrics; with ``--trace 1`` the window runs under the
JAX profiler and the result holds its per-layer metrics, the device's
busy and window seconds, and a breakdown. The last line of standard
output is that result, one JSON object.

Without a TPU, or with fewer chips than the cell asks for, the run exits
with code 3 and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

CHECKOUT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(CHECKOUT), str(CHECKOUT / "src")]

from bench import harness  # noqa: E402


def device_line(chips: int) -> dict:
    import jax

    devs = jax.devices()
    peak = 0
    for d in devs[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def accelerator_ok(wl: dict) -> bool:
    """Whether JAX found a TPU with at least the chips the cell asks for."""
    import jax

    devs = jax.devices()
    if devs[0].platform == "tpu" and len(devs) >= wl["chips"]:
        return True
    print(f"bench: cell {wl['name']} needs {wl['chips']} TPU chip(s); JAX "
          f"found {len(devs)} {devs[0].platform} device(s). Nothing was run.",
          file=sys.stderr)
    return False


def traced_context(trace_dir: Path, kernels: list[str]) -> dict:
    """Busy/window seconds, breakdown and kernel seconds of the window."""
    from bench import tracereduce

    path = tracereduce.find_xplane(str(trace_dir))
    if path is None:
        return {}
    trace = tracereduce.load(path, harness.ANNOTATIONS + ("bench.window",))
    spans = [s for s in trace.host_spans if s.name == "bench.window"]
    if not spans:
        return {}
    t0, t1 = spans[-1].start_ns, spans[-1].end_ns
    out = tracereduce.reduce(trace, t0, t1)
    out["kernel_seconds"] = {
        k: tracereduce.kernel_seconds(trace, k, t0, t1) for k in kernels}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[1])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = harness.benchmark()
    wl = harness.workload(args.workload, bench)
    if not accelerator_ok(wl):
        return 3
    import jax

    from repro.kernels.compat import enable_compile_cache

    enable_compile_cache()
    cfg = harness.config(wl["config"])
    mix = harness.traffic(wl["traffic"])
    driver = harness.load_module("drivers", mix["driver"]).Driver(
        wl, cfg, mix, args.seed)
    driver.setup()
    setup_s = time.perf_counter() - T_START

    trace_dir = harness.cache_dir() / "trace" / wl["name"]
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # host annotations only, no Python calls
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            e2e = driver.window(args.seconds)
    finally:
        if args.trace:
            jax.profiler.stop_trace()
    device = device_line(wl["chips"])
    driver.release()

    checks = driver.check(harness.limits(wl["name"]))
    correct = driver.failed == 0 and all(
        c["value"] is not None and c["value"] <= c["limit"]
        for c in checks.values())

    metrics = {}
    breakdown = None
    if args.trace:
        per_layer = harness.metrics_for("per_layer", wl["name"], bench)
        kernels = [m["name"][: -len("_roofline")] for m in per_layer
                   if m["name"].endswith("_roofline")]
        ctx = driver.layer_context()
        ctx.update(traced_context(trace_dir, kernels))
        ctx["peaks"] = harness.peaks(device["kind"])
        shutil.rmtree(trace_dir, ignore_errors=True)
        if "busy_s" in ctx:
            device["busy_s"] = ctx["busy_s"]
            device["window_s"] = ctx["window_s"]
            breakdown = ctx["breakdown"]
        for m in per_layer:
            value = harness.load_module("metrics", m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        e2e["setup_s"] = setup_s
        e2e["peak_hbm_gb"] = device["memory_peak_bytes"] / 1e9
        for m in harness.metrics_for("end_to_end", wl["name"], bench):
            if e2e.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}

    for err in driver.errors:
        print(f"bench: window error: {err}", file=sys.stderr)
    for name, c in checks.items():
        ok = c["value"] is not None and c["value"] <= c["limit"]
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r}) "
              f"{'ok' if ok else 'FAILED'}", file=sys.stderr)
    result = {"correct": correct, "attempted": driver.attempted,
              "failed": driver.failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except Exception:
        traceback.print_exc()
        sys.exit(1)
