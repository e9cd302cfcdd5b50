"""Registry and shared pieces of the benchmark.

Everything that belongs to one configuration, traffic mix, per-layer
metric or kernel lives in a file of its own and is found here by name:

    bench/configs/<config>.json        sizes, generator, program settings
    bench/traffic/<traffic>.json       parameters read by one driver
    bench/drivers/<driver>.py          the general generator of a mix kind
    bench/limits/<workload>.json       the limits of the correctness check
    bench/metrics/<metric>.py          per-layer reader: read(ctx) -> float | None
    bench/roofline/<kernel>.py         work(ctx) -> (flops, bytes) of a window
    bench/peaks.json                   device peaks by device_kind
"""
from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
CHECKOUT = BENCH.parent

# Host annotations the trace reduction labels idle gaps with.
ANNOTATIONS = ("job.pipeline", "job.render", "job.png")


def cache_dir() -> Path:
    """``bench/.cache``: generated graphs, images and traces (gitignored)."""
    return BENCH / ".cache"


def _read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return _read_json(CHECKOUT / "BENCHMARK.json")


def workload(name: str, bench: dict | None = None) -> dict:
    for wl in (bench or benchmark())["workloads"]:
        if wl["name"] == name:
            return wl
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(name: str) -> dict:
    return _read_json(BENCH / "configs" / f"{name}.json")


def traffic(name: str) -> dict:
    return _read_json(BENCH / "traffic" / f"{name}.json")


def limits(workload_name: str) -> dict:
    return _read_json(BENCH / "limits" / f"{workload_name}.json")


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = BENCH / kind / f"{name}.py"
    mod_name = f"bench_{kind}_{name.replace('.', '_')}"
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    if spec is None or not path.exists():
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def peaks(device_kind: str) -> dict:
    table = _read_json(BENCH / "peaks.json")
    if device_kind not in table["devices"]:
        raise KeyError(f"no published peaks for device kind {device_kind!r}")
    return table["devices"][device_kind]


def roofline_share(ctx: dict, kernel: str) -> float | None:
    """Percent of the kernel's roofline reached in the traced window: the
    least time the chip could take for the work (larger of flops over the
    peak rate and bytes over the peak bandwidth) over the kernel's device
    time. None where the trace holds no such kernel or no work was done."""
    seconds = ctx.get("kernel_seconds", {}).get(kernel)
    if not seconds:
        return None
    flops, nbytes = load_module("roofline", kernel).work(ctx)
    if flops <= 0 and nbytes <= 0:
        return None
    pk = ctx["peaks"]
    floor_s = max(flops / pk["flops_per_s"], nbytes / pk["bytes_per_s"])
    return 100.0 * floor_s / seconds


def metrics_for(kind: str, workload_name: str, bench: dict) -> list[dict]:
    """Entries of ``bench[kind]`` ("end_to_end" or "per_layer") that this
    workload reports: those whose ``workloads`` list names it, or that
    have no such list."""
    return [m for m in bench[kind]
            if workload_name in m.get("workloads", [workload_name])]
