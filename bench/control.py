#!/usr/bin/env python3
"""Readings that the correctness limits are set from (not part of a run).

    python3 bench/control.py --workload <cell> --seeds 1 2 3 ... [--faults] [--out F]

For each seed, in one process: the cell's driver generates the seed's
inputs and drives one window of the timed path at the cell's size; then
every number the check compares is read against the plain reference as
the configuration states it, for the window's output (the sound reading)
and for each control, the same reference with parts one precision lower
(``Driver.readings``). Each seed prints one JSON line ``{"seed",
"sound", "control", ...}``, also appended to ``--out``. A limit lies
above the largest sound reading and below the smallest control reading.

With ``--faults``, each fault of ``bench/faults.py`` is then planted in
turn and drives one more window on the last seed's inputs; its line
``{"fault", "numbers", "correct"}`` says whether the cell's limits catch
it.
"""
import argparse
import json
import sys
import time
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(CHECKOUT), str(CHECKOUT / "src")]

from bench import faults, harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=0.0)
    ap.add_argument("--faults", action="store_true")
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    from repro.kernels.compat import enable_compile_cache

    enable_compile_cache()
    wl = harness.workload(args.workload)
    cfg = harness.config(wl["config"])
    mix = harness.traffic(wl["traffic"])
    mod = harness.load_module("drivers", mix["driver"])
    drv = None
    for seed in args.seeds:
        t0 = time.perf_counter()
        drv = mod.Driver(wl, cfg, mix, seed)
        drv.setup(warm=False)
        drv.window(args.seconds)
        drv.release()
        t1 = time.perf_counter()
        line = {"seed": seed, "window_s": t1 - t0, "errors": drv.errors}
        line.update(drv.readings())
        line["reference_s"] = time.perf_counter() - t1
        emit(line, args.out)
    if args.faults and drv is not None:
        limits = harness.limits(wl["name"])
        for name, plant in faults.BATCH.items():
            emit(fault_line(drv, name, plant, limits, args.seconds), args.out)
    return 0


def fault_line(drv, name, plant, limits, seconds) -> dict:
    """One window of ``drv``'s cell with the fault planted, judged as a run
    judges its window."""
    import jax

    drv.jobs, drv.out, drv.errors, drv.attempted, drv.failed = [], None, [], 0, 0
    patch = faults.Patch()
    plant(patch.setattr)
    jax.clear_caches()  # no program traced before the fault is reused
    try:
        drv.window(seconds)
    finally:
        patch.undo()
        jax.clear_caches()
    checks = drv.check(limits)
    numbers = drv.numbers(drv.out, drv.ref) if drv.out is not None else {}
    correct = drv.failed == 0 and all(
        c["value"] is not None and c["value"] <= c["limit"] for c in checks.values())
    return {"fault": name, "numbers": numbers, "correct": correct,
            "errors": drv.errors}


def emit(line: dict, out: str | None) -> None:
    print(json.dumps(line), flush=True)
    if out:
        with open(out, "a") as f:
            f.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    sys.exit(main())
