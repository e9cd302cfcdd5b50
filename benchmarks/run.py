"""Benchmark harness entry point — one module per paper table/figure plus
the engine benches. Prints
``name,us_per_call,derived`` CSV and writes each module's rows as unified
structured records (benchmarks/common.py schema) to repo-root
``BENCH_<name>.json``; ``--list`` prints the registry with each bench's
one-line description.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--list] [--only fa2,agg]
"""
from __future__ import annotations

import argparse
import os
import sys
import time
import traceback

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def step_summary(bench: str, lines: list[str]) -> None:
    """Append a bench's ``--check`` result lines to the GitHub Actions job
    summary ($GITHUB_STEP_SUMMARY, a markdown file the runner renders under
    the job). No-op outside CI (env var unset) or with nothing to report —
    benches call this unconditionally after their checks pass.
    """
    path = os.environ.get("GITHUB_STEP_SUMMARY")
    if not path or not lines:
        return
    with open(path, "a") as f:
        f.write(f"### {bench}\n\n")
        for line in lines:
            f.write(f"- {line}\n")
        f.write("\n")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="1 graph / fewer sweeps")
    ap.add_argument("--only", default="", help="comma-separated module subset")
    ap.add_argument("--list", action="store_true",
                    help="print the bench registry (name + one-line "
                         "description) and exit")
    ap.add_argument("--no-json", action="store_true",
                    help="skip writing repo-root BENCH_<name>.json records")
    args = ap.parse_args()

    from benchmarks import (agg_bench, fa2_bench, fig_params, kernels_bench,
                            obs_bench, quality_bench, render_bench,
                            resilience_bench, serve_bench, shard_bench,
                            stream_bench, table1_speedup, table2_hashes,
                            table3_rounds)
    from benchmarks.common import record_from_csv, write_bench_json

    modules = {
        "table1": table1_speedup,
        "table2": table2_hashes,
        "table3": table3_rounds,
        "figs": fig_params,
        "kernels": kernels_bench,
        "stream": stream_bench,
        "agg": agg_bench,
        "render": render_bench,
        "serve": serve_bench,
        "fa2": fa2_bench,
        "quality": quality_bench,
        "shard": shard_bench,
        "obs": obs_bench,
        "resilience": resilience_bench,
    }
    if args.list:
        width = max(map(len, modules))
        for name, mod in modules.items():
            desc = (mod.__doc__ or "").strip().splitlines()[0]
            print(f"{name:<{width}}  {desc}")
        return
    if args.only:
        keep = set(args.only.split(","))
        modules = {k: v for k, v in modules.items() if k in keep}

    print("name,us_per_call,derived")
    failures = 0
    for name, mod in modules.items():
        try:
            lines = []
            for line in mod.run(quick=args.quick):
                print(line)
                lines.append(line)
        except Exception:
            failures += 1
            print(f"{name},0,ERROR", file=sys.stderr)
            traceback.print_exc()
            continue
        if not args.no_json:
            records = [r for r in map(record_from_csv, lines) if r]
            if records:
                path = os.path.join(REPO_ROOT, f"BENCH_{name}.json")
                write_bench_json(path, name, records, timestamp=time.time(),
                                 quick=args.quick)
                print(f"wrote {path} ({len(records)} records)",
                      file=sys.stderr)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
