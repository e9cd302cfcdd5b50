"""Serving launcher: the interactive tile-pyramid layout service.

    PYTHONPATH=src python -m repro.launch.serve --nodes 3000 --depth 3
    PYTHONPATH=src python -m repro.launch.serve --edges edges.npy --nodes 50000

Computes a BigGraphVis layout (synthetic planted-partition graph by
default, or any ``repro.data.edge_store`` source via ``--edges``), builds
the tile pyramid (``repro/serve/tiles.py``), precomputes the low-zoom
levels into the LRU cache, and serves a synthetic zipfian pan/zoom trace,
reporting tiles/s, cache hit rate, miss-latency percentiles, and the
steady-state recompile count (which should be zero — fixed tile shapes).

The start path turns on JAX's persistent compilation cache
(``kernels/compat.enable_compile_cache``: ``$JAX_COMPILATION_CACHE_DIR``
where set, else ``.bgv-compile-cache/`` at the checkout root;
``--no-compile-cache`` disables), so a restarted service deserializes its
compiled render/layout steps instead of recompiling them — cold-start
compile otherwise dominates first-request latency. The former LM decode demo lives on in
``examples/serve_lm.py`` (engine: ``repro/serve/engine.py``).
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from repro.kernels.compat import enable_compile_cache
from repro.obs.cli import add_obs_args, obs_session


def percentile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values), q)) if len(values) else 0.0


def main() -> None:
    ap = argparse.ArgumentParser(
        description="Interactive tile-pyramid layout service over a "
                    "BigGraphVis result"
    )
    ap.add_argument("--edges", default="",
                    help="edge source (.npy/.bin/shard dir); default: "
                         "synthetic planted-partition graph")
    ap.add_argument("--nodes", type=int, default=3000, help="node count")
    ap.add_argument("--communities", type=int, default=30,
                    help="planted communities (synthetic graph only)")
    ap.add_argument("--depth", type=int, default=3,
                    help="pyramid levels (level z = 2^z x 2^z tiles)")
    ap.add_argument("--tile-size", type=int, default=256,
                    help="square tile resolution in pixels")
    ap.add_argument("--cache-mb", type=float, default=64.0,
                    help="tile cache capacity in MiB")
    ap.add_argument("--slots", type=int, default=8,
                    help="max tile renders per engine tick")
    ap.add_argument("--iterations", type=int, default=60,
                    help="supergraph FA2 iterations")
    ap.add_argument("--requests", type=int, default=400,
                    help="synthetic pan/zoom requests to serve")
    ap.add_argument("--zipf", type=float, default=1.1,
                    help="zipf exponent of the tile popularity ranking")
    ap.add_argument("--drill-frac", type=float, default=0.05,
                    help="fraction of requests drilling into a community")
    ap.add_argument("--seed", type=int, default=0, help="traffic seed")
    ap.add_argument("--no-compile-cache", action="store_true",
                    help="skip persistent compilation caching")
    add_obs_args(ap)
    args = ap.parse_args()

    with obs_session(args):
        _run(args)


def _run(args) -> None:
    # Before any compilation: a warm cache turns the service's cold-start
    # compiles into deserialization.
    cache = None if args.no_compile_cache else enable_compile_cache()
    print(f"compile cache: {cache or 'off'}")

    import jax

    from repro.core import biggraphvis, default_config
    from repro.graph import mode_degree, planted_partition
    from repro.serve.tiles import (
        DrillSpec,
        TileConfig,
        TileEngine,
        TilePyramid,
        TileRequest,
        jit_compile_count,
        synthetic_trace,
    )

    n = args.nodes
    if args.edges:
        from repro.data.edge_store import as_edge_store

        store = as_edge_store(args.edges)
        edges = store.read(0, store.n_edges)
    else:
        edges, _ = planted_partition(
            n, args.communities, 0.15, 0.001, seed=42
        )
    print(f"graph: {n} nodes, {len(edges)} edges on {jax.default_backend()}")

    cfg = default_config(
        n, len(edges), mode_degree(np.asarray(edges), n),
        iterations=args.iterations, s_cap=min(n, 4096),
    )
    t0 = time.perf_counter()
    result = biggraphvis(edges, n, cfg)
    print(
        f"layout: {result.n_supernodes} supernodes, "
        f"{result.n_superedges} superedges, Q={result.modularity:.3f} "
        f"in {time.perf_counter() - t0:.1f}s"
    )

    pyramid = TilePyramid(
        result,
        TileConfig(tile_size=args.tile_size, depth=args.depth),
        source=edges,
        bgv_cfg=cfg,
    )
    engine = TileEngine(
        pyramid, cache_bytes=int(args.cache_mb * (1 << 20)), slots=args.slots
    )

    t0 = time.perf_counter()
    # Warm the full serving mix: every pyramid tile plus the drill pool the
    # trace samples from — after this, misses re-render on compiled code.
    drill_pool = pyramid.drillable_communities()[:8]
    warmed = engine.warmup(drills=drill_pool)
    n_tiles = sum(pyramid.n_tiles(z) ** 2 for z in range(args.depth))
    print(
        f"warmup: {warmed} tiles ({n_tiles} pyramid + {len(drill_pool)} "
        f"drill-downs) precomputed in {time.perf_counter() - t0:.1f}s "
        f"({engine.cache.bytes / (1 << 20):.1f} MiB cached)"
    )

    trace = synthetic_trace(
        pyramid, args.requests, zipf_a=args.zipf,
        drill_frac=args.drill_frac, seed=args.seed,
    )
    c0 = jit_compile_count()
    hits0 = engine.cache.hits
    miss_lat: list[float] = []
    t0 = time.perf_counter()
    for spec in trace:
        req = TileRequest(spec)
        engine.submit(req)
        while not req.done:
            engine.tick()
        if not req.hit:
            miss_lat.append(req.latency_s)
    dt = time.perf_counter() - t0

    served = len(trace)
    hits = engine.cache.hits - hits0
    drills = sum(1 for s in trace if isinstance(s, DrillSpec))
    print(
        f"served {served} requests ({drills} drill-downs) in {dt:.1f}s: "
        f"{served / dt:.1f} tiles/s, hit rate {hits / served:.1%}, "
        f"{len(miss_lat)} misses "
        f"(p50 {percentile(miss_lat, 50) * 1e3:.0f}ms, "
        f"p99 {percentile(miss_lat, 99) * 1e3:.0f}ms)"
    )
    print(
        f"steady-state recompiles: {jit_compile_count() - c0} "
        f"(fixed tile shapes), cache: {len(engine.cache)} tiles / "
        f"{engine.cache.bytes / (1 << 20):.1f} MiB, "
        f"{engine.cache.evictions} evictions, {engine.ticks} ticks"
    )


if __name__ == "__main__":
    main()
