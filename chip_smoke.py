#!/usr/bin/env python3
"""Chip smoke run: the BigGraphVis main path once, on a TPU, at full width.

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # the sharded pipeline on four chips

One chip. A planted-partition graph with the BerkStan shape of
``configs/biggraphvis.py`` (685,230 nodes, ~6.7M edges) is written to
``.npy``, streamed from disk through ``biggraphvis`` (SCoDA's rounds, CMS
community sizing, merge superedge aggregation, exact FA2 on the
supergraph), rendered to PNG, and served as pan/zoom/drill tiles by a
``TileEngine``. Every check below fails the run:

* the disk-streamed result equals the in-memory one-shot run
  (``stream=None``) of the same graph: labels, supergraph, sizes and
  modularity exactly, positions bit for bit;
* each Pallas kernel family matches its XLA reference at a main-path
  shape (exactly for the integer kernels, within the kernel tests'
  tolerance for the float ones);
* the programs each phase compiled hold the Pallas kernels
  (``tpu_custom_call``) that phase uses;
* no served tile failed, each served tile equals the warm-up render of
  the same address, and nothing compiled while serving.

Four chips (``--chips 4``): the same graph through ``StreamRunner`` over a
4-device mesh with ``shard_detect``/``shard_layout``/``shard_chunks``,
against the one-device run of the same graph in the same process; labels,
supergraph, sizes and modularity must match exactly, positions bit for
bit. Nothing else runs.

Earlier lines print what is worth reading: the device, the graph, per-phase
and compile seconds, supernodes/superedges/Q and peak device bytes. These
are a smoke run's numbers, not a benchmark's. The last line is one JSON
object, ``{"ok": true, "device": {...}}``. Where JAX finds no TPU the run
exits non-zero and prints no such line.
"""
from __future__ import annotations

import argparse
import json
import re
import sys
import tempfile
import time
import traceback
from contextlib import contextmanager
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.kernels.compat import enable_compile_cache  # noqa: E402

# BerkStan shape (configs/biggraphvis.py, paper Table 1): 685,230 nodes.
# planted_partition(685_230, 320, 0.0088, 1e-6) gives ~6.69M edges (mode
# degree 19); 320 planted blocks keep the generator's O(blocks²)
# inter-block loop short.
NODES = 685_230
COMMUNITIES = 320
P_IN, P_OUT = 0.0088, 1e-6
CHUNK = 1 << 20  # edges per streamed chunk
REQUESTS = 48  # served tile requests
# SCoDA finds ~12.7k communities in this graph, joined by ~960k distinct
# superedges: more than default_config's 262,144-superedge cap, which
# would keep only the smallest pairs. The run sizes the capacity to hold
# them; check "supergraph.capacity" fails if it ever does not.
SUPER_EDGES = 1 << 20


class Smoke:
    """Collects failures; a phase that raises is recorded, not fatal, so
    one run reports every fault it can reach."""

    def __init__(self, directory: str):
        self.failures: list[str] = []
        self.compile_dir = Path(directory) / "ir"
        self._phase_dirs: dict[str, Path] = {}

    def check(self, name: str, ok: bool, detail: str = "") -> bool:
        print(f"check {name}: {'ok' if ok else 'FAILED'}"
              + (f" ({detail})" if detail else ""), flush=True)
        if not ok:
            self.failures.append(f"{name}: {detail}")
        return ok

    @contextmanager
    def phase(self, name: str, timings: dict):
        """Time a phase, count its compiles and dump the programs it hands
        to the compiler (or loads from the cache) for the kernel check."""
        from repro.obs.meters import jit_compile_count
        from repro.obs.metrics import REGISTRY

        dump = self.compile_dir / name
        dump.mkdir(parents=True)
        self._phase_dirs[name] = dump
        jax.config.update("jax_dump_ir_to", str(dump))
        c0 = jit_compile_count()
        s0 = REGISTRY.histogram("jax.compile_seconds").total
        t0 = time.perf_counter()
        try:
            yield
        except Exception as e:  # recorded; later phases still run
            traceback.print_exc()
            self.failures.append(f"{name}: {type(e).__name__}: {e}")
        finally:
            timings[name] = {
                "seconds": time.perf_counter() - t0,
                "compiles": jit_compile_count() - c0,
                "compile_seconds":
                    REGISTRY.histogram("jax.compile_seconds").total - s0,
            }
            jax.config.update("jax_dump_ir_to", "")
            t = timings[name]
            print(f"phase {name}: {t['seconds']:.3f} s "
                  f"({t['compiles']} programs compiled or loaded from the "
                  f"cache, {t['compile_seconds']:.3f} s in it)", flush=True)

    def kernels_in(self, name: str) -> set[str]:
        """Pallas kernel names in the programs the phase compiled."""
        found = set()
        for f in self._phase_dirs[name].glob("*.mlir"):
            text = f.read_text()
            if "tpu_custom_call" in text:
                found.update(re.findall(r'kernel_name = "([^"]+)"', text))
        return found

    def require_kernels(self, name: str, required: set[str]) -> None:
        found = self.kernels_in(name)
        self.check(f"kernels[{name}]", required <= found,
                   f"found {sorted(found)}, need {sorted(required)}")


def device_line() -> dict:
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def peak_device_bytes() -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def make_config(nodes: int, n_edges: int, delta: int, super_edges: int):
    from dataclasses import replace

    from repro import default_config

    return replace(default_config(nodes, n_edges, delta),
                   max_super_edges=super_edges)


def check_capacity(smoke: Smoke, res, cfg) -> None:
    smoke.check("supergraph.capacity",
                res.n_supernodes <= cfg.s_cap
                and res.n_superedges <= cfg.max_super_edges,
                f"{res.n_supernodes} of {cfg.s_cap} supernodes, "
                f"{res.n_superedges} of {cfg.max_super_edges} superedges")


def make_graph(seed: int, nodes: int, communities: int, p_in: float,
               p_out: float, directory: str):
    from repro.data.edge_store import write_npy
    from repro.graph import mode_degree, planted_partition

    t0 = time.perf_counter()
    edges, _ = planted_partition(nodes, communities, p_in, p_out, seed=seed)
    delta = mode_degree(edges, nodes)
    path = write_npy(f"{directory}/edges.npy", edges)
    print(f"graph: {nodes} nodes, {len(edges)} edges, mode degree {delta}, "
          f"generated and written in {time.perf_counter() - t0:.1f} s "
          f"({path})", flush=True)
    return edges, delta, path


def compare_results(smoke: Smoke, name: str, a, b) -> None:
    """Exact equality of two BGVResults (positions bit for bit)."""
    pairs = {
        "labels": (a.labels, b.labels),
        "sg_edges": (a.supergraph.edges, b.supergraph.edges),
        "sg_weights": (a.supergraph.weights, b.supergraph.weights),
        "sizes": (a.sizes, b.sizes),
    }
    for key, (x, y) in pairs.items():
        x, y = np.asarray(x), np.asarray(y)
        smoke.check(f"{name}.{key}", x.shape == y.shape and
                    np.array_equal(x, y),
                    f"{int((x != y).sum()) if x.shape == y.shape else 'shape'}"
                    " elements differ")
    smoke.check(f"{name}.counts",
                (a.n_supernodes, a.n_superedges) ==
                (b.n_supernodes, b.n_superedges),
                f"{a.n_supernodes}/{a.n_superedges} vs "
                f"{b.n_supernodes}/{b.n_superedges}")
    smoke.check(f"{name}.modularity", a.modularity == b.modularity,
                f"{a.modularity!r} vs {b.modularity!r}")
    pa = np.asarray(a.positions)
    pb = np.asarray(b.positions)
    diff = float(np.max(np.abs(pa - pb))) if pa.shape == pb.shape else -1.0
    smoke.check(f"{name}.positions_bitwise",
                pa.tobytes() == pb.tobytes(), f"max |diff| {diff!r}")
    smoke.check(f"{name}.positions_finite", bool(np.isfinite(pa).all()))


# --------------------------------------------------------------- kernels

def _allclose(want, got, rtol, atol) -> tuple[bool, str]:
    want, got = np.asarray(want), np.asarray(got)
    err = np.abs(got - want)
    ok = bool(np.all(err <= atol + rtol * np.abs(want)))
    return ok, f"max |err| {float(err.max())!r}, max |ref| " \
               f"{float(np.abs(want).max())!r}"


def _equal(want, got) -> tuple[bool, str]:
    want, got = np.asarray(want), np.asarray(got)
    return bool(np.array_equal(want, got)), \
        f"{int((want != got).sum())} of {want.size} differ"


def kernel_checks(smoke: Smoke, rng, *, nodes: int, cms_cols: int,
                  seg_edges: int, rep_n: int, merge_cap: int, merge_c: int,
                  raster_side: int, splat_n: int, interpret: bool = False):
    """Each Pallas family against its reference at one main-path shape.
    Tolerances are tests/test_kernels.py's for the float kernels."""
    from repro.kernels.cms.cms_update import cms_update_pallas
    from repro.kernels.cms.ref import cms_update_ref
    from repro.kernels.grid.ops import cell_stats
    from repro.kernels.grid.ref import bin_and_sort, far_field_ref, near_field_ref
    from repro.kernels.grid.tiled import far_field_pallas, near_field_pallas
    from repro.kernels.merge.ref import merge_combine_ref
    from repro.kernels.merge.sorted_merge import merge_combine_pallas
    from repro.kernels.raster.ref import count_scatter_into_ref, disk_accum_ref
    from repro.kernels.raster.splat import count_scatter_pallas, disk_accum_pallas
    from repro.kernels.repulsion.nbody import repulsion_pallas
    from repro.kernels.repulsion.ref import repulsion_ref
    from repro.kernels.segment.ref import segment_sum_ref
    from repro.kernels.segment.seg_matmul import segment_sum_pallas

    ip = {"interpret": interpret}

    # CMS: node-keyed sizing, integer-valued degree weights.
    h = rng.integers(0, cms_cols, (4, nodes)).astype(np.int32)
    h[:, ::97] = -1
    w = rng.integers(1, 60, nodes).astype(np.float32)
    w[::1013] = 5000.0  # hub degrees beyond bf16's exact integers
    sk = jnp.zeros((4, cms_cols), jnp.float32)
    got = cms_update_pallas(sk, jnp.asarray(h), jnp.asarray(w), cms_cols, **ip)
    want = cms_update_ref(sk, jnp.asarray(h), jnp.asarray(w))
    smoke.check("kernel.cms", *_allclose(want, got, 1e-5, 1e-4))

    # Segment sum: grid cell stats [Σm·x, Σm·y, Σm] into 64² cells.
    data = rng.normal(size=(seg_edges, 3)).astype(np.float32)
    seg = rng.integers(0, 4096, seg_edges).astype(np.int32)
    got = segment_sum_pallas(jnp.asarray(data), jnp.asarray(seg), 4096, **ip)
    want = segment_sum_ref(jnp.asarray(data), jnp.asarray(seg), 4096)
    smoke.check("kernel.segment", *_allclose(want, got, 1e-5, 1e-2))

    # Exact repulsion on a supergraph-sized node set.
    pos = jnp.asarray(rng.uniform(-1000, 1000, (rep_n, 2)).astype(np.float32))
    mass = jnp.asarray(rng.integers(1, 50, rep_n).astype(np.float32))
    radii = jnp.sqrt(mass)
    got = repulsion_pallas(pos, mass, radii, 80.0, **ip)
    want = repulsion_ref(pos, mass, 80.0, radii=radii)
    scale = float(jnp.max(jnp.abs(want)))
    smoke.check("kernel.repulsion",
                *_allclose(want, got, 2e-4, 2e-4 * max(scale, 1.0)))

    # Grid far + near field over the full node set.
    gpos = jnp.asarray(rng.uniform(-1000, 1000, (nodes, 2)).astype(np.float32))
    gmass = jnp.asarray(rng.integers(1, 20, nodes).astype(np.float32))
    cell, order = bin_and_sort(gpos, 64)
    pos_s, mass_s, cell_s = gpos[order], gmass[order], cell[order]
    ccent, cmass = cell_stats(pos_s, mass_s, cell_s, 64 * 64, backend="ref")
    got = far_field_pallas(pos_s, mass_s, cell_s, ccent, cmass, 80.0, **ip)
    want = far_field_ref(pos_s, mass_s, cell_s, ccent, cmass, 80.0)
    scale = float(jnp.max(jnp.abs(want)))
    smoke.check("kernel.grid_far",
                *_allclose(want, got, 2e-4, 2e-4 * max(scale, 1.0)))
    got = near_field_pallas(pos_s, mass_s, cell_s, 80.0, 32, **ip)
    want = near_field_ref(pos_s, mass_s, cell_s, 80.0, 32)
    scale = float(jnp.max(jnp.abs(want)))
    smoke.check("kernel.grid_near",
                *_allclose(want, got, 2e-4, 2e-4 * max(scale, 1.0)))

    # Merge: superedge state at capacity-scale + one deduped chunk run.
    s_cap = 1 << 16

    def sorted_run(n_live, cap):
        keys = np.unique(rng.integers(0, s_cap * s_cap, 2 * n_live))
        a, b = keys // s_cap, keys % s_cap
        keep = a < b
        a, b = a[keep][:n_live], b[keep][:n_live]
        pad = cap - len(a)
        ra = np.concatenate([a, np.full(pad, s_cap)]).astype(np.int32)
        rb = np.concatenate([b, np.full(pad, s_cap)]).astype(np.int32)
        rw = np.concatenate([rng.integers(1, 9, len(a)),
                             np.zeros(pad)]).astype(np.float32)
        return jnp.asarray(ra), jnp.asarray(rb), jnp.asarray(rw)

    state = sorted_run(merge_cap * 3 // 4, merge_cap)
    chunk = sorted_run(merge_c // 2, merge_c)
    # Duplicates: a slice of the state's pairs reappears in the chunk.
    dup = min(merge_c // 8, merge_cap // 8)
    ca = np.concatenate([np.asarray(chunk[0])[: merge_c // 2 - dup],
                         np.asarray(state[0])[:dup]])
    cb = np.concatenate([np.asarray(chunk[1])[: merge_c // 2 - dup],
                         np.asarray(state[1])[:dup]])
    order = np.lexsort((cb, ca))
    ca, cb = ca[order], cb[order]
    ca_u, cb_u = np.unique(np.stack([ca, cb], 1), axis=0).T
    pad = merge_c - len(ca_u)
    chunk = (
        jnp.asarray(np.concatenate([ca_u, np.full(pad, s_cap)]).astype(np.int32)),
        jnp.asarray(np.concatenate([cb_u, np.full(pad, s_cap)]).astype(np.int32)),
        jnp.asarray(np.concatenate([rng.integers(1, 9, len(ca_u)),
                                    np.zeros(pad)]).astype(np.float32)),
    )
    got = merge_combine_pallas(*state, *chunk, s_cap, **ip)
    want = merge_combine_ref(*state, *chunk, s_cap)
    ok = all(_equal(x, y)[0] for x, y in zip(want, got))
    smoke.check("kernel.merge", ok,
                "; ".join(_equal(x, y)[1] for x, y in zip(want, got)))

    # Raster: edge splats into the [12, side, side] accumulator, in place,
    # and dense node disks.
    size = 12 * raster_side * raster_side
    spos = rng.integers(0, size, splat_n).astype(np.int32)
    spos[::7] = np.iinfo(np.int32).max  # dropped samples
    inc = rng.integers(1, 6, splat_n).astype(np.int32)
    acc = rng.integers(0, 3, size).astype(np.int32)
    want = count_scatter_into_ref(jnp.asarray(acc), jnp.asarray(spos),
                                  jnp.asarray(inc))
    got = count_scatter_pallas(jnp.asarray(spos), jnp.asarray(inc), size,
                               acc=jnp.asarray(acc), **ip)
    smoke.check("kernel.count_scatter", *_equal(want, got))
    nd = 1024
    cx = jnp.asarray(rng.uniform(0, raster_side, nd).astype(np.float32))
    cy = jnp.asarray(rng.uniform(0, raster_side, nd).astype(np.float32))
    r = jnp.asarray(rng.uniform(8, 64, nd).astype(np.float32))
    g = jnp.asarray(rng.integers(0, 12, nd).astype(np.int32))
    want = disk_accum_ref(cx, cy, r, g, 12, raster_side, raster_side)
    got = disk_accum_pallas(cx, cy, r, g, 12, raster_side, raster_side, **ip)
    smoke.check("kernel.disk_accum", *_equal(want, got))


# ------------------------------------------------------------ main path

def one_chip(smoke: Smoke, args, timings: dict, tmp: str, *,
             nodes=NODES, communities=COMMUNITIES, p_in=P_IN, p_out=P_OUT,
             chunk=CHUNK, requests=REQUESTS, super_edges=SUPER_EDGES,
             on_chip=True) -> None:
    from repro import (
        StreamConfig, TileConfig, TileEngine, TilePyramid, biggraphvis,
    )
    from repro.obs.meters import jit_compile_count
    from repro.obs.metrics import REGISTRY
    from repro.serve.tiles import DrillSpec, TileRequest, synthetic_trace

    edges, delta, path = make_graph(args.seed, nodes, communities, p_in,
                                    p_out, tmp)
    cfg = make_config(nodes, len(edges), delta, super_edges)
    print(f"config: CMS {cfg.cms.rows}x{cfg.cms.cols}, s_cap {cfg.s_cap}, "
          f"max_super_edges {cfg.max_super_edges}, "
          f"{cfg.layout.iterations} FA2 iterations ({cfg.layout.repulsion})",
          flush=True)

    res = res_one = warm = None
    with smoke.phase("streamed", timings):
        res = biggraphvis(path, nodes, cfg,
                          stream=StreamConfig(chunk_size=chunk))
    if res is not None:
        t = res.timings
        print(f"streamed run: detect {t['scoda_s']:.3f} s, supergraph "
              f"{t['supergraph_s']:.3f} s, layout {t['layout_s']:.3f} s; "
              f"{res.stream.passes} passes, {res.stream.chunks} chunks, "
              f"host fill {res.stream.host_fill_s:.3f} s, copy stall "
              f"{res.stream.copy_stall_s:.3f} s", flush=True)
        print(f"supergraph: {res.n_supernodes} supernodes, "
              f"{res.n_superedges} superedges, Q={res.modularity!r}",
              flush=True)
        check_capacity(smoke, res, cfg)
        if on_chip:
            smoke.require_kernels("streamed", {
                "cms_update", "merge_scatter_combine", "nbody_repulsion"})
    with smoke.phase("oneshot", timings):
        res_one = biggraphvis(edges, nodes, cfg)
    if res is not None and res_one is not None:
        compare_results(smoke, "streamed_vs_oneshot", res, res_one)
    if res is None:
        return

    with smoke.phase("render", timings):
        img, rstats = res.render(f"{tmp}/supergraph.png")
        smoke.check("render.image", img.shape == (1024, 1024, 3)
                    and img.dtype == np.uint8 and int(img.min()) < 255,
                    f"shape {img.shape}, {rstats.nodes_drawn} nodes drawn, "
                    f"{rstats.edges_streamed} edges splatted")
    if on_chip:
        smoke.require_kernels("render", {"raster_count_scatter"})

    with smoke.phase("serve_warmup", timings):
        pyramid = TilePyramid(res, TileConfig(depth=3), source=path,
                              bgv_cfg=cfg)
        trace = synthetic_trace(pyramid, requests, drill_frac=0.08,
                                drill_pool=2, seed=args.seed)
        drills = sorted({s.community for s in trace
                         if isinstance(s, DrillSpec)})
        warm = TileEngine(pyramid)
        n_warm = warm.warmup(drills=drills)
        print(f"serve warm-up: {n_warm} tiles ({len(drills)} drills: "
              f"{drills})", flush=True)
    if on_chip:
        smoke.require_kernels("serve_warmup", {"raster_count_scatter"})
    if warm is not None:
        failed0 = int(REGISTRY.counter("errors.failed_tiles").value)
        c0 = jit_compile_count()
        with smoke.phase("serve", timings):
            engine = TileEngine(pyramid)  # cold cache: misses re-render
            reqs = []
            for i, spec in enumerate(trace):
                req = TileRequest(spec)
                engine.submit(req)
                reqs.append(req)
                if i % engine.slots == engine.slots - 1:
                    engine.tick()
            while engine.n_pending:
                engine.tick()
        lat = np.array([r.latency_s for r in reqs])
        print(f"served {len(reqs)} requests: {engine.rendered} renders, "
              f"hit rate {engine.cache.hit_rate:.3f}, latency p50 "
              f"{np.percentile(lat, 50):.4f} s p99 "
              f"{np.percentile(lat, 99):.4f} s", flush=True)
        smoke.check("serve.failed_tiles",
                    engine.failed == 0 and int(REGISTRY.counter(
                        "errors.failed_tiles").value) == failed0,
                    f"{engine.failed} failed: {engine.last_error!r}")
        smoke.check("serve.recompiles", jit_compile_count() == c0,
                    f"{jit_compile_count() - c0} programs compiled or "
                    "loaded while serving")
        same = all(r.done and np.array_equal(r.tile, warm.cache.get(r.spec))
                   for r in reqs)
        smoke.check("serve.tiles_equal_warmup", same)


def four_chips(smoke: Smoke, args, timings: dict, tmp: str, *,
               nodes=NODES, communities=COMMUNITIES, p_in=P_IN, p_out=P_OUT,
               chunk=CHUNK, super_edges=SUPER_EDGES) -> None:
    from repro import StreamConfig, biggraphvis
    from repro.launch.mesh import make_stream_mesh
    from repro.launch.stream_runner import StreamRunner, StreamRunnerConfig

    edges, delta, path = make_graph(args.seed, nodes, communities, p_in,
                                    p_out, tmp)
    cfg = make_config(nodes, len(edges), delta, super_edges)
    res4 = res1 = None
    with smoke.phase("sharded", timings):
        mesh = make_stream_mesh(args.chips)
        runner = StreamRunner(cfg, StreamRunnerConfig(
            stream=StreamConfig(chunk_size=chunk, shard_detect=True,
                                shard_layout=True),
            shard_chunks=True,
        ), mesh=mesh)
        res4 = runner.run(path, nodes)
    with smoke.phase("one_device", timings):
        res1 = biggraphvis(path, nodes, cfg,
                           stream=StreamConfig(chunk_size=chunk))
    if res4 is not None:
        t = res4.timings
        print(f"sharded run on {res4.stream.devices} devices: detect "
              f"{t['scoda_s']:.3f} s, supergraph {t['supergraph_s']:.3f} s, "
              f"layout {t['layout_s']:.3f} s; {res4.n_supernodes} "
              f"supernodes, {res4.n_superedges} superedges, "
              f"Q={res4.modularity!r}", flush=True)
        smoke.check("sharded.engaged", res4.stream.devices == args.chips,
                    f"{res4.stream.devices} devices")
        check_capacity(smoke, res4, cfg)
    if res4 is not None and res1 is not None:
        compare_results(smoke, "sharded_vs_one_device", res4, res1)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the main path on one chip; 4: only the "
                         "sharded pipeline against one device")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the graph, kernel inputs and traffic")
    args = ap.parse_args(argv)

    if jax.default_backend() != "tpu":
        print(f"chip_smoke: JAX found no TPU (backend "
              f"{jax.default_backend()!r}); nothing was run", file=sys.stderr)
        return 2
    if len(jax.devices()) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX found {len(jax.devices())}", file=sys.stderr)
        return 2
    cache = enable_compile_cache()
    from repro.obs.meters import register_compile_listener

    register_compile_listener()
    dev = device_line()
    print(f"device: {dev}; compile cache {cache}", flush=True)

    timings: dict = {}
    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory(prefix="bgv-smoke-") as tmp:
        smoke = Smoke(tmp)
        if args.chips == 4:
            four_chips(smoke, args, timings, tmp)
        else:
            with smoke.phase("kernels", timings):
                kernel_checks(
                    smoke, np.random.default_rng(args.seed), nodes=NODES,
                    cms_cols=6_649, seg_edges=1 << 20, rep_n=8192,
                    merge_cap=SUPER_EDGES, merge_c=CHUNK, raster_side=1024,
                    splat_n=(1 << 16) * 8,
                )
            one_chip(smoke, args, timings, tmp)
    total = time.perf_counter() - t0
    compile_s = sum(t["compile_seconds"] for t in timings.values())
    print(f"total {total:.3f} s, of which compiling or loading programs "
          f"{compile_s:.3f} s ({sum(t['compiles'] for t in timings.values())}"
          f" programs); peak device bytes {peak_device_bytes()}", flush=True)
    print("timings " + json.dumps(timings), flush=True)
    if smoke.failures:
        print(f"chip_smoke: {len(smoke.failures)} check(s) failed:",
              file=sys.stderr)
        for f in smoke.failures:
            print(f"  {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
