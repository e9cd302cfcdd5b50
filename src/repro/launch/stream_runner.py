"""Launch driver for the streaming chunked-edge engine (core/stream.py).

Owns device placement for the chunk buffers: single-device by default, or
row-sharded across a mesh's devices (the per-chunk scatter updates then
merge through XLA's all-reduce — the same collective structure as the
``bgv_detect`` dry-run cells in launch/steps.py). Transfers are
``device_put_copied`` (kernels/compat.py), whose result stops reading the
engine's reusable staging buffers once it is ready, and the engine overlaps them
with compute via its double-buffered staging ring
(``EdgeChunkStream.device_chunks``).

    PYTHONPATH=src python -m repro.launch.stream_runner \
        --nodes 20000 --communities 200 --chunk 8192 --rounds 4

prints a one-shot vs streamed comparison: identical labels/supergraph,
pass count, chunk throughput, and peak device bytes. With ``--source
npy|bin|shards`` the streamed run is driven out-of-core from a converted
edge file (written to a temp dir via repro/data/edge_store.py), adding
host-residency and copy/compute-overlap numbers:

    PYTHONPATH=src python -m repro.launch.stream_runner \
        --nodes 20000 --source npy --chunk 8192
"""
from __future__ import annotations

import argparse
import tempfile
from dataclasses import dataclass, replace

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.pipeline import BGVConfig, BGVResult, biggraphvis
from repro.core.stream import StreamConfig, oneshot_device_bytes
from repro.data.edge_store import write_bin, write_npy, write_shards
from repro.kernels.compat import device_put_copied, enable_compile_cache
from repro.obs.cli import add_obs_args, obs_session
from repro.resilience.checkpoint import Preempted, StreamCheckpointer


@dataclass(frozen=True)
class StreamRunnerConfig:
    stream: StreamConfig = StreamConfig()
    shard_chunks: bool = False  # row-shard chunk buffers across the mesh


class StreamRunner:
    """Binds the engine to devices: placement/sharding of chunk buffers.

    ``put`` is handed to the engine as the host→device transfer; with a mesh
    it places each chunk row-sharded over every mesh axis, so each device
    streams its own slice of the chunk (edge shards, DESIGN.md §4). Either
    way it is ``device_put_copied``, whose result no longer reads the host
    buffer once ready, as the engine's staged disk path requires.

    A chunk whose row count doesn't divide by the mesh device count can't
    be row-sharded; ``put`` pads it to the next multiple with the engine's
    invalid-edge sentinel (the trash node, a no-op row for every chunk
    update — ``run`` records it). Standalone use before any ``run`` has no
    sentinel to pad with, so such chunks fall back to replication.

    Chunks-only sharding (``shard_chunks`` without ``shard_detect``) is a
    placement mode: the compiler auto-partitions the per-chunk updates
    around the sharded operand, which is a valid SCoDA run but may break
    scatter ties in a different order than one device. Bit-identical
    multi-device results are the ``StreamConfig.shard_detect`` /
    ``shard_layout`` contract (explicit shard_map collectives,
    core/stream.py), verified by the device-count CI matrix.

    When constructed with a mesh and a ``StreamConfig`` that requests
    sharding (``shard_detect``/``shard_layout``) without carrying a mesh of
    its own, the runner threads its mesh into the engine config.
    """

    def __init__(self, cfg: BGVConfig, runner_cfg: StreamRunnerConfig | None = None,
                 mesh: Mesh | None = None):
        self.cfg = cfg
        self.runner_cfg = runner_cfg or StreamRunnerConfig()
        self.mesh = mesh
        self._trash = None  # invalid-edge sentinel (n_nodes); set by run()
        stream = self.runner_cfg.stream
        if (mesh is not None and stream.mesh is None
                and (stream.shard_detect or stream.shard_layout)):
            self.runner_cfg = replace(
                self.runner_cfg, stream=replace(stream, mesh=mesh)
            )
        if mesh is not None and self.runner_cfg.shard_chunks:
            self._sharding = NamedSharding(mesh, P(tuple(mesh.axis_names), None))
        else:
            self._sharding = None

    def put(self, chunk_np: np.ndarray) -> jax.Array:
        if self._sharding is not None:
            rem = chunk_np.shape[0] % self.mesh.size
            if rem:
                if self._trash is None:
                    # No sentinel to pad with: replicate rather than shard.
                    return device_put_copied(chunk_np, None)
                pad = np.full(
                    (self.mesh.size - rem, 2), self._trash, chunk_np.dtype
                )
                chunk_np = np.concatenate([chunk_np, pad])
        return device_put_copied(chunk_np, self._sharding)

    def run(self, source, n_nodes: int, checkpoint=None,
            resume: bool | str = False) -> BGVResult:
        """``source``: host edge array, EdgeStore, or edge-file path.
        ``checkpoint``/``resume`` pass through to the streaming pipeline
        (repro/resilience/checkpoint.py ``StreamCheckpointer``)."""
        self._trash = n_nodes
        return biggraphvis(
            source, n_nodes, self.cfg,
            stream=self.runner_cfg.stream, put=self.put,
            checkpoint=checkpoint, resume=resume,
        )


def _materialize(edges: np.ndarray, source: str, directory: str):
    """Write the edge list to the requested on-disk form; returns a path."""
    if source == "npy":
        return write_npy(f"{directory}/edges.npy", edges)
    if source == "bin":
        return write_bin(f"{directory}/edges.bin", edges)
    write_shards(f"{directory}/shards", edges, shard_edges=max(1, len(edges) // 5))
    return f"{directory}/shards"


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--nodes", type=int, default=20000)
    ap.add_argument("--communities", type=int, default=200,
                    help="number of planted communities")
    ap.add_argument("--chunk", type=int, default=8192)
    ap.add_argument("--prefetch", type=int, default=1)
    ap.add_argument("--rounds", type=int, default=4)
    ap.add_argument("--block-size", type=int, default=4096)
    ap.add_argument("--agg-backend", choices=("merge", "lexsort"),
                    default="merge",
                    help="superedge aggregation: two-level sorted-merge "
                         "(kernels/merge) or the lexsort re-sort baseline")
    ap.add_argument("--iterations", type=int, default=30)
    ap.add_argument("--repulsion", default="exact",
                    choices=("exact", "grid", "grid_pallas", "grid_dense"),
                    help="FA2 repulsion backend for the supergraph layout "
                         "(core/forceatlas2.py backend matrix)")
    ap.add_argument("--grid-rebuild", type=int, default=1,
                    help="re-bin/re-sort grid cells every k layout iterations")
    ap.add_argument("--stop-tolerance", type=float, default=0.0,
                    help="FA2 adaptive stop: freeze the layout scan once "
                         "global swing <= tol * traction (0 = fixed count)")
    ap.add_argument("--min-iterations", type=int, default=0,
                    help="never stop the layout before this many iterations")
    ap.add_argument("--init", default="random",
                    choices=("random", "degree", "bfs"),
                    help="FA2 initial positions (core/forceatlas2.py)")
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--source", choices=("memory", "npy", "bin", "shards"),
                    default="memory",
                    help="edge source for the streamed run (non-memory "
                         "forms are written to a temp dir first)")
    ap.add_argument("--checkpoint-dir", default=None,
                    help="directory for streaming detect/supergraph "
                         "checkpoints (atomic .npz + meta.json, "
                         "repro/resilience/checkpoint.py); also installs "
                         "a SIGTERM handler that checkpoints at the next "
                         "chunk boundary and exits cleanly")
    ap.add_argument("--checkpoint-every", type=int, default=0,
                    help="checkpoint every N chunk boundaries "
                         "(0 = round boundaries only)")
    ap.add_argument("--checkpoint-keep", type=int, default=3,
                    help="keep the newest K checkpoints")
    ap.add_argument("--resume", action="store_true",
                    help="resume the streamed run from the latest valid "
                         "checkpoint in --checkpoint-dir")
    ap.add_argument("--nan-guard", action="store_true",
                    help="FA2 divergence sentinel: roll back and damp "
                         "speed on non-finite forces instead of "
                         "propagating NaNs into the layout")
    ap.add_argument("--shard", choices=("none", "chunks", "detect", "layout", "all"),
                    default="none",
                    help="multi-device mode over a 1-D mesh of all local "
                         "devices: 'chunks' row-shards chunk buffers only "
                         "(placement; scatter ties may break differently "
                         "than one device), 'detect' shards the per-chunk "
                         "edge passes and 'layout' node-partitions FA2 "
                         "(both bit-identical), 'all' does everything "
                         "(on CPU set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N)")
    add_obs_args(ap)
    args = ap.parse_args()
    enable_compile_cache()

    with obs_session(args):
        _run(args)


def _run(args) -> None:
    from repro.core.pipeline import default_config
    from repro.graph import mode_degree, planted_partition

    n = args.nodes
    edges, _ = planted_partition(n, args.communities, 0.12, 2e-4, seed=args.seed)
    delta = mode_degree(edges, n)
    print(f"graph: {n} nodes, {len(edges)} edges, mode degree δ={delta}")

    cfg = default_config(n, len(edges), delta, rounds=args.rounds,
                         iterations=args.iterations,
                         repulsion=args.repulsion,
                         grid_rebuild=args.grid_rebuild,
                         stop_tolerance=args.stop_tolerance,
                         min_iterations=args.min_iterations,
                         init=args.init,
                         nan_guard=args.nan_guard)
    cfg = replace(cfg, scoda=replace(cfg.scoda, block_size=args.block_size))

    ckpt = None
    if args.checkpoint_dir:
        ckpt = StreamCheckpointer(
            args.checkpoint_dir, every_chunks=args.checkpoint_every,
            keep=args.checkpoint_keep, exit_on_preempt=True,
        )
        ckpt.install_preemption_handler()
        print(f"checkpointing to {args.checkpoint_dir} "
              f"(every={args.checkpoint_every or 'round boundaries'}, "
              f"keep={args.checkpoint_keep}; SIGTERM checkpoints and exits)")
    elif args.resume:
        raise SystemExit("--resume requires --checkpoint-dir")

    res_one = biggraphvis(edges, n, cfg)
    mesh = None
    if args.shard != "none":
        from repro.launch.mesh import make_stream_mesh

        mesh = make_stream_mesh()
        print(f"mesh: {mesh.size} devices ({jax.default_backend()})")
    runner = StreamRunner(cfg, StreamRunnerConfig(
        stream=StreamConfig(
            chunk_size=args.chunk, prefetch=args.prefetch,
            agg_backend=args.agg_backend,
            shard_detect=args.shard in ("detect", "all"),
            shard_layout=args.shard in ("layout", "all"),
        ),
        shard_chunks=args.shard in ("chunks", "all"),
    ), mesh=mesh)
    try:
        with tempfile.TemporaryDirectory() as tmp:
            if args.source == "memory":
                src = edges
            else:
                src = _materialize(edges, args.source, tmp)
                print(f"streaming from {args.source} store: {src}")
            res_str = runner.run(src, n, checkpoint=ckpt, resume=args.resume)
    except Preempted as e:
        print(f"preempted: {e} — checkpoint saved, exiting cleanly "
              f"(restart with --resume)")
        raise SystemExit(0)
    if res_str.stream.resumed_at:
        print(f"resumed from checkpoint at {res_str.stream.resumed_at}")

    match = (
        np.array_equal(res_one.labels, res_str.labels)
        and np.array_equal(np.asarray(res_one.supergraph.edges),
                           np.asarray(res_str.supergraph.edges))
        and np.array_equal(res_one.sizes, res_str.sizes)
    )
    s = res_str.stream
    print(f"streamed == one-shot: {match}")
    print(f"supernodes={res_str.n_supernodes} superedges={res_str.n_superedges} "
          f"Q={res_str.modularity:.3f}")
    print(f"passes={s.passes} chunks={s.chunks} chunk_size={s.chunk_size} "
          f"throughput={s.edges_per_s / 1e6:.2f}M edges/s")
    print(f"overlap: host_fill={s.host_fill_s * 1e3:.1f}ms "
          f"copy_stall={s.copy_stall_s * 1e3:.1f}ms of {s.seconds * 1e3:.1f}ms")
    print(f"peak host bytes: streamed={s.peak_host_bytes:,} "
          f"(in-memory edge list={edges.nbytes:,})")
    print(f"peak device bytes: streamed={s.peak_device_bytes:,} "
          f"one-shot={res_one.stream.peak_device_bytes:,} "
          f"(one-shot input residency={oneshot_device_bytes(len(edges), n):,})")


if __name__ == "__main__":
    main()
