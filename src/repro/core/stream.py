"""Streaming chunked-edge execution engine (paper §3: community information
extracted "in a few passes on the edge list").

The one-shot pipeline materializes the whole padded edge list on device
before any stage runs, capping the reproduction at device-memory scale.
This engine instead keeps the edge list out of device memory and drives
every edge-consuming stage over fixed-size chunks:

    EdgeStore (host array · mmap .npy/.bin · sharded files)
        ──► EdgeChunkStream (padded chunk buffers)
        ──► double-buffered host staging + device_put_copied
        ──► per-chunk jitted update steps, state donated
            (SCoDA labels+degrees · graph degrees · superedge aggregation
             — two-level sorted-merge by default, ``StreamConfig.
             agg_backend`` — · modularity accumulators · CMS sketch)
        ──► finalize: Supergraph + labels, device-resident node-sized state

Device residency is O(n_nodes + chunk_size + max_super_edges + sketch) —
independent of |E| — so edge lists larger than device memory process in
``rounds + 1`` passes. With a disk-backed ``EdgeStore`` (repro/data/
edge_store.py) *host* residency is also |E|-independent: the only host
buffers are the staging pair, filled from the store and overwritten in
place once the device copy of their previous contents is ready
(``EdgeChunkStream.device_chunks``). The transfer is
``kernels/compat.device_put_copied``: a ``device_put`` followed by a
device-side copy, and the copy is the array compute reads — so no array
compute reads still refers to a staged buffer once it is refilled.

Bit-exactness: every stage's one-shot function is a thin wrapper over the
same chunk-update body (single chunk = whole list), and the SCoDA block
partition is preserved because chunk sizes are rounded up to a multiple of
``ScodaConfig.block_size`` — so chunked and one-shot runs produce identical
labels, supergraphs, and modularity whatever the source (see
tests/test_stream.py and tests/test_edge_store.py).

This is the single-device engine; ``launch/stream_runner.py`` adds device
placement/sharding, and is the substrate for the multi-device edge-sharded
form promised in core/pipeline.py's docstring.
"""
from __future__ import annotations

import functools
import time
from collections import deque
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import cms as cms_lib
from repro.core.modularity import (
    modularity_finalize,
    modularity_init,
    modularity_update,
    sharded_modularity_update,
)
from repro.core.scoda import (
    ScodaConfig,
    dense_labels,
    round_threshold,
    scoda_finalize,
    scoda_init,
    scoda_update,
    sharded_scoda_update,
    sharded_update_collective_bytes,
)
from repro.core.supergraph import (
    Supergraph,
    agg_finalize,
    agg_init,
    agg_update,
    community_sizes,
    sharded_agg_collective_bytes,
    sharded_agg_update,
)
from repro.data.edge_store import EDGE_DTYPE, InMemoryEdgeStore, as_edge_store
from repro.kernels.compat import device_put_copied, shard_map_compat
from repro.obs.metrics import REGISTRY, ensure_error_counters
from repro.obs.trace import get_tracer
from repro.resilience.checkpoint import (
    CheckpointMismatchError,
    config_fingerprint,
    restore_latest_valid,
)
from repro.resilience.validate import ValidationAccounting, validated_read


@dataclass(frozen=True)
class StreamConfig:
    """Engine knobs. ``chunk_size`` is rounded up to a multiple of the SCoDA
    block size so the chunked block partition matches the one-shot one.
    ``agg_backend`` selects the superedge-aggregation algorithm ("merge" =
    two-level sorted-merge via kernels/merge, "lexsort" = full re-sort
    baseline; bit-identical below capacity — core/supergraph.py).

    Multi-device (DESIGN.md §2, ROADMAP item 1): ``mesh`` + ``shard_detect``
    lower every per-chunk edge pass (SCoDA labels, degrees, superedge
    aggregation, modularity, CMS sizing) onto the mesh via ``shard_map`` —
    chunk buffers are device-sharded, node/sketch/agg state replicated,
    results bit-identical to single-device. ``shard_layout`` asks the
    downstream FA2 layout (core/pipeline.py) to node-partition its force
    pass on the same mesh. Both degrade to the unsharded path when a shape
    doesn't divide by the device count (see ``stream_detect`` /
    ``stream_supergraph`` gates).

    ``obs`` threads a ``repro.obs.Tracer`` through every engine stage
    (per-pass/per-chunk spans); None falls back to the process-global
    tracer, a no-op until ``repro.obs.enable_tracing()``.

    ``validation`` (a ``repro.resilience.ValidationPolicy``) makes every
    chunk read defensive: transient I/O errors retry with backoff, chunks
    that stay unreadable are quarantined (trash-filled, counted in
    ``StreamStats``/``errors.*``), and out-of-range node ids drop to the
    trash node — instead of any of those crashing a multi-pass run."""

    chunk_size: int = 1 << 16  # edges resident on device per chunk
    prefetch: int = 1  # host→device copies dispatched ahead of compute
    agg_backend: str = "merge"  # superedge aggregation: "merge" | "lexsort"
    mesh: object = None  # jax.sharding.Mesh for the sharded paths (or None)
    shard_detect: bool = False  # shard the per-chunk edge passes over mesh
    shard_layout: bool = False  # node-partition the FA2 layout over mesh
    obs: object = None  # repro.obs.Tracer (None = process-global tracer)
    validation: object = None  # resilience.ValidationPolicy (None = trusting)


@dataclass
class StreamStats:
    """Per-run accounting. ``peak_device_bytes`` is the analytic resident
    footprint of the streaming state (chunk buffer + node/sketch/agg state),
    the number the one-shot path's full edge materialization is compared to;
    ``peak_host_bytes`` is its host-side mirror (edge array + tail buffer
    in-memory, staging buffers only when disk-backed). ``host_fill_s`` is
    time spent reading the store into staging; ``copy_stall_s`` is time
    blocked waiting for an in-flight transfer before a staging buffer could
    be reused. That copy queues behind every program dispatched before it,
    so ``copy_stall_s`` is mostly the host held back by device work; the
    device sits idle only where a profiled run's ``stream.stall`` spans
    hold no device operation. ``raster_update_s`` / ``raster_chunks`` are
    the blocking per-chunk timing of the renderer's streamed edge-splat
    pass (repro/render/raster.py, populated under
    ``RenderConfig.time_raster``; benchmarks/render_bench.py).

    ``stage_devices`` maps each stage that ran ("detect", "supergraph",
    and "layout" once ``biggraphvis`` has laid out) to the mesh size its
    compiled update actually ran on, 1 where it ran unsharded or fell
    back; ``collective_bytes`` maps it to the bytes one device contributed
    to all-reduces and all-gathers, computed from shapes at dispatch.
    ``devices`` is the fewest devices any stage that was asked to shard
    (``sharded_stages``) ran on, so one stage's fallback shows even where
    the others engaged; 1 when no stage was asked. ``peak_local_bytes`` is
    the analytic *per-device* resident footprint — replicated state at
    full size plus this device's 1/D slice of the chunk buffers. With
    ``devices == 1`` it equals ``peak_device_bytes``;
    benchmarks/shard_bench.py asserts it shrinks toward 1/D of the
    single-device peak as the chunk term dominates."""

    passes: int = 0
    chunks: int = 0
    edges_streamed: int = 0
    seconds: float = 0.0
    chunk_size: int = 0
    devices: int = 1
    peak_device_bytes: int = 0
    peak_local_bytes: int = 0
    peak_host_bytes: int = 0
    host_fill_s: float = 0.0
    copy_stall_s: float = 0.0
    raster_update_s: float = 0.0
    raster_chunks: int = 0
    stage_seconds: dict = field(default_factory=dict)
    stage_devices: dict = field(default_factory=dict)
    collective_bytes: dict = field(default_factory=dict)
    sharded_stages: list = field(default_factory=list)
    # Resilience accounting (ISSUE 10): validation/quarantine tallies are
    # copied from the stream's ``ValidationAccounting``. ``quarantined_*``
    # report *distinct* chunks (a permanently-bad chunk is hit once per
    # pass; the per-occurrence tally is the ``errors.quarantined_chunks``
    # counter, which increments at the point of occurrence). ``resumed_at``
    # records the checkpoint cursor a resumed run picked up from ("" for
    # an uninterrupted run).
    retries: int = 0
    quarantined_chunks: int = 0
    quarantined_chunk_ids: list = field(default_factory=list)
    dropped_edges: int = 0
    resumed_at: str = ""

    @property
    def edges_per_s(self) -> float:
        return self.edges_streamed / self.seconds if self.seconds > 0 else 0.0

    def publish(self, registry=None) -> None:
        """Mirror this run's accounting into the metrics registry
        (``repro.obs.REGISTRY`` by default) — the engine's side of the
        one-instrumentation-layer contract: counters accumulate across
        runs, per-run seconds land as gauges, residency peaks as
        high-watermark gauges. ``launch/render_runner.py`` and the
        ``--metrics-out`` CLI dumps read these instead of hand-formatting
        the dataclass fields."""
        reg = registry if registry is not None else REGISTRY
        ensure_error_counters(reg)  # degradation visible even at 0
        reg.counter("stream.runs").inc()
        reg.counter("stream.passes").inc(self.passes)
        reg.counter("stream.chunks").inc(self.chunks)
        reg.counter("stream.edges").inc(self.edges_streamed)
        for name, value in (
            ("stream.seconds", self.seconds),
            ("stream.edges_per_s", self.edges_per_s),
            ("stream.chunk_size", self.chunk_size),
            ("stream.host_fill_s", self.host_fill_s),
            ("stream.copy_stall_s", self.copy_stall_s),
            ("stream.raster_update_s", self.raster_update_s),
        ):
            reg.gauge(name).set(value)
        for stage, secs in self.stage_seconds.items():
            reg.gauge(f"stream.stage.{stage}").set(secs)
        reg.gauge("stream.peak_device_bytes").set_max(self.peak_device_bytes)
        reg.gauge("stream.peak_local_bytes").set_max(self.peak_local_bytes)
        reg.gauge("stream.peak_host_bytes").set_max(self.peak_host_bytes)
        self.publish_shards(reg)

    def record_stage(self, stage: str, devices: int, collective_bytes: int,
                     sharded: bool) -> None:
        """Record what ``stage`` ran on; ``sharded``: it was asked to shard."""
        self.stage_devices[stage] = devices
        self.collective_bytes[stage] = collective_bytes
        if sharded and stage not in self.sharded_stages:
            self.sharded_stages.append(stage)
        self.devices = min(
            (self.stage_devices[s] for s in self.sharded_stages), default=1)

    def publish_shards(self, registry=None) -> None:
        """``shard.devices.<stage>`` and ``shard.collective_bytes.<stage>``
        gauges of the recorded stages, and ``stream.devices``."""
        reg = registry if registry is not None else REGISTRY
        for stage, d in self.stage_devices.items():
            reg.gauge(f"shard.devices.{stage}").set(d)
            reg.gauge(f"shard.collective_bytes.{stage}").set(
                self.collective_bytes[stage])
        reg.gauge("stream.devices").set(self.devices)


def tree_bytes(*trees) -> int:
    """Total bytes of every array leaf across the given pytrees."""
    total = 0
    for tree in trees:
        for leaf in jax.tree_util.tree_leaves(tree):
            if hasattr(leaf, "dtype"):
                total += int(np.prod(leaf.shape)) * leaf.dtype.itemsize
    return total


class EdgeChunkStream:
    """Chunked view over any edge source (``repro.data.edge_store``).

    Yields [chunk_size, 2] int32 chunks; the tail chunk is padded with the
    trash node ``n_nodes`` (a no-op for every chunk-update body). The source
    is validated (dtype/shape) once here, at construction — a float or
    mis-shaped edge array raises immediately instead of failing deep inside
    a kernel. Iterating counts one pass.

    Two host-side regimes:

    * in-memory source — chunks are zero-copy slices of the edge array;
      the padded tail buffer is allocated once and never mutated, so it is
      safe even when the host→device transfer aliases host memory.
    * disk-backed source — ``device_chunks`` fills a small ring of
      persistent staging buffers (the pinned-staging analog; allocated
      once, reused across chunks and passes) and transfers each with
      ``device_put_copied``. What makes the reuse safe is the wait: before
      a buffer is refilled, the device copy made from its previous
      contents is blocked on, and once that copy is ready no array reads
      the buffer. ``device_put`` alone gives no such point — on CPU its
      result may alias the buffer for as long as it lives. Plain iteration
      allocates a fresh buffer per chunk instead, since yielded chunks may
      outlive the next read.
    """

    def __init__(self, source, n_nodes: int, chunk_size: int,
                 block_size: int = 1, policy=None):
        if chunk_size < 1:
            raise ValueError(f"chunk_size must be positive, got {chunk_size}")
        self.store = as_edge_store(source)
        self.n_nodes = n_nodes
        self.n_edges = self.store.n_edges
        # Defensive reads (resilience.ValidationPolicy): every chunk goes
        # through validated_read — retry/quarantine/range checks — which
        # needs a mutable staging buffer, so the in-memory zero-copy slice
        # path is disabled below when a policy is set.
        self.policy = policy
        self.acct = ValidationAccounting()
        # Round up so chunk boundaries align with SCoDA block boundaries,
        # and clamp to the padded edge list — a chunk larger than |E| would
        # only buy a bigger trash-padded buffer.
        bs = max(1, block_size)
        cap = max(bs, ((self.n_edges + bs - 1) // bs) * bs)
        self.chunk_size = min(((chunk_size + bs - 1) // bs) * bs, cap)
        self.n_chunks = max(1, -(-self.n_edges // self.chunk_size))
        self.passes = 0
        self.edges = (
            self.store.array
            if isinstance(self.store, InMemoryEdgeStore) and policy is None
            else None
        )
        self._staging = None  # lazy ring of reusable disk-path buffers
        self._inflight = None  # device array whose transfer reads each buffer
        if self.edges is not None:
            # The tail chunk is identical every pass, so its padded buffer
            # is filled once and never mutated — safe even when the
            # host→device transfer aliases host memory.
            start = (self.n_chunks - 1) * self.chunk_size
            self._tail_buf = np.full(
                (self.chunk_size, 2), n_nodes, dtype=EDGE_DTYPE
            )
            self._tail_buf[: self.n_edges - start] = self.edges[start:]
        else:
            self._tail_buf = None

    def __len__(self) -> int:
        return self.n_chunks

    @property
    def chunk_bytes(self) -> int:
        return self.chunk_size * 2 * 4

    def staging_buffers(self, prefetch: int = 1) -> int:
        """Host staging buffers the disk path keeps in flight: one being
        filled plus one per outstanding transfer (0 for in-memory)."""
        if self.edges is not None:
            return 0
        return max(2, prefetch + 1)

    def inflight_buffers(self, prefetch: int = 1) -> int:
        """Device-side chunk buffers alive at once under ``prefetch``."""
        if self.edges is not None:
            live = 1 + max(0, prefetch)  # dispatch-ahead queue + current
        else:
            live = max(2, prefetch + 1)  # one per staging-ring slot
        return min(self.n_chunks, live)

    def host_bytes(self, prefetch: int = 1) -> int:
        """Host residency of streaming this source: the resident edge array
        + tail buffer in-memory; just the staging ring when disk-backed."""
        base = self.store.resident_bytes
        if self.edges is not None:
            return base + self._tail_buf.nbytes
        return base + self.staging_buffers(prefetch) * self.chunk_bytes

    def _read_chunk(self, i: int, buf: np.ndarray) -> np.ndarray:
        if self.policy is not None:
            return validated_read(
                self.store, i, self.chunk_size, buf, self.n_nodes,
                self.policy, self.acct,
            )
        k = self.store.read_into(i * self.chunk_size, buf)
        if k < self.chunk_size:
            buf[k:] = self.n_nodes  # pad the tail with the trash node
        return buf

    def _host_chunks(self, start: int = 0):
        cs = self.chunk_size
        if self.edges is not None:
            for i in range(start, self.n_chunks - 1):
                yield self.edges[i * cs:(i + 1) * cs]
            if start <= self.n_chunks - 1:
                yield self._tail_buf
        else:
            for i in range(start, self.n_chunks):
                buf = np.empty((cs, 2), dtype=EDGE_DTYPE)
                yield self._read_chunk(i, buf)

    def __iter__(self):
        self.passes += 1
        return self._host_chunks()

    def device_chunks(self, put=None, prefetch: int = 1,
                      stats: StreamStats | None = None, start: int = 0,
                      tracer=None):
        """One pass of device-resident chunks, transfers overlapping compute.

        In-memory sources dispatch ``put`` up to ``prefetch`` chunks ahead
        (chunks are immutable slices, so no staging is needed). Disk-backed
        sources run the double-buffered pipeline described in the class
        docstring; their default ``put`` is ``device_put_copied``, and any
        caller-supplied ``put`` must return an array that no longer reads
        the host buffer once it is ready (StreamRunner's ``put`` is
        ``device_put_copied`` too). ``start`` skips the first chunks — the
        checkpoint/resume cursor (``stream_detect(resume=)``). On the disk
        path ``tracer`` (None = process-global) spans each chunk's
        ``stream.stall`` (the wait ``copy_stall_s`` adds up),
        ``stream.fill`` (the store read) and ``stream.put`` (the transfer's
        dispatch).
        """
        self.passes += 1
        depth = max(0, prefetch)
        if self.edges is not None:
            yield from _dispatch_ahead(
                self._host_chunks(start), put or jnp.asarray, depth
            )
            return

        put = put or device_put_copied
        tr = tracer if tracer is not None else get_tracer()
        nbuf = self.staging_buffers(depth)
        if self._staging is None or len(self._staging) < nbuf:
            self._staging = [
                np.full((self.chunk_size, 2), self.n_nodes, dtype=EDGE_DTYPE)
                for _ in range(nbuf)
            ]
            self._inflight = [None] * nbuf
        # In-flight transfers are tracked on the stream, not the generator:
        # the staging ring persists across passes, so the first fills of a
        # new pass must still wait out the previous pass's tail transfers
        # (device_put is asynchronous; CPU only hides this by luck).
        inflight = self._inflight
        pending = deque()
        for i in range(start, self.n_chunks):
            b = i % nbuf
            if inflight[b] is not None:
                # The ring wrapped: before overwriting this staging buffer,
                # wait until the device copy made from it is ready.
                with tr.span("stream.stall", chunk=i):
                    t0 = time.perf_counter()
                    inflight[b].block_until_ready()
                    if stats is not None:
                        stats.copy_stall_s += time.perf_counter() - t0
                inflight[b] = None
            with tr.span("stream.fill", chunk=i):
                t0 = time.perf_counter()
                buf = self._read_chunk(i, self._staging[b])
                if stats is not None:
                    stats.host_fill_s += time.perf_counter() - t0
            with tr.span("stream.put", chunk=i):
                dev = put(buf)
            inflight[b] = dev
            pending.append(dev)
            if len(pending) > depth:
                yield pending.popleft()
        yield from pending


def _dispatch_ahead(chunks, put, depth: int):
    """Host→device copy dispatched ``depth`` chunks ahead of compute."""
    if depth <= 0:
        for chunk in chunks:
            yield put(chunk)
        return
    queue = []
    for chunk in chunks:
        queue.append(put(chunk))
        if len(queue) > depth:
            yield queue.pop(0)
    yield from queue


@functools.partial(jax.jit, donate_argnums=(0,))
def _degree_update(deg, chunk):
    """Chunk-incremental graph degrees ([n+1] accumulator, trash last)."""
    deg = deg.at[chunk[:, 0]].add(1)
    deg = deg.at[chunk[:, 1]].add(1)
    return deg.at[-1].set(0)


@functools.lru_cache(maxsize=None)
def _sharded_degree_update(mesh):
    """``_degree_update`` over the detect-pass placement ([n_blocks, bs, 2]
    sharded on the within-block axis): local scatter-add + integer psum."""
    from jax.sharding import PartitionSpec as P

    from repro.sharding.rules import block_chunk_spec

    axes = tuple(mesh.axis_names)

    def body(deg, blocks):
        flat = blocks.reshape(-1, 2)
        inc = jnp.zeros_like(deg).at[flat[:, 0]].add(1).at[flat[:, 1]].add(1)
        return (deg + jax.lax.psum(inc, axes)).at[-1].set(0)

    mapped = shard_map_compat(
        body, mesh, in_specs=(P(), block_chunk_spec(mesh)), out_specs=P()
    )
    return jax.jit(mapped, donate_argnums=(0,))


def _detect_put(mesh, block_size: int):
    """Chunk placement for the sharded detect pass: view the [C, 2] host
    buffer as [n_blocks, block_size, 2] and shard the within-block axis
    (``block_chunk_spec``) so the SCoDA block scan runs in lockstep."""
    from jax.sharding import NamedSharding

    from repro.sharding.rules import block_chunk_spec

    sharding = NamedSharding(mesh, block_chunk_spec(mesh))

    def put(buf):
        blocks = np.asarray(buf).reshape(-1, block_size, 2)
        return device_put_copied(blocks, sharding)

    return put


def _row_put(mesh):
    """Chunk placement for the sharded supergraph pass: contiguous [C/D, 2]
    row shards per device (``row_chunk_spec`` — StreamRunner's placement)."""
    from jax.sharding import NamedSharding

    from repro.sharding.rules import row_chunk_spec

    sharding = NamedSharding(mesh, row_chunk_spec(mesh))

    def put(buf):
        return device_put_copied(np.asarray(buf), sharding)

    return put


def _chunk_edges(chunk) -> int:
    """Edge count of a device chunk in either layout ([C,2] or [B,bs,2])."""
    return int(np.prod(chunk.shape[:-1]))


def _effective_mesh(mesh, shard: bool, *divisible: int):
    """The mesh to shard on, or None: sharding must be requested, the mesh
    multi-device, and every gated extent divisible by the device count."""
    if mesh is None or not shard or mesh.size <= 1:
        return None
    if any(d % mesh.size != 0 for d in divisible):
        return None
    return mesh


def _account_pass_peaks(stats, stream, prefetch, *state_trees, devices: int = 1):
    state_b = tree_bytes(*state_trees)
    chunk_b = stream.chunk_bytes * stream.inflight_buffers(prefetch)
    stats.peak_device_bytes = max(stats.peak_device_bytes, state_b + chunk_b)
    # Per-device analytic: state replicated, chunk buffers sharded 1/D.
    stats.peak_local_bytes = max(
        stats.peak_local_bytes, state_b + chunk_b // devices
    )
    stats.peak_host_bytes = max(
        stats.peak_host_bytes, stream.host_bytes(prefetch)
    )


def stream_detect(
    stream: EdgeChunkStream,
    n_nodes: int,
    cfg: ScodaConfig,
    *,
    put=None,
    prefetch: int = 1,
    stats: StreamStats | None = None,
    mesh=None,
    shard: bool = False,
    tracer=None,
    ckpt=None,
    resume: dict | None = None,
):
    """Multi-round SCoDA over the chunk stream; graph degrees are fused into
    the first pass. Returns (labels [n], scoda_deg [n], graph_deg [n]).

    With ``mesh`` + ``shard`` the per-chunk updates run device-sharded
    (bit-identical — core/scoda.py); the engine then owns chunk placement
    (the detect pass needs ``block_chunk_spec``, so any caller ``put`` is
    superseded). Falls back to the unsharded path unless ``block_size`` and
    the chunk size divide by the device count. ``tracer`` emits the
    ``detect``/``detect.round``/``detect.chunk`` span tree (None =
    process-global tracer).

    ``ckpt`` (a ``resilience.StreamCheckpointer``) is notified at every
    chunk boundary with the normalized resume cursor — the (round, chunk)
    of the next unprocessed chunk — and a lazy host-side payload of the
    full detect state (SCoDA com/deg + graph-degree accumulator), all
    unsharded host arrays. ``resume`` is that payload plus the cursor
    (``{"round", "chunk", "com", "deg", "gdeg"}``): the loops pick up
    exactly there, so a resumed run replays no chunk and skips none —
    bit-identical to uninterrupted, on any device count (replicated state
    is re-``device_put`` by the first update that consumes it).
    """
    tr = tracer if tracer is not None else get_tracer()
    m = _effective_mesh(mesh, shard, cfg.block_size, stream.chunk_size)
    if m is not None and stream.chunk_size % cfg.block_size != 0:
        m = None  # chunk must hold whole blocks to reshape [B, bs, 2]
    if m is not None:
        put = _detect_put(m, cfg.block_size)
        upd = sharded_scoda_update(m, cfg)
        deg_upd = _sharded_degree_update(m)
    else:
        upd, deg_upd = None, _degree_update
    state = scoda_init(n_nodes)
    gdeg = jnp.zeros(n_nodes + 1, dtype=jnp.int32)
    sent = 0  # bytes this device contributes to collectives
    start_round, start_chunk = 0, 0
    if resume is not None:
        start_round, start_chunk = int(resume["round"]), int(resume["chunk"])
        state = (jnp.asarray(resume["com"]), jnp.asarray(resume["deg"]))
        gdeg = jnp.asarray(resume["gdeg"])
    with tr.span(
        "detect", rounds=cfg.rounds, chunk_size=stream.chunk_size,
        devices=m.size if m is not None else 1,
    ):
        for r in range(start_round, cfg.rounds):
            thr = jnp.int32(round_threshold(cfg, r))
            c0 = start_chunk if r == start_round else 0
            with tr.span("detect.round", round=r):
                for i, chunk in enumerate(
                    stream.device_chunks(put, prefetch, stats, start=c0,
                                         tracer=tr),
                    start=c0,
                ):
                    with tr.span("detect.chunk", round=r, chunk=i):
                        if m is not None:
                            sent += sharded_update_collective_bytes(
                                state, chunk, m.size, cfg)
                            if r == 0:
                                sent += gdeg.nbytes  # psum of the degrees
                        if r == 0:
                            gdeg = deg_upd(gdeg, chunk)
                        if m is not None:
                            state = upd(state, chunk, thr)
                        else:
                            state = scoda_update(state, chunk, thr, cfg)
                    if stats is not None:
                        stats.chunks += 1
                        stats.edges_streamed += _chunk_edges(chunk)
                    if ckpt is not None:
                        last = i + 1 == stream.n_chunks
                        nr, nc = (r + 1, 0) if last else (r, i + 1)
                        ckpt.boundary(
                            "detect", nr, nc, last,
                            # Bind current values: np.asarray blocks until
                            # the update is done, before the next donation.
                            lambda s=state, g=gdeg: {
                                "com": np.asarray(s[0]),
                                "deg": np.asarray(s[1]),
                                "gdeg": np.asarray(g),
                            },
                        )
    if stats is not None:
        stats.passes += cfg.rounds - start_round
        devices = m.size if m is not None else 1
        _account_pass_peaks(stats, stream, prefetch, state, gdeg,
                            devices=devices)
        stats.record_stage("detect", devices, sent,
                           sharded=mesh is not None and shard)
    labels, scoda_deg = scoda_finalize(state, n_nodes, cfg)
    return labels, scoda_deg, gdeg[:n_nodes]


def stream_supergraph(
    stream: EdgeChunkStream,
    labels: jnp.ndarray,
    node_deg: jnp.ndarray,
    n_nodes: int,
    s_cap: int,
    max_super_edges: int,
    cms_cfg: cms_lib.CMSConfig,
    *,
    put=None,
    prefetch: int = 1,
    stats: StreamStats | None = None,
    with_modularity: bool = True,
    agg_backend: str = "merge",
    mesh=None,
    shard: bool = False,
    tracer=None,
    ckpt=None,
    resume: dict | None = None,
):
    """One fused pass: superedge aggregation + modularity accumulation.

    ``ckpt``/``resume`` follow ``stream_detect``'s contract. The supergraph
    payload carries the aggregation + modularity accumulators *and* the
    detect outputs (labels, graph degrees) so a run killed in this phase
    resumes without re-running detect; dense labels and the CMS community
    sizes are deterministic functions of the labels and are recomputed on
    resume rather than checkpointed.

    CMS community sizing is node-keyed (one sketch update per node, weight =
    graph degree) and so needs no edge pass. Returns (Supergraph, Q) with Q
    None when ``with_modularity`` is false. ``agg_backend`` is the
    ``StreamConfig`` aggregation knob (see its docstring).

    With ``mesh`` + ``shard`` the aggregation/modularity chunk updates and
    the node-keyed CMS sizing run device-sharded (bit-identical —
    core/supergraph.py, core/modularity.py, core/cms.py); chunks are placed
    row-sharded by the engine. Falls back to unsharded when the chunk size
    doesn't divide by the device count.
    """
    tr = tracer if tracer is not None else get_tracer()
    m = _effective_mesh(mesh, shard, stream.chunk_size)
    with tr.span(
        "supergraph", chunk_size=stream.chunk_size, s_cap=s_cap,
        agg_backend=agg_backend, devices=m.size if m is not None else 1,
    ):
        labels_dense, n_supernodes = dense_labels(labels, n_nodes)
        # Bytes this device contributes to collectives: the psum of the
        # CMS sketch (rows × cols float32), then per chunk below.
        sent = 4 * cms_cfg.rows * cms_cfg.cols if m is not None else 0
        with tr.span("supergraph.sizes"):
            sizes = community_sizes(
                labels_dense, node_deg, n_supernodes, s_cap, cms_cfg, mesh=m
            )

        if m is not None:
            put = _row_put(m)
            one_agg = sharded_agg_update(m, s_cap, max_super_edges, agg_backend)
            mod_upd = sharded_modularity_update(m) if with_modularity else None
        else:
            def one_agg(st, chunk, ext):
                return agg_update(st, chunk, ext, s_cap, max_super_edges, agg_backend)

            mod_upd = modularity_update

        agg_ext = jnp.concatenate([labels_dense, jnp.array([s_cap], jnp.int32)])
        mod_ext = jnp.concatenate([labels_dense, jnp.array([-1], jnp.int32)])
        agg = agg_init(s_cap, max_super_edges)
        mod = modularity_init(n_nodes) if with_modularity else None
        start_chunk = 0
        if resume is not None:
            start_chunk = int(resume["chunk"])
            agg = tuple(
                jnp.asarray(resume[k])
                for k in ("agg_a", "agg_b", "agg_w", "agg_n")
            )
            if with_modularity:
                mod = tuple(
                    jnp.asarray(resume[k])
                    for k in ("mod_m", "mod_intra", "mod_dcom")
                )

        def payload(a, md):
            out = {
                "labels": np.asarray(labels),
                "deg": np.asarray(node_deg),
                "agg_a": np.asarray(a[0]),
                "agg_b": np.asarray(a[1]),
                "agg_w": np.asarray(a[2]),
                "agg_n": np.asarray(a[3]),
            }
            if md is not None:
                out["mod_m"] = np.asarray(md[0])
                out["mod_intra"] = np.asarray(md[1])
                out["mod_dcom"] = np.asarray(md[2])
            return out

        for i, chunk in enumerate(
            stream.device_chunks(put, prefetch, stats, start=start_chunk,
                                 tracer=tr),
            start=start_chunk,
        ):
            with tr.span("supergraph.chunk", chunk=i):
                if m is not None:
                    sent += sharded_agg_collective_bytes(chunk.shape[0], m.size)
                    if with_modularity:
                        sent += tree_bytes(mod)  # psum of the accumulators
                agg = one_agg(agg, chunk, agg_ext)
                if with_modularity:
                    mod = mod_upd(mod, chunk, mod_ext)
            if stats is not None:
                stats.chunks += 1
                stats.edges_streamed += _chunk_edges(chunk)
            if ckpt is not None:
                last = i + 1 == stream.n_chunks
                ckpt.boundary(
                    "supergraph", 0, i + 1, last,
                    functools.partial(payload, agg, mod),
                )
    if stats is not None:
        stats.passes += 1
        devices = m.size if m is not None else 1
        _account_pass_peaks(
            stats, stream, prefetch, agg, mod, labels_dense, sizes, node_deg,
            devices=devices,
        )
        stats.record_stage("supergraph", devices, sent,
                           sharded=mesh is not None and shard)
    sedges, sweights, n_superedges = agg_finalize(agg)
    q = modularity_finalize(mod) if with_modularity else None
    sg = Supergraph(
        edges=sedges,
        weights=sweights,
        sizes=sizes,
        n_supernodes=n_supernodes,
        n_superedges=n_superedges,
        labels=labels_dense,
    )
    return sg, q


def stream_pipeline(
    source,
    n_nodes: int,
    scoda_cfg: ScodaConfig,
    cms_cfg: cms_lib.CMSConfig,
    s_cap: int,
    max_super_edges: int,
    stream_cfg: StreamConfig | None = None,
    *,
    put=None,
    with_modularity: bool = True,
    tracer=None,
    checkpoint=None,
    resume=False,
):
    """Edge source → (labels, graph degrees, Supergraph, Q, StreamStats).

    ``source`` is anything ``repro.data.edge_store.as_edge_store`` accepts:
    a host NumPy array, an ``EdgeStore``, a path to a ``.npy``/``.bin``
    edge file or shard directory, or a list of shard paths. The engine's
    full edge-consuming pipeline; layout/coloring operate on the (small,
    device-resident) supergraph and stay with the caller.

    Fault tolerance (ISSUE 10): ``checkpoint`` is a
    ``resilience.StreamCheckpointer`` — the run then persists its full
    streaming state at the checkpointer's cadence, stamped with this
    config's fingerprint. ``resume`` is False, True (restore the newest
    valid checkpoint from ``checkpoint.ckpt_dir``), or a directory path to
    restore from; a fingerprint mismatch raises
    ``CheckpointMismatchError``, and a resume with no checkpoint on disk
    starts fresh. Resumed runs are bit-identical to uninterrupted ones on
    any device count (tests/test_resilience.py).
    """
    store = as_edge_store(source)
    cfg = stream_cfg or StreamConfig(chunk_size=max(1, store.n_edges))
    tr = tracer if tracer is not None else (
        cfg.obs if cfg.obs is not None else get_tracer()
    )
    stream = EdgeChunkStream(
        store, n_nodes, cfg.chunk_size, block_size=scoda_cfg.block_size,
        policy=cfg.validation,
    )
    stats = StreamStats(chunk_size=stream.chunk_size)

    fingerprint = config_fingerprint(
        n_nodes=n_nodes, n_edges=store.n_edges, chunk_size=stream.chunk_size,
        scoda=scoda_cfg, cms=cms_cfg, s_cap=s_cap,
        max_super_edges=max_super_edges, agg_backend=cfg.agg_backend,
        with_modularity=with_modularity,
    )
    if checkpoint is not None:
        checkpoint.fingerprint = fingerprint
    resume_detect = resume_sg = None
    labels = gdeg = None
    if resume:
        ckpt_dir = resume if isinstance(resume, (str,)) else (
            checkpoint.ckpt_dir if checkpoint is not None else None
        )
        # A checkpoint without the resume cursor (meta lost to a crash)
        # is invalid — walk back to the previous one instead of crashing.
        found = (
            restore_latest_valid(ckpt_dir, valid=lambda a, m: "chunk" in m)
            if ckpt_dir else None
        )
        if found is not None:
            arrays, meta = found
            if meta.get("fingerprint") and meta["fingerprint"] != fingerprint:
                raise CheckpointMismatchError(
                    f"checkpoint {ckpt_dir} was written by a run with "
                    f"fingerprint {meta['fingerprint']}, this run is "
                    f"{fingerprint} — resuming would not be bit-identical"
                )
            if checkpoint is not None:
                checkpoint.seed(meta)
            phase = meta.get("phase", "detect")
            cursor = {"round": meta.get("round", 0), "chunk": meta["chunk"]}
            if phase == "detect":
                resume_detect = {**cursor, **arrays}
            else:
                resume_sg = {**cursor, **arrays}
                labels = jnp.asarray(arrays["labels"])
                gdeg = jnp.asarray(arrays["deg"])
            stats.resumed_at = (
                f"{phase}:r{cursor['round']}:c{cursor['chunk']}"
            )

    with tr.span(
        "stream_pipeline", n_nodes=n_nodes, n_edges=store.n_edges,
        chunk_size=stream.chunk_size,
    ):
        # Resuming past detect skips the stage; keep the timing key so
        # downstream consumers (pipeline timings) never miss it.
        stats.stage_seconds["detect_s"] = 0.0
        if resume_sg is None:
            t0 = time.perf_counter()
            labels, _scoda_deg, gdeg = stream_detect(
                stream, n_nodes, scoda_cfg, put=put, prefetch=cfg.prefetch,
                stats=stats, mesh=cfg.mesh, shard=cfg.shard_detect, tracer=tr,
                ckpt=checkpoint, resume=resume_detect,
            )
            jax.block_until_ready(labels)
            stats.stage_seconds["detect_s"] = time.perf_counter() - t0

        t0 = time.perf_counter()
        sg, q = stream_supergraph(
            stream, labels, gdeg, n_nodes, s_cap, max_super_edges, cms_cfg,
            put=put, prefetch=cfg.prefetch, stats=stats,
            with_modularity=with_modularity,
            agg_backend=cfg.agg_backend,
            mesh=cfg.mesh, shard=cfg.shard_detect, tracer=tr,
            ckpt=checkpoint, resume=resume_sg,
        )
        jax.block_until_ready(sg.edges)
        stats.stage_seconds["supergraph_s"] = time.perf_counter() - t0
    stats.seconds = sum(stats.stage_seconds.values())
    stats.retries = stream.acct.retries
    # acct.quarantined is per-occurrence (a bad chunk is hit once per pass);
    # the stats mirror reports distinct chunks.
    qids = sorted(set(stream.acct.quarantined))
    stats.quarantined_chunks = len(qids)
    stats.quarantined_chunk_ids = qids
    stats.dropped_edges = stream.acct.dropped_edges
    stats.publish()
    return labels, gdeg, sg, q, stats


def oneshot_device_bytes(n_edges: int, n_nodes: int) -> int:
    """Resident bytes the one-shot path pins just to hold the inputs: the
    full padded edge list + node-sized state. The streaming engine's
    ``peak_device_bytes`` replaces the |E| term with one chunk buffer."""
    return n_edges * 2 * 4 + 2 * (n_nodes + 1) * 4
