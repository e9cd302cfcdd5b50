"""Streaming density-accumulation rasterizer: (positions, sizes, groups,
edges) → RGB image, entirely on-device (paper §4.3's colored drawing at
BigGraphVis scale — the stage ``coloring.write_svg``'s per-edge Python
string loop could not scale past ~2·10⁵ nodes).

Accumulation model (GMine/BatchLayout lesson: the drawing stage must be
batch-parallel too):

* **edges** — splatted as ``RenderConfig.edge_samples`` points along each
  segment, each sample crediting the color group of its nearer endpoint.
  Chunks stream through the engine's ``EdgeChunkStream`` double-buffered
  path (``repro.data.edge_store`` sources all work), so host and device
  residency are independent of |E|; per-chunk raster timing lands in
  ``StreamStats.raster_update_s`` under ``RenderConfig.time_raster``.
* **nodes** — radius-∝-√size disks, dense per-pixel coverage.

Both passes accumulate **int32 counts** into a per-community-color buffer
[n_groups, H·ss, W·ss] (``kernels/raster``: Pallas on TPU, XLA scatter
elsewhere). Integer adds are associative, so a chunked render is
bit-identical to the one-shot render of the same edge list — the
renderer's analogue of the engine's chunked==one-shot contract
(tests/test_render.py). Tone mapping is log1p density → palette-weighted
color + saturating alpha, composited edges-under-nodes over the
background, then box-downsampled by the supersample factor.
"""
from __future__ import annotations

import functools
import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.coloring import PALETTE
from repro.core.stream import EdgeChunkStream, StreamStats, tree_bytes
from repro.data.edge_store import as_edge_store
from repro.kernels.raster import ops as raster_ops
from repro.obs.metrics import REGISTRY
from repro.obs.trace import get_tracer

_INT32_MAX = np.iinfo(np.int32).max
_MAX_INC = 1 << 20  # per-sample increment clamp (keeps counts far from 2³¹)

# Node disks split by pixel radius: disks ≤ _SMALL_R rasterize via a
# _BBOX×_BBOX bounding-box scatter (work ∝ n·_BBOX², not n·H·W); only the
# few larger disks take the dense per-pixel kernel. _BBOX covers every
# pixel a radius-_SMALL_R disk can touch (2·(_SMALL_R+1)) and the per-pixel
# inside test is identical, so hybrid == all-dense, bit for bit.
_SMALL_R = 8.0
_BBOX = 2 * (int(_SMALL_R) + 1)


@dataclass(frozen=True)
class RenderConfig:
    """Rasterizer knobs. ``supersample`` renders at k× resolution and
    box-downsamples, ``edge_samples`` is points splatted per edge segment,
    ``backend`` is the kernels/raster dispatch (auto/ref/pallas/interpret),
    ``chunk_size``/``prefetch`` drive the EdgeChunkStream edge pass, and
    ``time_raster`` blocks per chunk to fill StreamStats raster timing
    (costs copy/compute overlap; leave off outside benchmarks).

    ``viewport`` renders a fixed world rectangle ``(x0, y0, x1, y1)``
    instead of auto-fitting the scene's bounding box: the rect maps onto
    the full image (no ``margin``), off-rect geometry is clipped by the
    rasterizer's bounds checks, and splats crossing the rect boundary are
    cut exactly at the pixel edge — so a grid of adjacent viewports tiles
    the scene seamlessly (the tile-pyramid service, repro/serve/tiles.py).
    Non-square rects keep the uniform (min-axis) scale, centered."""

    width: int = 1024
    height: int = 1024
    supersample: int = 1
    edge_samples: int = 8
    draw_edges: bool = True
    draw_nodes: bool = True
    backend: str = "auto"
    chunk_size: int = 1 << 16  # edges resident on device per raster chunk
    prefetch: int = 1
    margin: float = 0.04  # blank border as a fraction of the image
    viewport: tuple | None = None  # world rect (x0, y0, x1, y1) to render
    background: tuple = (255, 255, 255)
    edge_gain: float = 1.0  # density → intensity gains (log1p tone map)
    node_gain: float = 4.0
    edge_alpha: float = 0.85  # max edge-layer opacity
    min_radius_px: float = 1.0  # node radius floor, in output pixels
    max_radius_frac: float = 0.125  # radius cap as a fraction of min(H, W)
    time_raster: bool = False
    # Optional repro.obs.Tracer for the render spans (render.nodes /
    # render.edges / render.compose); None falls back to the
    # process-global tracer — disabled (no-op) by default.
    obs: object = None


@dataclass
class RenderStats:
    """Per-render accounting. ``peak_device_bytes`` is the analytic
    resident footprint (accumulation buffers + node state + in-flight
    chunk buffers) — independent of |E|, the number render_bench.py
    checks. ``stream`` carries the edge pass's engine-level accounting
    (chunks, stall/fill overlap, per-chunk raster timing)."""

    width: int = 0
    height: int = 0
    supersample: int = 1
    n_groups: int = 0
    nodes_drawn: int = 0
    edges_streamed: int = 0
    chunks: int = 0
    node_raster_s: float = 0.0
    edge_raster_s: float = 0.0
    compose_s: float = 0.0
    seconds: float = 0.0
    peak_device_bytes: int = 0
    stream: StreamStats | None = None
    timings: dict = field(default_factory=dict)

    @property
    def edges_per_s(self) -> float:
        return self.edges_streamed / self.edge_raster_s if self.edge_raster_s else 0.0

    @property
    def mpixels_per_s(self) -> float:
        px = self.width * self.height * self.supersample**2
        return px / self.seconds / 1e6 if self.seconds else 0.0

    def publish(self, registry=None) -> None:
        """Mirror this render's accounting into the metrics registry
        (``render.*`` — README "Observability" glossary). Gauges hold the
        last render; counters/watermarks accumulate across renders."""
        reg = registry if registry is not None else REGISTRY
        reg.counter("render.renders").inc()
        reg.counter("render.edges").inc(self.edges_streamed)
        for name, value in (
            ("render.node_raster_s", self.node_raster_s),
            ("render.edge_raster_s", self.edge_raster_s),
            ("render.compose_s", self.compose_s),
            ("render.seconds", self.seconds),
            ("render.edges_per_s", self.edges_per_s),
            ("render.mpixels_per_s", self.mpixels_per_s),
        ):
            reg.gauge(name).set(value)
        reg.gauge("render.peak_device_bytes").set_max(self.peak_device_bytes)


def _fit_transform(pos: np.ndarray, ws: int, hs: int, margin: float):
    """Uniform scale + center mapping world coords into the supersampled
    image with a blank margin, y flipped (world y-up → raster y-down)."""
    lo = pos.min(axis=0)
    hi = pos.max(axis=0)
    span = np.maximum(hi - lo, 1e-6)
    scale = (1.0 - 2.0 * margin) * min(ws / span[0], hs / span[1])
    center = (lo + hi) / 2.0
    return float(scale), float(center[0]), float(center[1])


def _viewport_transform(viewport, ws: int, hs: int):
    """Uniform scale + center mapping the fixed world rect onto the full
    image — the same (scale, ox, oy) form as ``_fit_transform`` so both
    paths share the pixel-coordinate arithmetic bit for bit."""
    x0, y0, x1, y1 = (float(c) for c in viewport)
    if not (x1 > x0 and y1 > y0):
        raise ValueError(f"degenerate viewport {viewport!r}: need x1>x0, y1>y0")
    scale = min(ws / (x1 - x0), hs / (y1 - y0))
    return scale, (x0 + x1) / 2.0, (y0 + y1) / 2.0


@functools.partial(
    jax.jit, static_argnames=("n_groups", "hs", "ws", "backend")
)
def _small_disk_splat(
    px: jnp.ndarray,  # [m] float32 pixel centers (small-radius subset)
    py: jnp.ndarray,
    r: jnp.ndarray,  # [m] float32 radii (≤ 0 rows draw nothing)
    groups: jnp.ndarray,  # [m] int32
    n_groups: int,
    hs: int,
    ws: int,
    backend: str,
) -> jnp.ndarray:
    """Bounding-box rasterization of small disks → flat [G·hs·ws] counts.

    Same per-pixel predicate as ``kernels/raster`` ``disk_accum`` ((x−cx)²
    + (y−cy)² ≤ r²) over the _BBOX×_BBOX pixel grid around each center;
    pixels outside the disk, the image, or the palette drop out via the
    scatter's INT32_MAX marker.
    """
    bx = jnp.floor(px).astype(jnp.int32) - _BBOX // 2  # [m]
    by = jnp.floor(py).astype(jnp.int32) - _BBOX // 2
    d = jnp.arange(_BBOX, dtype=jnp.int32)
    xs = bx[:, None] + d[None, :]  # [m, B]
    ys = by[:, None] + d[None, :]
    dx2 = (xs.astype(jnp.float32) - px[:, None]) ** 2  # [m, B]
    dy2 = (ys.astype(jnp.float32) - py[:, None]) ** 2
    inside = dy2[:, :, None] + dx2[:, None, :] <= (r * r)[:, None, None]
    ok = (
        inside
        & (r > 0)[:, None, None]
        & ((groups >= 0) & (groups < n_groups))[:, None, None]
        & ((ys >= 0) & (ys < hs))[:, :, None]
        & ((xs >= 0) & (xs < ws))[:, None, :]
    )
    flat = (groups[:, None, None] * hs + ys[:, :, None]) * ws + xs[:, None, :]
    flat = jnp.where(ok, flat, _INT32_MAX)
    return raster_ops.count_scatter_into(
        jnp.zeros(n_groups * hs * ws, jnp.int32), flat.reshape(-1), None, backend
    )


def _pad_pow2(arrs: tuple, fill, lo: int = 16) -> tuple:
    """Pad same-length 1-D host arrays to the next power of two (≥ lo) so
    jitted shapes recompile O(log n) times, not per scene."""
    m = len(arrs[0])
    target = max(lo, 1 << max(0, (m - 1).bit_length()))
    return tuple(
        np.concatenate([a, np.full(target - m, fill_v, a.dtype)])
        for a, fill_v in zip(arrs, fill)
    )


def _node_pass(px, py, r_px, groups, n_groups, hs, ws, backend):
    """Hybrid node rasterization: bbox scatter for small disks, dense
    per-pixel kernel for the (few) large ones. Integer counts over
    disjoint node subsets sum to exactly the all-dense result."""
    small = (r_px > 0) & (r_px <= _SMALL_R)
    large = r_px > _SMALL_R
    acc = None
    if small.any():
        args = _pad_pow2(
            (px[small].astype(np.float32), py[small].astype(np.float32),
             r_px[small].astype(np.float32), groups[small]),
            fill=(0.0, 0.0, 0.0, -1),
        )
        acc = _small_disk_splat(
            *(jnp.asarray(a) for a in args), n_groups, hs, ws, backend
        ).reshape(n_groups, hs, ws)
    if large.any():
        args = _pad_pow2(
            (px[large].astype(np.float32), py[large].astype(np.float32),
             r_px[large].astype(np.float32), groups[large]),
            fill=(0.0, 0.0, 0.0, -1),
        )
        dense = raster_ops.disk_accum(
            *(jnp.asarray(a) for a in args), n_groups, hs, ws, backend
        )
        acc = dense if acc is None else acc + dense
    return acc


@functools.partial(
    jax.jit,
    donate_argnums=(0,),
    static_argnames=("hs", "ws", "samples", "n_groups", "backend"),
)
def _edge_splat_update(
    acc: jnp.ndarray,  # [n_groups·hs·ws] int32, donated
    chunk: jnp.ndarray,  # [C, 2] int32 (trash id n_nodes = padding)
    pxy_ext: jnp.ndarray,  # [n_nodes+1, 2] float32 pixel coords
    groups_ext: jnp.ndarray,  # [n_nodes+1] int32
    winc: jnp.ndarray | None,  # [C] int32 per-edge increments (None = 1)
    hs: int,
    ws: int,
    samples: int,
    n_groups: int,
    backend: str,
):
    """One chunk of the streamed edge pass: sample segments, scatter-add."""
    n_nodes = pxy_ext.shape[0] - 1
    u, v = chunk[:, 0], chunk[:, 1]
    valid = (u >= 0) & (u < n_nodes) & (v >= 0) & (v < n_nodes)
    ui = jnp.clip(u, 0, n_nodes)
    vi = jnp.clip(v, 0, n_nodes)
    pu = pxy_ext[ui]  # [C, 2]
    pv = pxy_ext[vi]
    t = (jnp.arange(samples, dtype=jnp.float32) + 0.5) / samples  # [S]
    p = pu[:, None, :] + t[None, :, None] * (pv - pu)[:, None, :]  # [C, S, 2]
    ix = jnp.floor(p[..., 0]).astype(jnp.int32)
    iy = jnp.floor(p[..., 1]).astype(jnp.int32)
    # Each sample credits the color group of its nearer endpoint.
    g = jnp.where(
        t[None, :] < 0.5, groups_ext[ui][:, None], groups_ext[vi][:, None]
    )
    # Samples outside the image DROP (clamping would smear density streaks
    # along the border — e.g. edges incident to dead nodes whose positions
    # sit outside the alive-node viewport the transform was fitted to).
    ok = (
        valid[:, None]
        & (g >= 0) & (g < n_groups)
        & (ix >= 0) & (ix < ws)
        & (iy >= 0) & (iy < hs)
    )
    flat = jnp.where(ok, (g * hs + iy) * ws + ix, _INT32_MAX)
    inc = (
        None
        if winc is None
        else jnp.broadcast_to(winc[:, None], flat.shape).reshape(-1)
    )
    return raster_ops.count_scatter_into(acc, flat.reshape(-1), inc, backend)


@functools.partial(jax.jit, static_argnames=("ss",))
def _compose(
    node_acc,  # [G, hs, ws] int32 | None
    edge_acc,  # [G, hs, ws] int32 | None
    palette,  # [G, 3] float32
    background,  # [3] float32
    node_gain,
    edge_gain,
    edge_alpha,
    ss: int,
):
    """Tone-map (log1p density), blend palette colors, composite
    edges-under-nodes over the background, box-downsample by ``ss``."""

    def layer(acc, gain):
        i = jnp.log1p(gain * acc.astype(jnp.float32))  # [G, hs, ws]
        tot = jnp.sum(i, axis=0)
        rgb = jnp.einsum("ghw,gc->hwc", i, palette)
        rgb = rgb / jnp.maximum(tot, 1e-9)[..., None]
        alpha = 1.0 - jnp.exp(-tot)
        return rgb, alpha

    some = node_acc if node_acc is not None else edge_acc
    img = jnp.broadcast_to(background, (*some.shape[1:], 3))
    if edge_acc is not None:
        rgb, a = layer(edge_acc, edge_gain)
        a = (edge_alpha * a)[..., None]
        img = a * rgb + (1.0 - a) * img
    if node_acc is not None:
        rgb, a = layer(node_acc, node_gain)
        a = a[..., None]
        img = a * rgb + (1.0 - a) * img
    h, w = img.shape[0] // ss, img.shape[1] // ss
    img = img.reshape(h, ss, w, ss, 3).mean(axis=(1, 3))
    return jnp.clip(jnp.round(img), 0, 255).astype(jnp.uint8)


def render_arrays(
    pos,
    radii,
    groups,
    edge_source=None,
    *,
    edge_weights=None,
    cfg: RenderConfig | None = None,
) -> tuple[np.ndarray, RenderStats]:
    """Rasterize a laid-out graph → ([H, W, 3] uint8 image, RenderStats).

    ``pos`` [n, 2] world coordinates, ``radii`` [n] world radii (≤ 0 slots
    are dead padding and draw nothing), ``groups`` [n] palette indices.
    ``edge_source`` is any engine edge source over node ids < n (array,
    ``EdgeStore``, or path — repro/data/edge_store.py); ids ≥ n (the
    stream's trash padding) draw nothing. ``edge_weights`` (host [E]
    array, in-memory sources only) thickens edges by splat increment.
    """
    cfg = cfg or RenderConfig()
    pos = np.asarray(pos, np.float32).reshape(-1, 2)
    radii = np.asarray(radii, np.float32).reshape(-1)
    groups = np.asarray(groups, np.int32).reshape(-1)
    n = len(pos)
    if len(radii) != n or len(groups) != n:
        raise ValueError(
            f"pos/radii/groups disagree: {n}/{len(radii)}/{len(groups)} rows"
        )
    ss = max(1, int(cfg.supersample))
    hs, ws = cfg.height * ss, cfg.width * ss
    n_groups = len(PALETTE)
    if n_groups * hs * ws >= 2**31:
        raise ValueError(
            f"accumulation buffer {n_groups}×{hs}×{ws} overflows int32 "
            "flat indexing — lower resolution or supersample"
        )
    stats = RenderStats(
        width=cfg.width, height=cfg.height, supersample=ss, n_groups=n_groups
    )
    t_start = time.perf_counter()

    alive = radii > 0
    if cfg.viewport is not None:
        scale, ox, oy = _viewport_transform(cfg.viewport, ws, hs)
    else:
        bounds_src = pos[alive] if alive.any() else pos
        scale, ox, oy = _fit_transform(bounds_src, ws, hs, cfg.margin)
    px = (pos[:, 0] - ox) * scale + ws / 2.0
    py = hs / 2.0 - (pos[:, 1] - oy) * scale  # y-up world → y-down raster
    r_px = np.where(
        alive,
        np.clip(
            radii * scale, cfg.min_radius_px * ss,
            cfg.max_radius_frac * min(hs, ws),
        ),
        0.0,
    ).astype(np.float32)

    tr = cfg.obs if cfg.obs is not None else get_tracer()
    node_acc = None
    if cfg.draw_nodes and alive.any():
        t0 = time.perf_counter()
        with tr.span("render.nodes", n=n, hs=hs, ws=ws):
            node_acc = _node_pass(
                px.astype(np.float32), py.astype(np.float32), r_px, groups,
                n_groups, hs, ws, cfg.backend,
            )
            jax.block_until_ready(node_acc)
        stats.node_raster_s = time.perf_counter() - t0
        stats.nodes_drawn = int(alive.sum())

    edge_acc = None
    sstats = None
    if cfg.draw_edges and edge_source is not None:
        with tr.span("render.edge_setup"):
            store = as_edge_store(edge_source)
            stream = EdgeChunkStream(store, n, cfg.chunk_size)
            sstats = StreamStats(chunk_size=stream.chunk_size)
            pxy_ext = jnp.asarray(
                np.concatenate([np.stack([px, py], 1), [[0.0, 0.0]]]).astype(
                    np.float32
                )
            )
            groups_ext = jnp.asarray(
                np.concatenate([groups, [0]]).astype(np.int32))
            acc = jnp.zeros(n_groups * hs * ws, jnp.int32)
            cs = stream.chunk_size
            weights = (
                None if edge_weights is None else np.asarray(edge_weights)
            )
        t0 = time.perf_counter()
        with tr.span("render.edges", chunk_size=cs, samples=cfg.edge_samples):
            for i, chunk in enumerate(
                stream.device_chunks(prefetch=cfg.prefetch, stats=sstats,
                                     tracer=tr)
            ):
                winc = None
                if weights is not None:
                    wsl = weights[i * cs : (i + 1) * cs]
                    if len(wsl) < cs:
                        wsl = np.pad(wsl, (0, cs - len(wsl)))
                    winc = jnp.asarray(
                        np.clip(np.round(wsl), 1, _MAX_INC).astype(np.int32)
                    )
                t1 = time.perf_counter()
                with tr.span("render.edge_chunk", chunk=i):
                    acc = _edge_splat_update(
                        acc, chunk, pxy_ext, groups_ext, winc,
                        hs, ws, cfg.edge_samples, n_groups, cfg.backend,
                    )
                    if cfg.time_raster:
                        jax.block_until_ready(acc)
                        sstats.raster_update_s += time.perf_counter() - t1
                        sstats.raster_chunks += 1
                sstats.chunks += 1
                sstats.edges_streamed += chunk.shape[0]
            jax.block_until_ready(acc)
        stats.edge_raster_s = time.perf_counter() - t0
        sstats.passes += 1
        sstats.seconds = stats.edge_raster_s
        edge_acc = acc.reshape(n_groups, hs, ws)
        stats.edges_streamed = sstats.edges_streamed
        stats.chunks = sstats.chunks
        stats.peak_device_bytes += (
            stream.chunk_bytes * stream.inflight_buffers(cfg.prefetch)
            + tree_bytes(pxy_ext, groups_ext)
        )
        sstats.peak_device_bytes = stats.peak_device_bytes + tree_bytes(
            edge_acc, node_acc
        )
        sstats.peak_host_bytes = stream.host_bytes(cfg.prefetch)

    t0 = time.perf_counter()
    with tr.span("render.compose", ss=ss):
        if node_acc is None and edge_acc is None:
            image = np.broadcast_to(
                np.asarray(cfg.background, np.uint8),
                (cfg.height, cfg.width, 3),
            ).copy()
        else:
            image = np.asarray(
                _compose(
                    node_acc,
                    edge_acc,
                    jnp.asarray(PALETTE, jnp.float32),
                    jnp.asarray(np.asarray(cfg.background, np.float32)),
                    cfg.node_gain,
                    cfg.edge_gain,
                    cfg.edge_alpha,
                    ss,
                )
            )
    stats.compose_s = time.perf_counter() - t0
    stats.peak_device_bytes += tree_bytes(node_acc, edge_acc)
    stats.seconds = time.perf_counter() - t_start
    stats.stream = sstats
    stats.timings = {
        "node_raster_s": stats.node_raster_s,
        "edge_raster_s": stats.edge_raster_s,
        "compose_s": stats.compose_s,
    }
    stats.publish()
    return image, stats


def render(
    result,
    path: str | None = None,
    cfg: RenderConfig | None = None,
) -> tuple[np.ndarray, RenderStats]:
    """Render a ``BGVResult`` supergraph drawing (paper §4.3): supernode
    disks radius ∝ √size, superedges weighted by aggregated multiplicity.
    Writes a PNG when ``path`` is given; returns (image, RenderStats)."""
    cfg = cfg or RenderConfig()
    sizes = np.maximum(np.asarray(result.sizes, np.float32), 0.0)
    radii = np.sqrt(sizes)  # paper §4.1: radius ∝ √size; 0 = dead slot
    sg = result.supergraph
    edge_source = None
    weights = None
    if cfg.draw_edges and sg is not None:
        tr = cfg.obs if cfg.obs is not None else get_tracer()
        with tr.span("render.fetch"):  # device → host copy of the superedges
            edge_source = np.asarray(sg.edges)
            weights = np.asarray(sg.weights)
    image, stats = render_arrays(
        result.positions, radii, result.groups,
        edge_source, edge_weights=weights, cfg=cfg,
    )
    if path is not None:
        from repro.render.png import write_png

        write_png(path, image, tracer=cfg.obs)
    return image, stats


def image_summary(
    image: np.ndarray,
    background: tuple = (255, 255, 255),
    tol: float = 60.0,
) -> tuple[float, np.ndarray]:
    """(non-background pixel fraction, per-palette-entry pixel counts).

    A pixel counts toward a palette entry when that entry is its nearest
    palette color within euclidean RGB distance ``tol`` — the CI
    render-smoke content check (≥ 1% non-background, ≥ 3 palette colors).
    """
    flat = np.asarray(image).reshape(-1, 3).astype(np.int32)
    bg = np.asarray(background, np.int32)
    nonbg = np.any(flat != bg, axis=1)
    frac = float(nonbg.mean()) if len(flat) else 0.0
    sub = flat[nonbg]
    counts = np.zeros(len(PALETTE), np.int64)
    if len(sub):
        d2 = ((sub[:, None, :] - PALETTE.astype(np.int32)[None]) ** 2).sum(-1)
        near = d2.argmin(axis=1)
        close = d2[np.arange(len(sub)), near] <= tol * tol
        counts = np.bincount(near[close], minlength=len(PALETTE)).astype(
            np.int64
        )
    return frac, counts
