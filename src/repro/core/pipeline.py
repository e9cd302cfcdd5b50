"""End-to-end BigGraphVis pipeline (paper Fig. 2 / Algorithm 3):

    edge stream ──► SCoDA communities ──► CMS sizing ──► supergraph
                ──► ForceAtlas2 layout ──► colored supernode drawing
                ──► rasterized image (repro/render, ``render_path=``)

plus the paper's second output mode: a *full-graph* ForceAtlas2 layout
recolored by the detected communities (§4.3).

Every edge-consuming stage runs through the streaming chunked-edge engine
(core/stream.py): ``biggraphvis()`` is the single-host driver, processing
the edge list as one chunk by default and as fixed-size chunks (device
residency independent of |E|) when given a ``StreamConfig``. The
multi-device form (edge shards streamed per device; CMS merged by
all-reduce, labels by all-reduce-min — DESIGN.md §4) is lowered and
compiled for the production meshes by ``launch/steps.build_bgv_step``
(the ``biggraphvis`` dry-run cells); ``launch/stream_runner.py`` drives
the chunked engine with device placement and host prefetch.
"""
from __future__ import annotations

import time
import warnings
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import cms as cms_lib
from repro.core import forceatlas2 as fa2
from repro.core.coloring import color_groups
from repro.core.scoda import ScodaConfig, detect_communities
from repro.core.stream import StreamConfig, StreamStats, stream_pipeline
from repro.core.supergraph import Supergraph, build_supergraph
from repro.graph.utils import degrees, pad_edges
from repro.obs.metrics import REGISTRY
from repro.obs.trace import get_tracer


@dataclass(frozen=True)
class BGVConfig:
    scoda: ScodaConfig
    cms: cms_lib.CMSConfig
    layout: fa2.FA2Config
    s_cap: int = 65536  # supernode capacity
    max_super_edges: int = 262144
    # Optional repro.obs.Tracer for the whole pipeline (detect → supergraph
    # → layout → render). None falls back to StreamConfig.obs, then the
    # process-global tracer (repro.obs.get_tracer) — disabled by default.
    obs: object = None


@dataclass
class BGVResult:
    positions: np.ndarray  # [s_cap, 2]
    sizes: np.ndarray  # [s_cap]
    groups: np.ndarray  # [s_cap] color group
    labels: np.ndarray  # [n] node → dense community
    supergraph: Supergraph
    modularity: float
    n_supernodes: int
    n_superedges: int
    timings: dict = field(default_factory=dict)
    stream: StreamStats | None = None  # chunked-engine accounting
    obs: object = field(default=None, repr=False)  # Tracer from the run

    def render(self, path: str | None = None, cfg=None):
        """Rasterize this result's supergraph drawing (paper §4.3) through
        the streaming renderer — the one render entry point shared by the
        batch path and the tile service (repro/serve/tiles.py renders
        viewport-restricted tiles of the same scene).

        ``path`` additionally writes a PNG; ``cfg`` is an optional
        ``repro.render.RenderConfig``. Returns ``(image [H, W, 3] uint8,
        RenderStats)`` and records the wall time in
        ``timings["render_s"]``.
        """
        # Local import: repro.render consumes this module's BGVResult.
        import dataclasses

        from repro.render import render as render_result

        tr = self.obs if self.obs is not None else get_tracer()
        if self.obs is not None:
            # Thread the run's explicit tracer into the render config so the
            # raster spans nest under this render span.
            from repro.render import RenderConfig

            if cfg is None:
                cfg = RenderConfig(obs=tr)
            elif getattr(cfg, "obs", None) is None:
                cfg = dataclasses.replace(cfg, obs=tr)
        t0 = time.perf_counter()
        with tr.span("render", path=path or ""):
            out = render_result(self, path, cfg=cfg)
        self.timings["render_s"] = time.perf_counter() - t0
        return out


# The render_path=/render_cfg= shims warn once per process, not per call
# (a streaming driver may invoke biggraphvis in a loop).
_RENDER_KWARGS_WARNED = False


def _warn_render_kwargs() -> None:
    global _RENDER_KWARGS_WARNED
    if not _RENDER_KWARGS_WARNED:
        warnings.warn(
            "biggraphvis(render_path=, render_cfg=) is deprecated; call "
            ".render(path, cfg=...) on the returned BGVResult instead",
            DeprecationWarning,
            stacklevel=3,
        )
        _RENDER_KWARGS_WARNED = True


def default_cms_cols(n_edges: int) -> int:
    """Count-min-sketch width used by ``default_config``:
    ``max(256, |E| // 1000)`` — pinned by tests/test_api.py.

    This is denser than the seed docstring's claimed ``1e-4·|E|``: at the
    paper's 34M-edge ceiling 1e-4 gives a 3.4k-column sketch whose
    collision bias visibly inflates small-community sizes, and at the
    CPU-scale suite sizes it would pin every graph at the 256 floor. One
    column per ~1000 edges keeps the §4.2 size estimates reliable across
    both regimes for 4 hash rows.
    """
    return max(256, n_edges // 1000)


def default_config(
    n_nodes: int,
    n_edges: int,
    degree_threshold: int,
    rounds: int = 4,
    iterations: int = 100,
    s_cap: int | None = None,
    repulsion: str = "exact",
    grid_size: int = 64,
    grid_window: int = 32,
    grid_rebuild: int = 1,
    stop_tolerance: float = 0.0,
    min_iterations: int = 0,
    init: str = "random",
    nan_guard: bool = False,
) -> BGVConfig:
    """Paper-shaped defaults: 4 hash rows, CMS cols = max(256, |E| // 1000)
    (``default_cms_cols`` — see its docstring for why the sketch is denser
    than the 1e-4·|E| the seed docstring claimed), δ = mode degree.

    ``repulsion``/``grid_*`` select the FA2 backend for the supergraph
    layout and seed the grid parameters ``full_layout_colored`` reuses
    (see the backend matrix in core/forceatlas2.py): "exact" is right for
    supergraphs; "grid"/"grid_pallas" are the tiled full-graph fast path.
    ``stop_tolerance``/``min_iterations`` enable FA2's adaptive stop
    (``iterations`` becomes an upper bound) and ``init`` picks the
    starting positions ("random" | "degree" | "bfs") — both also seed the
    full-graph knobs ``full_layout_colored`` reuses. ``nan_guard`` turns
    on FA2's divergence sentinel (non-finite iterations rolled back and
    damped instead of NaN-poisoning the layout — core/forceatlas2.py).
    """
    cols = default_cms_cols(n_edges)
    return BGVConfig(
        scoda=ScodaConfig(degree_threshold=degree_threshold, rounds=rounds),
        cms=cms_lib.CMSConfig(rows=4, cols=cols),
        layout=fa2.FA2Config(
            iterations=iterations, repulsion=repulsion, grid_size=grid_size,
            grid_window=grid_window, grid_rebuild=grid_rebuild,
            stop_tolerance=stop_tolerance, min_iterations=min_iterations,
            init=init, nan_guard=nan_guard,
        ),
        s_cap=s_cap or min(n_nodes, 65536),
        max_super_edges=min(4 * n_edges, 262144),
    )


def _block(fn, *args):
    out = fn(*args)
    jax.block_until_ready(jax.tree_util.tree_leaves(out)[0])
    return out


def layout_supergraph(
    sg: Supergraph, cfg: BGVConfig, mesh=None, shard_layout: bool = False,
    tracer=None,
) -> tuple[jnp.ndarray, int]:
    """ForceAtlas2 on the (small, device-resident) supergraph.

    Returns ``(positions [s_cap, 2], iterations_run)`` — the latter is
    ``cfg.layout.iterations`` unless the adaptive stop
    (``cfg.layout.stop_tolerance``) froze the scan earlier.

    The layout stage is sized to the LIVE supernode count (padded to a
    power of two for shape reuse): laying out the full s_cap padding
    would erase the paper's headline speedup — the whole point is that
    the supergraph is orders of magnitude smaller than the graph.

    With ``mesh`` + ``shard_layout`` the force pass is node-partitioned
    over the mesh (``fa2.layout_sharded`` — bit-identical, with its own
    fallbacks). ``s_layout`` is a power of two ≥ 64, so it divides by any
    power-of-two device count.
    """
    tr = tracer if tracer is not None else get_tracer()
    s_live = max(int(sg.n_supernodes), 2)
    s_layout = 1 << (s_live - 1).bit_length()
    s_layout = min(max(s_layout, 64), cfg.s_cap)
    e_live = max(int(sg.n_superedges), 1)
    e_layout = min(1 << (e_live - 1).bit_length(), sg.edges.shape[0])
    mass = jnp.maximum(sg.sizes[:s_layout], 0.0) + jnp.where(
        jnp.arange(s_layout) < sg.n_supernodes, 1.0, 0.0
    )
    mass = jnp.where(jnp.arange(s_layout) < sg.n_supernodes, mass, 0.0)
    sedges = jnp.minimum(sg.edges[:e_layout], s_layout)  # trash → s_layout
    if mesh is not None and shard_layout:
        def run(e, w, m):
            return fa2.layout_sharded(e, w, m, s_layout, cfg.layout, mesh)
    else:
        def run(e, w, m):
            return fa2.layout(e, w, m, s_layout, cfg.layout)
    with tr.span(
        "layout.supergraph", n=s_layout, edges=e_layout,
        sharded=bool(mesh is not None and shard_layout),
    ):
        pos_live, _trace, iters_run = _block(
            run, sedges, sg.weights[:e_layout], mass
        )
    if cfg.layout.nan_guard:
        # Host sync on the trace is only paid when the sentinel is armed.
        recovered = fa2.recovery_count(_trace)
        if recovered:
            REGISTRY.counter("errors.fa2_recoveries").inc(recovered)
    pos = jnp.zeros((cfg.s_cap, 2), pos_live.dtype).at[:s_layout].set(pos_live)
    return pos, int(iters_run)


def biggraphvis(
    source,
    n_nodes: int,
    cfg: BGVConfig,
    stream: StreamConfig | None = None,
    put=None,
    render_path: str | None = None,
    render_cfg=None,
    checkpoint=None,
    resume=False,
) -> BGVResult:
    """Single-host driver. ``source`` is any engine edge source: an [E,2]
    unpadded int32 host array, an ``EdgeStore``, or a path to a ``.npy`` /
    ``.bin`` edge file or shard directory (repro/data/edge_store.py) — the
    disk-backed forms stream graphs larger than host memory.

    ``stream=None`` feeds the whole edge list through the engine as a single
    chunk (the one-shot path); a ``StreamConfig`` streams it in fixed-size
    chunks so device residency is independent of |E|. Both paths produce
    identical results whatever the source (tests/test_stream.py,
    tests/test_edge_store.py) and whatever the superedge-aggregation
    backend (``StreamConfig.agg_backend``: two-level "merge" default vs
    "lexsort" baseline). ``put`` is the host→device transfer for
    chunk buffers (launch/stream_runner.py passes a sharded
    ``device_put_copied``; None selects the engine default for the source).

    ``render_path``/``render_cfg`` are deprecated shims (one
    ``DeprecationWarning`` per process) forwarding to the render entry
    point, ``BGVResult.render(path, cfg=...)`` — call that instead.

    ``checkpoint`` (a ``resilience.StreamCheckpointer``) and ``resume``
    forward to the streaming engine: the edge-consuming stages persist
    their state at chunk boundaries and a killed run restarts
    bit-identically from the newest checkpoint — see
    ``core/stream.stream_pipeline``. The layout itself is deterministic
    given the supergraph and cheap relative to streaming, so it simply
    re-runs after a resume.
    """
    tr = cfg.obs
    if tr is None and stream is not None:
        tr = stream.obs
    if tr is None:
        tr = get_tracer()
    with tr.span("biggraphvis", n_nodes=n_nodes, s_cap=cfg.s_cap):
        labels, _gdeg, sg, q, stats = stream_pipeline(
            source, n_nodes, cfg.scoda, cfg.cms, cfg.s_cap,
            cfg.max_super_edges,
            stream, put=put, tracer=tr,
            checkpoint=checkpoint, resume=resume,
        )
        t = {
            "scoda_s": stats.stage_seconds["detect_s"],
            "supergraph_s": stats.stage_seconds["supergraph_s"],
        }

        t0 = time.perf_counter()
        with tr.span("layout", iterations=cfg.layout.iterations,
                     repulsion=cfg.layout.repulsion):
            pos, layout_iters = layout_supergraph(
                sg, cfg,
                mesh=stream.mesh if stream is not None else None,
                shard_layout=stream.shard_layout if stream is not None else False,
                tracer=tr,
            )
        t["layout_s"] = time.perf_counter() - t0
        t["layout_iterations"] = layout_iters
        REGISTRY.counter("layout.runs").inc()
        REGISTRY.gauge("layout.iterations_run").set(layout_iters)
        REGISTRY.gauge("layout.seconds").set(t["layout_s"])
        REGISTRY.gauge("layout.converged").set(
            int(layout_iters < cfg.layout.iterations)
        )

        groups = color_groups(sg.sizes)
        with tr.span("biggraphvis.fetch"):  # device → host copies
            result = BGVResult(
                positions=np.asarray(pos),
                sizes=np.asarray(sg.sizes),
                groups=np.asarray(groups),
                labels=np.asarray(sg.labels),
                supergraph=sg,
                modularity=float(q),
                n_supernodes=int(sg.n_supernodes),
                n_superedges=int(sg.n_superedges),
                timings=t,
                stream=stats,
                # Only carry an *explicit* tracer; global-tracer users keep
                # the late-binding get_tracer() fallback in .render().
                obs=cfg.obs if cfg.obs is not None
                else (stream.obs if stream is not None else None),
            )
    if render_path is not None or render_cfg is not None:
        _warn_render_kwargs()
        result.render(render_path, cfg=render_cfg)
    return result


def full_layout_colored(
    edges_np: np.ndarray,
    n_nodes: int,
    cfg: BGVConfig,
    iterations: int = 500,
    stop_tolerance: float | None = None,
    min_iterations: int | None = None,
) -> tuple[np.ndarray, np.ndarray]:
    """Paper's comparison/styling path: full-graph FA2 (grid repulsion for
    scale) + BigGraphVis community colors. Returns (pos [n,2], groups [n]).

    ``cfg.layout.repulsion == "exact"`` (the supergraph default) is treated
    as "unset" here and upgraded to the tiled "grid" backend above 4096
    nodes — an exact full-graph layout at larger n is a deliberate O(n²)
    choice; call ``fa2.layout`` directly for that.

    ``stop_tolerance``/``min_iterations`` override ``cfg.layout``'s
    adaptive-stop knobs for this call (the tile service caps drill-miss
    latency this way — serve/tiles.py ``drill_stop_tolerance``); None
    inherits the config. ``cfg.layout.init`` picks the initialization.
    """
    e_cap = len(edges_np)
    edges = jnp.asarray(pad_edges(edges_np, e_cap, n_nodes))
    deg = degrees(edges, n_nodes)
    labels, _ = detect_communities(edges, n_nodes, cfg.scoda)
    sg = build_supergraph(
        edges, labels, deg, n_nodes, cfg.s_cap, cfg.max_super_edges, cfg.cms
    )
    # Full-graph scale wants the tiled grid family; honor an explicit grid
    # backend choice from the config, defaulting to the auto-dispatched
    # "grid" (Pallas on TPU, chunked XLA elsewhere) above 4096 nodes.
    repulsion = (
        cfg.layout.repulsion
        if cfg.layout.repulsion != "exact"
        else ("grid" if n_nodes > 4096 else "exact")
    )
    lcfg = fa2.FA2Config(
        iterations=iterations,
        repulsion=repulsion,
        grid_size=cfg.layout.grid_size,
        grid_window=cfg.layout.grid_window,
        grid_rebuild=cfg.layout.grid_rebuild,
        use_radii=False,
        gravity=cfg.layout.gravity,
        repulsion_k=cfg.layout.repulsion_k,
        dtype=cfg.layout.dtype,
        stop_tolerance=(
            cfg.layout.stop_tolerance
            if stop_tolerance is None
            else stop_tolerance
        ),
        min_iterations=(
            cfg.layout.min_iterations
            if min_iterations is None
            else min_iterations
        ),
        init=cfg.layout.init,
        init_bfs_rounds=cfg.layout.init_bfs_rounds,
        nan_guard=cfg.layout.nan_guard,
    )
    mass = deg.astype(jnp.float32) + 1.0
    w = jnp.ones(edges.shape[0], jnp.float32)
    tr = cfg.obs if cfg.obs is not None else get_tracer()
    with tr.span("layout.full", n=n_nodes, repulsion=repulsion):
        pos, trace, iters_run = fa2.layout(edges, w, mass, n_nodes, lcfg)
    if lcfg.nan_guard:
        recovered = fa2.recovery_count(trace)
        if recovered:
            REGISTRY.counter("errors.fa2_recoveries").inc(recovered)
    REGISTRY.gauge("layout.full_iterations_run").set(int(iters_run))
    node_groups = color_groups(sg.sizes)[jnp.clip(sg.labels, 0, cfg.s_cap - 1)]
    return np.asarray(pos), np.asarray(node_groups)
