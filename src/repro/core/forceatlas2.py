"""ForceAtlas2 (Jacomy et al. 2014) in JAX — paper §3.1 / Algorithm 1.

Faithful force model:
  * gravity            f_g(i)  = kg · m_i · (towards origin)
  * attraction         f_a(e)  = w_e · (x_v − x_u)            (linear FA2)
  * repulsion          f_r(i,j)= kr · m_i · m_j / d(i,j)       (along unit vec)
  * adaptive speed     swing/traction + global & local speeds  (Algorithm 1 l.23)

with mass m_i = deg_i + 1 for plain graphs and m_i = community size for
supernodes (paper §4.1: radius ∝ √size; repulsion distance shifted by
radii so big supernodes get the space they need).

Repulsion backends (``repulsion=``):

  * "exact"       — tiled O(n²) pairwise (Pallas kernel on TPU, chunked jnp
                    on CPU; kernels/repulsion). The right choice for
                    supergraphs (n ≤ ~2·10⁵), where n² elementwise beats
                    tree codes on a systolic machine, and the only backend
                    honoring ``use_radii``.
  * "grid"        — uniform-grid monopole far field + banded same-cell
                    near field (kernels/grid), auto-dispatched: Pallas
                    tiles on TPU, the chunked/shifted XLA path elsewhere.
                    O(n·(G² + W)) work with an O(tile·G²) live set — the
                    full-graph fast path (n ≳ 10⁵, up to paper scale).
  * "grid_pallas" — same math, Pallas kernels forced (interpret mode off
                    TPU; for validation and kernel benchmarking).
  * "grid_dense"  — the legacy dense formulation materializing an
                    [n, G², 2] far-field tensor per iteration (≈100 GB at
                    the paper's 3M nodes with G=64). Kept only as the
                    baseline ``benchmarks/fa2_bench.py`` measures the tiled
                    backends against — do not use at scale.

``layout`` hoists everything reusable out of the iteration scan: positions,
weights and mass live in ``cfg.dtype``; radii √mass are computed once per
call; attraction edges are pre-sorted once into a directed segment layout
and accumulated per iteration with one sorted ``kernels/segment``
segment-sum (``indices_are_sorted`` fast path) instead of two unsorted
scatter-adds; and the grid backends carry (cell ids, cell-sorted order)
through the scan, rebuilding them every ``grid_rebuild`` iterations
(default 1 = rebuild each step, the exact legacy semantics; larger values
amortize the per-iteration argsort against slightly stale binning —
monopole masses/centroids always track the current positions).

Iterations run under ``lax.scan``; 100 iterations suffice for supergraphs
(paper §4.2.3) vs 500 for full graphs.

Convergence engineering (BatchLayout, PAPERS.md): the fixed iteration
count is an upper bound, not a schedule. With ``stop_tolerance`` > 0 the
scan carries a ``converged`` flag and freezes the body via ``lax.cond``
once the controller's global swing falls to ``stop_tolerance`` × global
traction (after ``min_iterations``) — same compiled shape, near-zero cost
for frozen steps, and ``layout`` reports ``iterations_run``. The
per-iteration trace is (g_swing, g_traction, global_speed); rows past
``iterations_run`` are zero. ``init`` picks the starting positions:
"random" (legacy uniform), "degree" (golden-angle sunflower spiral, heavy
nodes at the center), or "bfs" (hop-distance rings from the heaviest
node) — structured inits start closer to equilibrium so the stop
criterion triggers earlier.
"""
from __future__ import annotations

import functools
import warnings
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.grid import ops as grid_ops
from repro.kernels.repulsion import ops as repulsion_ops
from repro.kernels.segment import ops as segment_ops

_GOLDEN_ANGLE = 2.3999632297286533  # π(3 − √5)


@dataclass(frozen=True)
class FA2Config:
    iterations: int = 100
    gravity: float = 1.0
    repulsion_k: float = 80.0  # paper §5.1: kr = 80, kg = 1 for all networks
    strong_gravity: bool = False
    jitter_tolerance: float = 1.0  # τ in the FA2 speed controller
    repulsion: str = "exact"  # "exact" | "grid" | "grid_pallas" | "grid_dense"
    grid_size: int = 64
    grid_window: int = 32  # near-field band half-width of grid repulsion
    grid_rebuild: int = 1  # re-bin/re-sort cells every k iterations
    use_radii: bool = True  # supernode radii shift repulsion distances
    seed: int = 0
    dtype: str = "float32"  # position/force dtype of the layout loop
    # Adaptive stopping: freeze the scan body once
    # g_swing <= stop_tolerance * g_traction (0.0 = fixed iterations).
    stop_tolerance: float = 0.0
    min_iterations: int = 0  # never stop before this many iterations
    init: str = "random"  # "random" | "degree" | "bfs"
    init_bfs_rounds: int = 32  # BFS depth-propagation rounds for init="bfs"
    # Divergence sentinel (resilience, ISSUE 10): when on, an iteration
    # whose forces contain a non-finite value is rolled back (positions and
    # speed-controller memory kept) with the global speed halved, instead
    # of NaN-poisoning every later position. Recovered iterations trace as
    # [-1, -1, damped_speed] rows — ``recovery_count`` tallies them. Off by
    # default: the guard-off graph is bit-identical to pre-sentinel code.
    nan_guard: bool = False


def init_positions(
    n: int, key: jax.Array, scale: float = 1000.0, dtype: str = "float32"
) -> jnp.ndarray:
    return jax.random.uniform(
        key, (n, 2), minval=-scale, maxval=scale, dtype=jnp.dtype(dtype)
    )


def init_positions_degree(
    n: int, mass: jnp.ndarray, scale: float = 1000.0, dtype: str = "float32"
) -> jnp.ndarray:
    """Degree-greedy sunflower init: nodes placed on a golden-angle spiral
    in descending-mass order, so hubs start at the center — where FA2's
    equilibrium puts them — and leaves at the rim. Deterministic (argsort
    ties break by index) and collision-free (every radius is distinct)."""
    rank = jnp.zeros(n, jnp.int32).at[jnp.argsort(-mass)].set(
        jnp.arange(n, dtype=jnp.int32)
    )
    rf = rank.astype(jnp.float32)
    r = scale * jnp.sqrt((rf + 0.5) / n)
    theta = rf * _GOLDEN_ANGLE
    pos = jnp.stack([r * jnp.cos(theta), r * jnp.sin(theta)], axis=1)
    return pos.astype(jnp.dtype(dtype))


def init_positions_bfs(
    edges: jnp.ndarray,
    mass: jnp.ndarray,
    n: int,
    key: jax.Array,
    rounds: int = 32,
    smooth_rounds: int = 10,
    scale: float = 1000.0,
    dtype: str = "float32",
) -> jnp.ndarray:
    """BFS-ring + neighbor-smoothing init (the parallel analog of
    BatchLayout's greedy "place next to your placed neighbors").

    Scaffold: hop depths from the heaviest node via ``rounds`` scatter-min
    relaxations (jit-friendly fixed trip count; unreached nodes land one
    ring past the deepest reached one), radius ∝ depth, golden-angle
    azimuth + a small keyed radial jitter to break exact ring degeneracy.
    Then ``smooth_rounds`` Laplacian sweeps pull each node halfway to its
    neighbors' centroid (rescaled to the scaffold's RMS radius each sweep
    so the cloud doesn't collapse): graph-adjacent nodes — hence
    communities — start co-located, which is what lets the adaptive stop
    reach fixed-500-iteration quality in a fraction of the iterations
    (benchmarks/quality_bench.py gates exactly this). Padded edge slots
    (endpoint == n) write to trash rows that are dropped or reset."""
    u, v = edges[:, 0], edges[:, 1]
    seed_node = jnp.argmax(mass).astype(jnp.int32)
    unreached = jnp.int32(rounds + 1)
    depth = jnp.full(n + 1, unreached, jnp.int32).at[seed_node].set(0)

    def body(depth, _):
        new = depth.at[v].min(depth[u] + 1).at[u].min(depth[v] + 1)
        return new.at[n].set(unreached), None

    depth, _ = jax.lax.scan(body, depth, None, length=rounds)
    depth = depth[:n]
    deepest = jnp.max(jnp.where(depth >= unreached, 0, depth))
    d = jnp.where(depth >= unreached, deepest + 1, depth).astype(jnp.float32)
    r = scale * (d + 0.5) / (deepest.astype(jnp.float32) + 1.5)
    jitter = jax.random.uniform(key, (n,), dtype=jnp.float32)
    r = r * (0.9 + 0.2 * jitter)
    theta = jnp.arange(n, dtype=jnp.float32) * _GOLDEN_ANGLE
    pos = jnp.stack([r * jnp.cos(theta), r * jnp.sin(theta)], axis=1)

    deg = jnp.zeros(n + 1, jnp.float32).at[u].add(1.0).at[v].add(1.0)
    degn = jnp.maximum(deg[:n], 1.0)
    has_nbr = (deg[:n] > 0.0)[:, None]
    rms0 = jnp.sqrt(jnp.mean(jnp.sum(pos * pos, axis=1)))

    def smooth(pos, _):
        ext = jnp.concatenate([pos, jnp.zeros((1, 2), jnp.float32)])
        s = jnp.zeros((n + 1, 2), jnp.float32).at[u].add(ext[v]).at[v].add(ext[u])
        mean = s[:n] / degn[:, None]
        new = jnp.where(has_nbr, 0.5 * pos + 0.5 * mean, pos)
        rms = jnp.sqrt(jnp.mean(jnp.sum(new * new, axis=1)))
        return new * (rms0 / jnp.maximum(rms, 1e-9)), None

    pos, _ = jax.lax.scan(smooth, pos, None, length=smooth_rounds)
    return pos.astype(jnp.dtype(dtype))


def initial_positions(
    edges: jnp.ndarray, mass: jnp.ndarray, n: int, cfg: FA2Config
) -> jnp.ndarray:
    """Dispatch ``cfg.init``.

    ``layout`` and ``layout_sharded`` both take their default starting
    positions from the SAME compiled instance of this function
    (``_initial_positions_jit``) rather than tracing it inline: op-by-op
    eager execution and fused jit compilation round differently (e.g. FMA
    contraction in the spiral radii), and the sharded bit-identity
    contract needs the two entry points to start from bitwise-equal
    positions."""
    if cfg.init == "random":
        return init_positions(n, jax.random.PRNGKey(cfg.seed), dtype=cfg.dtype)
    if cfg.init == "degree":
        return init_positions_degree(n, jnp.asarray(mass), dtype=cfg.dtype)
    if cfg.init == "bfs":
        return init_positions_bfs(
            jnp.asarray(edges), jnp.asarray(mass), n,
            jax.random.PRNGKey(cfg.seed), rounds=cfg.init_bfs_rounds,
            dtype=cfg.dtype,
        )
    raise ValueError(
        f"unknown init {cfg.init!r}: expected 'random', 'degree', or 'bfs'"
    )


@functools.partial(jax.jit, static_argnames=("n", "cfg"))
def _initial_positions_jit(edges, mass, n: int, cfg: FA2Config):
    return initial_positions(edges, mass, n, cfg)


def _gravity(pos, mass, cfg: FA2Config):
    d = jnp.linalg.norm(pos, axis=-1, keepdims=True)
    unit = pos / jnp.maximum(d, 1e-9)
    if cfg.strong_gravity:
        return -cfg.gravity * mass[:, None] * pos
    return -cfg.gravity * mass[:, None] * unit


def _attraction(pos, edges, weights, n: int):
    """Σ over incident edges of w·(x_other − x_self); padded slots hit trash.

    Unsorted two-scatter form — the single-``step`` path. ``layout``
    pre-sorts the edges once and uses ``_attraction_sorted`` instead.
    """
    u, v = edges[:, 0], edges[:, 1]
    pos_ext = jnp.concatenate([pos, jnp.zeros((1, 2), pos.dtype)])
    delta = pos_ext[v] - pos_ext[u]  # force on u toward v
    f = weights[:, None] * delta
    force = jnp.zeros((n + 1, 2), pos.dtype)
    force = force.at[u].add(f)
    force = force.at[v].add(-f)
    return force[:n]


def _attraction_edge_layout(edges, weights):
    """Directed segment layout, built once per ``layout`` call: both edge
    directions concatenated and sorted by source node, so each iteration's
    accumulation is one sorted segment-sum. Padded slots (trash endpoints
    == n) sort last and are dropped by the segment-sum's range check."""
    u, v = edges[:, 0], edges[:, 1]
    src = jnp.concatenate([u, v])
    dst = jnp.concatenate([v, u])
    w2 = jnp.concatenate([weights, weights])
    order = jnp.argsort(src)
    return src[order], dst[order], w2[order]


def _attraction_sorted(pos, src, dst, w, n: int):
    """Σ over directed incident edges of w·(x_dst − x_src), src-sorted —
    the kernels/segment ``indices_are_sorted`` fast path.

    Pinned to the XLA ref backend: this sum has *n* segments, and the
    one-hot-matmul Pallas kernel streams every edge block once per node
    tile — O(n/tn · E) at full-graph n, where the sorted scatter is O(E).
    That kernel is for small-segment-count sums (supergraph aggregation,
    grid cell stats), not node-sized ones.
    """
    pos_ext = jnp.concatenate([pos, jnp.zeros((1, 2), pos.dtype)])
    f = w[:, None] * (pos_ext[dst] - pos_ext[src])
    return segment_ops.segment_sum(
        f, src, n, backend="ref", indices_are_sorted=True
    )


def _total_force(*terms):
    """Sum per-node force terms (gravity, attraction, repulsion).

    The barrier materializes each term before the adds. Without it XLA
    fuses a term's last multiply into the sum where it likes, and a fused
    multiply-add rounds once where a multiply then an add rounds twice:
    the single-device and the sharded layout bodies, fused differently,
    then drew totals 1 ulp apart. With it both add the same three arrays.
    """
    terms = jax.lax.optimization_barrier(terms)
    total = terms[0]
    for t in terms[1:]:
        total = total + t
    return total


def _pair_force(dpos, mi, mj, kr):
    """kr·mi·mj/d along the unit vector, for a [..., 2] displacement."""
    d2 = jnp.sum(dpos * dpos, axis=-1)
    mag = kr * mi * mj / jnp.maximum(d2, 1e-4)  # (1/d along unit) = 1/d²·vec
    return mag[..., None] * dpos


def _grid_repulsion(pos, mass, cfg: FA2Config):
    """Dense uniform-grid repulsion — the ``grid_dense`` baseline.

    Same monopole-far-field + banded-near-field math as kernels/grid, in
    the original fully-materialized form: an [n, G², 2] far-field tensor
    plus an [n, 2W+1] near-field gather per call. Superseded by the tiled
    backends ("grid"/"grid_pallas"); retained as the benchmark baseline
    (benchmarks/fa2_bench.py) and as a semantics oracle in tests.
    """
    g = cfg.grid_size
    window = cfg.grid_window
    n = pos.shape[0]
    kr = cfg.repulsion_k
    lo = jnp.min(pos, axis=0)
    hi = jnp.max(pos, axis=0)
    extent = jnp.maximum(hi - lo, 1e-6)
    cell2d = jnp.clip(((pos - lo) / extent * g).astype(jnp.int32), 0, g - 1)
    cell = cell2d[:, 0] * g + cell2d[:, 1]
    n_cells = g * g
    cmass = jnp.zeros(n_cells, pos.dtype).at[cell].add(mass)
    cpos = jnp.zeros((n_cells, 2), pos.dtype).at[cell].add(pos * mass[:, None])
    ccent = cpos / jnp.maximum(cmass, 1e-9)[:, None]

    # Far field: node → every cell monopole.
    diff = pos[:, None, :] - ccent[None, :, :]  # [n, G², 2]
    force = jnp.sum(_pair_force(diff, mass[:, None], cmass[None, :], kr), axis=1)

    # Subtract the own-cell monopole (it badly approximates near field + self).
    own_diff = pos - ccent[cell]
    own_f = _pair_force(own_diff, mass, cmass[cell], kr)
    force = force - own_f

    # Exact near field: same-cell neighbors are contiguous after sorting.
    order = jnp.argsort(cell)
    inv = jnp.zeros(n, jnp.int32).at[order].set(jnp.arange(n, dtype=jnp.int32))
    pos_s, mass_s, cell_s = pos[order], mass[order], cell[order]
    p = jnp.arange(n)
    offs = jnp.arange(-window, window + 1)
    raw = p[:, None] + offs[None, :]  # [n, 2W+1]
    in_range = (raw >= 0) & (raw < n)  # clipping would duplicate endpoints
    nbr = jnp.clip(raw, 0, n - 1)
    same = in_range & (cell_s[nbr] == cell_s[:, None]) & (nbr != p[:, None])
    dn = pos_s[:, None, :] - pos_s[nbr]
    fn = _pair_force(dn, mass_s[:, None], jnp.where(same, mass_s[nbr], 0.0), kr)
    near = jnp.sum(fn, axis=1)
    force = force + near[inv]
    return force


def _repulsion_forces(pos, mass, radii, cfg: FA2Config, cell=None, order=None):
    """Dispatch one iteration's repulsion to the configured backend."""
    if cfg.repulsion == "grid_dense":
        return _grid_repulsion(pos, mass, cfg)
    if cfg.repulsion in ("grid", "grid_pallas"):
        backend = "auto" if cfg.repulsion == "grid" else "pallas"
        return grid_ops.grid_repulsion(
            pos, mass, cfg.repulsion_k, cfg.grid_size, cfg.grid_window,
            cell=cell, order=order, backend=backend,
        )
    r = radii if cfg.use_radii else None
    return repulsion_ops.repulsion(pos, mass, cfg.repulsion_k, radii=r)


def _apply_speed(state, f, mass, cfg: FA2Config):
    """FA2 speed controller (Algorithm 1): swing/traction → displacement.

    Returns the updated ``(pos, f, global_speed)`` state and the trace row
    ``[g_swing, g_traction, global_speed]`` — the quantities the adaptive
    stop criterion (and the convergence trace) are built from.
    """
    pos, prev_force, global_speed = state
    swing = jnp.linalg.norm(f - prev_force, axis=-1)
    traction = 0.5 * jnp.linalg.norm(f + prev_force, axis=-1)
    g_swing = jnp.sum(mass * swing) + 1e-9
    g_traction = jnp.sum(mass * traction)
    new_gs = cfg.jitter_tolerance * g_traction / g_swing
    global_speed = jnp.minimum(new_gs, 1.5 * global_speed + 1e-3)

    fmag = jnp.linalg.norm(f, axis=-1)
    local_speed = global_speed / (1.0 + global_speed * jnp.sqrt(swing))
    # FA2 caps node displacement: speed ≤ 10 / |f|.
    local_speed = jnp.minimum(local_speed, 10.0 / jnp.maximum(fmag, 1e-9))
    pos = pos + local_speed[:, None] * f
    row = jnp.stack([g_swing, g_traction, global_speed])
    return (pos, f, global_speed), row


def _apply_speed_guarded(state, f, mass, cfg: FA2Config):
    """``_apply_speed`` behind the divergence sentinel.

    With ``cfg.nan_guard`` off this IS ``_apply_speed`` (same jaxpr, so
    guard-off layouts stay bit-identical). With it on, a non-finite force
    array skips the update entirely — positions and speed-controller
    memory are kept, the global speed is halved (so a diverging step size
    shrinks until forces are finite again) — and the trace row is
    ``[-1, -1, damped_speed]``: g_swing is otherwise ≥ 1e-9, so negative
    rows unambiguously mark recoveries (``recovery_count``) and are
    excluded from the adaptive stop test.
    """
    if not cfg.nan_guard:
        return _apply_speed(state, f, mass, cfg)
    pos, prev_force, global_speed = state

    def recover():
        damped = 0.5 * global_speed
        neg = -jnp.ones((), damped.dtype)
        return (pos, prev_force, damped), jnp.stack([neg, neg, damped])

    return jax.lax.cond(
        jnp.all(jnp.isfinite(f)),
        lambda: _apply_speed(state, f, mass, cfg),
        recover,
    )


def recovery_count(trace) -> int:
    """Number of iterations the ``nan_guard`` sentinel rolled back in a
    ``layout``/``step`` trace (negative-g_swing rows)."""
    return int((np.asarray(trace)[:, 0] < 0).sum())


@functools.partial(jax.jit, static_argnames=("cfg", "n"))
def step(
    state, edges, weights, mass, radii, cfg: FA2Config, n: int,
    cell=None, order=None,
):
    """One FA2 iteration (Algorithm 1 body): forces → speeds → displacement.

    Single-step public API (launch/steps.py builds the distributed layout
    cell on it): edge scatter runs inside the call. For the grid backends,
    pass precomputed ``(cell, order)`` from ``kernels/grid.bin_and_sort``
    to skip the per-call re-bin + argsort — repeated-step callers refresh
    them every ``cfg.grid_rebuild`` steps, mirroring ``layout``'s scan
    carry. ``layout`` also hoists the edge sort — prefer it for full runs.

    Returns ``(state, trace_row)`` with the same ``[g_swing, g_traction,
    global_speed]`` row ``layout`` traces per iteration.
    """
    pos, _, _ = state
    f = _gravity(pos, mass, cfg)
    f = f + _attraction(pos, edges, weights, n)
    f = f + _repulsion_forces(pos, mass, radii, cfg, cell=cell, order=order)
    return _apply_speed_guarded(state, f, mass, cfg)


def layout(
    edges: jnp.ndarray,
    weights: jnp.ndarray,
    mass: jnp.ndarray,
    n: int,
    cfg: FA2Config,
    pos0: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Run up to ``cfg.iterations`` FA2 steps.

    Returns ``(positions [n,2], trace [iterations,3], iterations_run)``.
    Trace rows are (g_swing, g_traction, global_speed) per iteration. With
    ``cfg.stop_tolerance`` > 0 the scan body freezes (via ``lax.cond``)
    once g_swing ≤ stop_tolerance · g_traction after ``min_iterations``;
    frozen iterations cost almost nothing and trace as zero rows, and
    ``iterations_run`` reports the live count (it is ``cfg.iterations``
    exactly when the tolerance never triggered or adaptivity is off).
    """
    from repro.obs.trace import get_tracer

    # Host-side span: brackets init + dispatch of the jitted scan (compile
    # time on first call). Never forces a device sync.
    with get_tracer().span(
        "fa2.layout", n=n, iterations=cfg.iterations,
        repulsion=cfg.repulsion, adaptive=cfg.stop_tolerance > 0.0,
    ):
        if pos0 is None:
            pos0 = _initial_positions_jit(edges, mass, n, cfg)
        return _layout_jit(edges, weights, mass, n, cfg, pos0)


@functools.partial(jax.jit, static_argnames=("cfg", "n"))
def _layout_jit(edges, weights, mass, n: int, cfg: FA2Config, pos0):
    dtype = jnp.dtype(cfg.dtype)
    pos = pos0.astype(dtype)
    weights = weights.astype(dtype)
    mass = mass.astype(dtype)
    # Hoisted per-call prep (once per layout, not once per iteration):
    radii = jnp.sqrt(jnp.maximum(mass, 0.0))  # paper: radius ∝ √size
    src, dst, w2 = _attraction_edge_layout(edges, weights)

    grid_state = cfg.repulsion in ("grid", "grid_pallas")
    # Carry (cell, order) through the scan only when a rebuild cadence > 1
    # actually reuses them; iteration 0 always rebuilds (0 % k == 0), so
    # the seed is never read and can be zeros.
    carry_grid = grid_state and cfg.grid_rebuild > 1
    adaptive = cfg.stop_tolerance > 0.0
    state = (pos, jnp.zeros_like(pos), jnp.asarray(1.0, dtype))
    if carry_grid:
        z = jnp.zeros(n, jnp.int32)
        state = state + (z, z)
    if adaptive:
        state = state + (jnp.asarray(0, jnp.int32), jnp.asarray(False))

    def live(core, cell, order, it):
        pos = core[0]
        if carry_grid:
            cell, order = jax.lax.cond(
                it % cfg.grid_rebuild == 0,
                lambda: grid_ops.bin_and_sort(pos, cfg.grid_size),
                lambda: (cell, order),
            )
        elif grid_state:
            cell, order = grid_ops.bin_and_sort(pos, cfg.grid_size)
        f = _total_force(
            _gravity(pos, mass, cfg),
            _attraction_sorted(pos, src, dst, w2, n),
            _repulsion_forces(pos, mass, radii, cfg, cell=cell, order=order),
        )
        core, row = _apply_speed_guarded(core, f, mass, cfg)
        return core, cell, order, row

    def body(state, it):
        core = state[:3]
        cell = order = None
        if carry_grid:
            cell, order = state[3], state[4]
        if not adaptive:
            core, cell, order, row = live(core, cell, order, it)
            return core + ((cell, order) if carry_grid else ()), row

        it_run, converged = state[-2], state[-1]

        def live_branch():
            c, cell2, order2, row = live(core, cell, order, it)
            # row[0] < 0 marks a nan_guard recovery — never "converged".
            done = (it + 1 >= cfg.min_iterations) & (row[0] >= 0) & (
                row[0] <= cfg.stop_tolerance * row[1]
            )
            out = c + ((cell2, order2) if carry_grid else ())
            return out + (it_run + 1, done), row

        def frozen_branch():
            return state, jnp.zeros(3, dtype)

        return jax.lax.cond(converged, frozen_branch, live_branch)

    state, trace = jax.lax.scan(body, state, jnp.arange(cfg.iterations))
    iterations_run = (
        state[-2] if adaptive else jnp.asarray(cfg.iterations, jnp.int32)
    )
    return state[0], trace, iterations_run


# --------------------------------------------------------------------------
# Node-partitioned multi-device layout (ROADMAP item 1, Arleo et al. in
# PAPERS.md): each device owns n/D consecutive nodes and computes only their
# forces; one tiled all_gather per iteration reassembles the force array for
# the (replicated) speed controller. Per-force-term placement:
#
#   gravity     — elementwise on the owned rows.
#   attraction  — full-size sorted segment-sum with non-owned sources
#                 weight-masked, owned rows sliced: owned segments receive
#                 exactly the single-device terms in the same order.
#   exact rep.  — ``repulsion_rows``: the owned rows through the same
#                 backend one device would pick — the Pallas kernel on
#                 TPU (target rows sliced, same blocks), on CPU the dense
#                 ref sliced (n ≤ 2048) or the j-chunk scan on the owned
#                 rows (rows are independent, so bitwise equal).
#   grid rep.   — bin/sort/monopole stats replicated (O(n + G²)); far field
#                 row-sliced through ``far_field_ref`` (per-node cell sums);
#                 near field via the psum-free ``near_field_rows`` halo;
#                 sorted rows gathered, then the unsort scatter replicated.
#
# Every cross-device step is a concatenation (all_gather) — never a float
# reduction — and both bodies sum the force terms through ``_total_force``,
# so D-device layouts are bit-identical to one device for "exact" on any
# platform and for "grid" on CPU (tests/test_sharded_pipeline.py). On TPU
# the single-device "grid" runs the Pallas grid kernels, which this body
# does not mirror.
# The adaptive stop composes with this for free: the gathered force array
# (hence swing/traction, hence the converged flag) is replicated, so every
# device freezes on the same iteration.
# --------------------------------------------------------------------------


_FALLBACK_WARNED: set[str] = set()


def _warn_fallback(reason: str) -> None:
    """Warn once per distinct reason that a configured mesh disengaged."""
    if reason not in _FALLBACK_WARNED:
        _FALLBACK_WARNED.add(reason)
        warnings.warn(
            f"layout_sharded: falling back to single-device layout ({reason})",
            UserWarning,
            stacklevel=3,
        )


def _sharded_fallback_reason(n: int, cfg: FA2Config, mesh) -> str | None:
    """Why a non-None mesh cannot engage, or None if it can."""
    if mesh.size <= 1:
        return "mesh is trivial (1 device)"
    if n % mesh.size != 0:
        return f"n={n} does not divide evenly over {mesh.size} devices"
    if cfg.repulsion in ("grid_pallas", "grid_dense"):
        return f"repulsion={cfg.repulsion!r} has no sharded form"
    if cfg.repulsion == "grid" and cfg.dtype != "float32":
        return (
            f"the sharded grid path runs in float32 (kernels/grid is "
            f"float32-pinned) and has no dtype={cfg.dtype!r} form"
        )
    return None


@functools.lru_cache(maxsize=None)
def _sharded_layout_fn(mesh, cfg: FA2Config, n: int):
    from jax.sharding import PartitionSpec as P

    from repro.kernels.compat import shard_map_compat
    from repro.sharding.rules import linear_axis_index

    axes = tuple(mesh.axis_names)
    sizes = tuple(mesh.shape[a] for a in axes)
    nl = n // mesh.size
    dtype = jnp.dtype(cfg.dtype)
    grid_state = cfg.repulsion == "grid"
    carry_grid = grid_state and cfg.grid_rebuild > 1
    adaptive = cfg.stop_tolerance > 0.0
    kr = cfg.repulsion_k

    def sharded_body(pos0, mass, radii, src, dst, w2):
        i0 = nl * linear_axis_index(axes, sizes)

        def rows(x):
            return jax.lax.dynamic_slice_in_dim(x, i0, nl)

        state = (pos0, jnp.zeros_like(pos0), jnp.asarray(1.0, dtype))
        if carry_grid:
            z = jnp.zeros(n, jnp.int32)
            state = state + (z, z)
        if adaptive:
            state = state + (jnp.asarray(0, jnp.int32), jnp.asarray(False))

        def live(core, cell, order, it):
            pos = core[0]
            if carry_grid:
                cell, order = jax.lax.cond(
                    it % cfg.grid_rebuild == 0,
                    lambda: grid_ops.bin_and_sort(pos, cfg.grid_size),
                    lambda: (cell, order),
                )
            elif grid_state:
                cell, order = grid_ops.bin_and_sort(pos, cfg.grid_size)

            grav = _gravity(rows(pos), rows(mass), cfg)

            pos_ext = jnp.concatenate([pos, jnp.zeros((1, 2), pos.dtype)])
            own = (src >= i0) & (src < i0 + nl)
            fe = jnp.where(own, w2, 0.0)[:, None] * (pos_ext[dst] - pos_ext[src])
            att = segment_ops.segment_sum(
                fe, src, n, backend="ref", indices_are_sorted=True
            )

            if grid_state:
                # This path only engages for cfg.dtype == "float32"
                # (layout_sharded falls back otherwise): the kernels/grid
                # helpers are float32-pinned, so pos/mass are used as-is.
                pos_s, mass_s, cell_s = pos[order], mass[order], cell[order]
                ccent, cmass = grid_ops.cell_stats(
                    pos_s, mass_s, cell_s, cfg.grid_size * cfg.grid_size,
                    backend="ref",
                )
                force_sr = grid_ops.far_field_ref(
                    rows(pos_s), rows(mass_s), rows(cell_s), ccent, cmass, kr
                )
                force_sr = force_sr + grid_ops.near_field_rows(
                    pos_s, mass_s, cell_s, kr, cfg.grid_window, i0, nl
                )
                force_s = jax.lax.all_gather(force_sr, axes, axis=0, tiled=True)
                rep = jnp.zeros_like(force_s).at[order].set(force_s)
                rep_r = rows(rep.astype(pos.dtype))
            else:
                rep_r = repulsion_ops.repulsion_rows(
                    pos, mass, i0, nl, kr,
                    radii=radii if cfg.use_radii else None,
                )
            f_r = _total_force(grav, rows(att), rep_r)

            f = jax.lax.all_gather(f_r, axes, axis=0, tiled=True)
            core, row = _apply_speed_guarded(core, f, mass, cfg)
            return core, cell, order, row

        def body(state, it):
            core = state[:3]
            cell = order = None
            if carry_grid:
                cell, order = state[3], state[4]
            if not adaptive:
                core, cell, order, row = live(core, cell, order, it)
                return core + ((cell, order) if carry_grid else ()), row

            it_run, converged = state[-2], state[-1]

            def live_branch():
                c, cell2, order2, row = live(core, cell, order, it)
                # row[0] < 0 marks a nan_guard recovery — never "converged".
                done = (it + 1 >= cfg.min_iterations) & (row[0] >= 0) & (
                    row[0] <= cfg.stop_tolerance * row[1]
                )
                out = c + ((cell2, order2) if carry_grid else ())
                return out + (it_run + 1, done), row

            def frozen_branch():
                return state, jnp.zeros(3, dtype)

            return jax.lax.cond(converged, frozen_branch, live_branch)

        state, trace = jax.lax.scan(body, state, jnp.arange(cfg.iterations))
        iterations_run = (
            state[-2] if adaptive else jnp.asarray(cfg.iterations, jnp.int32)
        )
        return state[0], trace, iterations_run

    mapped = shard_map_compat(
        sharded_body,
        mesh,
        in_specs=(P(), P(), P(), P(), P(), P()),
        out_specs=(P(), P(), P()),
    )

    def run(edges, weights, mass, pos0):
        weights = weights.astype(dtype)
        mass = mass.astype(dtype)
        radii = jnp.sqrt(jnp.maximum(mass, 0.0))
        src, dst, w2 = _attraction_edge_layout(edges, weights)
        return mapped(pos0, mass, radii, src, dst, w2)

    return jax.jit(run)


def layout_sharded(
    edges: jnp.ndarray,
    weights: jnp.ndarray,
    mass: jnp.ndarray,
    n: int,
    cfg: FA2Config,
    mesh,
    pos0: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """``layout`` with the force pass node-partitioned over ``mesh``.

    Falls back to ``layout`` — with a warn-once ``UserWarning`` naming the
    reason — when the mesh is trivial, ``n`` doesn't divide by the device
    count, the backend has no sharded form ("grid_pallas", "grid_dense"),
    or the grid backend is asked for a non-float32 dtype (kernels/grid is
    float32-pinned, so honoring ``cfg.dtype`` sharded is impossible; the
    single-device path keeps its cast-in/cast-out semantics). ``mesh=None``
    falls back silently — that is the caller opting out, not a surprise.
    Bit-identical to ``layout`` for "exact" on any platform and for
    "grid" on CPU (on TPU, ``layout``'s "grid" runs Pallas grid kernels
    this path does not mirror), including the adaptive stop: the
    converged flag is computed from the replicated gathered forces, so
    the sharded run freezes on exactly the same iteration.
    """
    if mesh is None:
        return layout(edges, weights, mass, n, cfg, pos0)
    reason = _sharded_fallback_reason(n, cfg, mesh)
    if reason is not None:
        _warn_fallback(reason)
        return layout(edges, weights, mass, n, cfg, pos0)
    from repro.obs.trace import get_tracer

    with get_tracer().span(
        "fa2.layout_sharded", n=n, iterations=cfg.iterations,
        repulsion=cfg.repulsion, devices=mesh.size,
    ):
        dtype = jnp.dtype(cfg.dtype)
        pos = (
            _initial_positions_jit(edges, mass, n, cfg)
            if pos0 is None
            else pos0.astype(dtype)
        )
        return _sharded_layout_fn(mesh, cfg, n)(edges, weights, mass, pos)
