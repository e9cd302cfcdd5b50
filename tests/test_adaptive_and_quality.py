"""Adaptive FA2 convergence (stop_tolerance/min_iterations), speed-controller
invariants, structured inits, the precomputed-grid ``step`` path, and the
repro/quality metric suite that gates the convergence claim."""
import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import forceatlas2 as fa2
from repro.graph import pad_edges, planted_partition
from repro.graph.utils import degrees
from repro.quality import (
    bfs_hops,
    crossing_proxy,
    edge_length_cv,
    layout_quality,
    neighborhood_preservation,
    sampled_stress,
)
from repro.quality.metrics import _csr


def _inputs(n=160, seed=8, communities=4):
    edges_np, _ = planted_partition(n, communities, 0.3, 0.02, seed=seed)
    edges = jnp.asarray(pad_edges(edges_np, len(edges_np), n))
    mass = degrees(edges, n).astype(jnp.float32) + 1.0
    w = jnp.ones(edges.shape[0], jnp.float32)
    return edges_np, edges, w, mass, n


# ------------------------------------------------------------ adaptive stop

def test_adaptive_stop_prefix_bit_identical():
    """A run frozen at min_iterations is bitwise the fixed run of that
    length: live rows match, frozen rows trace zero, positions agree."""
    _, edges, w, mass, n = _inputs()
    base = fa2.FA2Config(iterations=40, repulsion="exact", use_radii=False)
    # An always-true tolerance isolates the freeze machinery: the stop
    # fires the moment min_iterations allows.
    adapt = dataclasses.replace(base, stop_tolerance=1e9, min_iterations=12)
    pos_a, trace_a, it_a = fa2.layout(edges, w, mass, n, adapt)
    assert int(it_a) == 12
    fixed = dataclasses.replace(base, iterations=12)
    pos_f, trace_f, it_f = fa2.layout(edges, w, mass, n, fixed)
    assert int(it_f) == 12
    assert np.array_equal(np.asarray(pos_a), np.asarray(pos_f))
    trace_a = np.asarray(trace_a)
    assert np.array_equal(trace_a[:12], np.asarray(trace_f))
    assert (trace_a[12:] == 0.0).all()


def test_adaptive_machinery_neutral_when_never_triggered():
    """With a tolerance too tight to ever fire, the lax.cond-wrapped body
    reproduces the non-adaptive scan bit for bit and reports a full run."""
    _, edges, w, mass, n = _inputs(n=120, seed=3)
    base = fa2.FA2Config(iterations=15, repulsion="exact", use_radii=False)
    never = dataclasses.replace(base, stop_tolerance=1e-12, min_iterations=0)
    pos_b, trace_b, it_b = fa2.layout(edges, w, mass, n, base)
    pos_n, trace_n, it_n = fa2.layout(edges, w, mass, n, never)
    assert int(it_b) == int(it_n) == 15
    assert np.array_equal(np.asarray(pos_b), np.asarray(pos_n))
    assert np.array_equal(np.asarray(trace_b), np.asarray(trace_n))


def test_adaptive_stop_grid_backend_with_carry():
    """The adaptive carry composes with the grid (cell, order) carry."""
    _, edges, w, mass, n = _inputs(n=180, seed=6)
    base = fa2.FA2Config(iterations=20, repulsion="grid", grid_size=8,
                         grid_window=8, grid_rebuild=2, use_radii=False)
    adapt = dataclasses.replace(base, stop_tolerance=1e9, min_iterations=6)
    pos_a, trace_a, it_a = fa2.layout(edges, w, mass, n, adapt)
    assert int(it_a) == 6
    fixed = dataclasses.replace(base, iterations=6)
    pos_f, _, _ = fa2.layout(edges, w, mass, n, fixed)
    assert np.array_equal(np.asarray(pos_a), np.asarray(pos_f))
    assert (np.asarray(trace_a)[6:] == 0.0).all()


def test_pipeline_reports_layout_iterations():
    """biggraphvis threads the adaptive knobs to the supergraph layout and
    records the live iteration count in timings."""
    from repro.core.pipeline import biggraphvis, default_config
    from repro.graph import mode_degree

    n = 150
    edges_np, _ = planted_partition(n, 5, 0.3, 0.01, seed=3)
    cfg = default_config(n, len(edges_np), mode_degree(edges_np, n),
                         rounds=2, iterations=8, stop_tolerance=1e9,
                         min_iterations=3)
    res = biggraphvis(edges_np, n, cfg)
    assert res.timings["layout_iterations"] == 3
    assert np.isfinite(res.positions).all()


def test_full_layout_colored_adaptive_override():
    """The per-call stop_tolerance/min_iterations overrides reach the
    full-graph layout (a frozen 1-iteration run differs from the default)."""
    from repro.core import default_config, full_layout_colored
    from repro.graph import mode_degree

    n = 120
    edges_np, _ = planted_partition(n, 4, 0.3, 0.01, seed=2)
    cfg = default_config(n, len(edges_np), mode_degree(edges_np, n),
                         rounds=2, iterations=5)
    pos_full, _ = full_layout_colored(edges_np, n, cfg, iterations=30)
    pos_one, _ = full_layout_colored(edges_np, n, cfg, iterations=30,
                                     stop_tolerance=1e9, min_iterations=1)
    assert np.isfinite(pos_one).all()
    assert not np.array_equal(pos_full, pos_one)


# ------------------------------------------------- speed-controller algebra

@settings(max_examples=25, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(1, 64),
       st.floats(0.0, 1e3, allow_nan=False))
def test_apply_speed_invariants(seed, n, prev_gs):
    """FA2 Algorithm 1 controller invariants on arbitrary force fields:
    the displacement cap |Δx| ≤ 10 (speed ≤ 10/|f|), the global-speed
    clamp min(τ·traction/swing, 1.5·prev + 1e-3), the force passthrough,
    and the (g_swing, g_traction, global_speed) trace row."""
    rng = np.random.default_rng(seed)
    pos = jnp.asarray(rng.normal(0, 100, (n, 2)).astype(np.float32))
    prev_f = jnp.asarray(rng.normal(0, 5, (n, 2)).astype(np.float32))
    f = jnp.asarray(rng.normal(0, 5, (n, 2)).astype(np.float32))
    mass = jnp.asarray(rng.uniform(0.5, 4.0, n).astype(np.float32))
    cfg = fa2.FA2Config()
    state = (pos, prev_f, jnp.float32(prev_gs))
    (new_pos, kept_f, gs), row = fa2._apply_speed(state, f, mass, cfg)

    disp = np.linalg.norm(np.asarray(new_pos) - np.asarray(pos), axis=-1)
    assert (disp <= 10.0 * (1.0 + 1e-4) + 1e-6).all()

    swing = np.linalg.norm(np.asarray(f - prev_f, np.float64), axis=-1)
    traction = 0.5 * np.linalg.norm(np.asarray(f + prev_f, np.float64), axis=-1)
    m = np.asarray(mass, np.float64)
    g_sw = float((m * swing).sum()) + 1e-9
    g_tr = float((m * traction).sum())
    expect = min(cfg.jitter_tolerance * g_tr / g_sw, 1.5 * prev_gs + 1e-3)
    assert np.isclose(float(gs), expect, rtol=1e-2, atol=1e-6)
    assert np.array_equal(np.asarray(kept_f), np.asarray(f))
    np.testing.assert_allclose(
        np.asarray(row, np.float64), [g_sw, g_tr, float(gs)],
        rtol=1e-2, atol=1e-8,
    )


def test_apply_speed_zero_force_is_stationary():
    """No force and no history → zero global speed, positions untouched."""
    pos = jnp.asarray(np.random.default_rng(0).normal(0, 10, (5, 2)),
                      jnp.float32)
    zero = jnp.zeros_like(pos)
    state = (pos, zero, jnp.float32(1.0))
    (new_pos, _, gs), row = fa2._apply_speed(
        state, zero, jnp.ones(5, jnp.float32), fa2.FA2Config())
    assert np.array_equal(np.asarray(new_pos), np.asarray(pos))
    assert float(gs) == 0.0
    assert float(row[1]) == 0.0  # no traction either


def test_apply_speed_single_node():
    """n=1 (a one-community supergraph) stays finite and capped."""
    pos = jnp.asarray([[3.0, -4.0]], jnp.float32)
    f = jnp.asarray([[1e6, 0.0]], jnp.float32)  # huge force → cap binds
    state = (pos, jnp.zeros_like(pos), jnp.float32(1.0))
    (new_pos, _, gs), row = fa2._apply_speed(
        state, f, jnp.ones(1, jnp.float32), fa2.FA2Config())
    new_pos = np.asarray(new_pos)
    assert np.isfinite(new_pos).all() and np.isfinite(float(gs))
    assert np.linalg.norm(new_pos - np.asarray(pos)) <= 10.0 * (1 + 1e-4)
    assert np.isfinite(np.asarray(row)).all()


@pytest.mark.parametrize("init", ["random", "degree", "bfs"])
def test_layout_all_isolated_nodes(init):
    """An edgeless graph (every padded slot is trash) must not NaN out —
    repulsion-only dynamics, every init mode."""
    n = 16
    edges = jnp.asarray(pad_edges(np.empty((0, 2), np.int32), 8, n))
    w = jnp.ones(8, jnp.float32)
    mass = jnp.ones(n, jnp.float32)
    cfg = fa2.FA2Config(iterations=5, repulsion="exact", use_radii=False,
                        init=init)
    pos, trace, it = fa2.layout(edges, w, mass, n, cfg)
    assert np.isfinite(np.asarray(pos)).all()
    assert np.isfinite(np.asarray(trace)).all()
    assert int(it) == 5


# ------------------------------------------------------------- init modes

def test_init_modes_deterministic_and_dispatch():
    _, edges, w, mass, n = _inputs(n=96, seed=2)
    for init in ("random", "degree", "bfs"):
        cfg = fa2.FA2Config(init=init, dtype="float32")
        a = fa2.initial_positions(edges, mass, n, cfg)
        b = fa2.initial_positions(edges, mass, n, cfg)
        assert a.shape == (n, 2) and a.dtype == jnp.float32
        assert np.isfinite(np.asarray(a)).all()
        assert np.array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(ValueError, match="unknown init"):
        fa2.initial_positions(edges, mass, n, fa2.FA2Config(init="spectral"))


def test_layout_sharded_bit_identical_per_init():
    """layout vs layout_sharded start from the same compiled init, so the
    bit-identity contract survives every init mode (regression: an
    eagerly-computed degree init differed from the traced one in the low
    bits — FMA contraction — and broke sharded bit-identity). On one
    device the sharded call falls back; the shard-smoke CI matrix re-runs
    this with real multi-device meshes (96 divides 2 and 8)."""
    from repro.launch.mesh import make_stream_mesh

    _, edges, w, mass, n = _inputs(n=96, seed=2)
    for init in ("random", "degree", "bfs"):
        cfg = fa2.FA2Config(iterations=4, repulsion="exact", init=init)
        pos, trace, it = fa2.layout(edges, w, mass, n, cfg)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")  # 1-device mesh warns fallback
            pos_s, trace_s, it_s = fa2.layout_sharded(
                edges, w, mass, n, cfg, make_stream_mesh())
        assert np.array_equal(np.asarray(pos), np.asarray(pos_s)), init
        assert np.array_equal(np.asarray(trace), np.asarray(trace_s)), init
        assert int(it) == int(it_s)


def test_init_degree_places_hubs_centrally():
    n = 50
    mass = jnp.asarray(np.arange(1, n + 1, dtype=np.float32))
    pos = np.asarray(fa2.init_positions_degree(n, mass))
    r = np.linalg.norm(pos, axis=1)
    # Heaviest node sits at the innermost spiral slot.
    assert r[n - 1] == r.min()
    assert r[0] > np.median(r)


def test_init_bfs_groups_communities():
    """Smoothed BFS init starts communities co-located: mean intra-community
    distance well under mean inter-community distance before any FA2 step."""
    n = 300
    edges_np, labels = planted_partition(n, 5, 0.4, 0.002, seed=7)
    edges = jnp.asarray(pad_edges(edges_np, len(edges_np), n))
    mass = degrees(edges, n).astype(jnp.float32) + 1.0
    pos = np.asarray(fa2.init_positions_bfs(
        edges, mass, n, jax.random.PRNGKey(0)))
    d = np.linalg.norm(pos[:, None] - pos[None, :], axis=-1)
    same = labels[:, None] == labels[None, :]
    off = ~np.eye(n, dtype=bool)
    assert d[same & off].mean() < 0.8 * d[~same].mean()


# -------------------------------------------- precomputed grid step inputs

def test_step_precomputed_cell_order_parity():
    """step(cell=, order=) with fresh bin_and_sort inputs is bitwise the
    internal-binning step."""
    from repro.kernels.grid import ops as grid_ops

    _, edges, w, mass, n = _inputs(n=180, seed=5)
    cfg = fa2.FA2Config(repulsion="grid", grid_size=8, grid_window=8,
                        use_radii=False)
    rng = np.random.default_rng(1)
    pos = jnp.asarray(rng.uniform(-500, 500, (n, 2)).astype(np.float32))
    radii = jnp.sqrt(mass)
    state = (pos, jnp.zeros_like(pos), jnp.float32(1.0))
    (p1, f1, g1), r1 = fa2.step(state, edges, w, mass, radii, cfg, n)
    cell, order = grid_ops.bin_and_sort(pos, cfg.grid_size)
    (p2, f2, g2), r2 = fa2.step(state, edges, w, mass, radii, cfg, n,
                                cell=cell, order=order)
    assert np.array_equal(np.asarray(p1), np.asarray(p2))
    assert np.array_equal(np.asarray(f1), np.asarray(f2))
    assert np.array_equal(np.asarray(r1), np.asarray(r2))


# --------------------------------------------------------- quality metrics

def _path_graph(n=50):
    edges = np.stack([np.arange(n - 1), np.arange(1, n)], axis=1)
    pos = np.stack([np.arange(n, dtype=np.float64), np.zeros(n)], axis=1)
    return edges.astype(np.int32), pos


def test_quality_perfect_path_layout():
    """A path laid out as a unit-spaced line realizes its graph distances
    exactly: zero stress, full neighborhood preservation, uniform edges,
    no crossings."""
    edges, pos = _path_graph()
    n = len(pos)
    assert sampled_stress(pos, edges, n, seed=0) < 1e-6
    assert neighborhood_preservation(pos, edges, n, seed=0) == 1.0
    assert edge_length_cv(pos, edges) < 1e-9
    assert crossing_proxy(pos, edges, seed=0) == 0.0


def test_bfs_hops_on_path():
    edges, _ = _path_graph(20)
    indptr, indices = _csr(edges, 20)
    d = bfs_hops(indptr, indices, 0, 20)
    assert np.array_equal(d, np.arange(20))
    d3 = bfs_hops(indptr, indices, 0, 20, max_hops=3)
    assert (d3[:4] == np.arange(4)).all() and (d3[4:] == -1).all()


def test_sampled_stress_scale_invariant():
    edges_np, _, _, _, n = _inputs(n=200, seed=4)
    rng = np.random.default_rng(0)
    pos = rng.normal(0, 50, (n, 2))
    s1 = sampled_stress(pos, edges_np, n, seed=1)
    s2 = sampled_stress(pos * 37.0, edges_np, n, seed=1)
    assert np.isclose(s1, s2, rtol=1e-9)
    assert 0.0 <= s1 <= 1.0


def test_quality_separates_good_from_random():
    """A community-blob layout scores better than a random scatter on both
    gated metrics — the discriminative power the bench's ratio gate rests
    on."""
    n = 400
    edges_np, labels = planted_partition(n, 8, 0.3, 0.002, seed=9)
    rng = np.random.default_rng(0)
    centers = rng.uniform(-500, 500, (8, 2))
    good = centers[labels] + rng.normal(0, 18, (n, 2))
    rand = rng.uniform(-500, 500, (n, 2))
    q_good = layout_quality(good, edges_np, n, seed=0)
    q_rand = layout_quality(rand, edges_np, n, seed=0)
    assert q_good["neighborhood"] > 2.0 * q_rand["neighborhood"]
    assert q_good["stress"] < q_rand["stress"]


def test_quality_bench_check_rejects_bad_records():
    """The bench's gate actually fails on a quality regression."""
    from benchmarks.quality_bench import _check

    base = [
        {"graph": "g", "arm": "fixed", "iterations_run": 500,
         "stress": 0.2, "neighborhood": 0.25},
        {"graph": "g", "arm": "adaptive", "iterations_run": 200,
         "stress": 0.2, "neighborhood": 0.25},
        {"graph": "g", "arm": "recompile", "repeat_calls": 2,
         "compile_delta": 0},
    ]
    lines = _check([dict(r) for r in base])
    assert any("adaptive stopped" in ln for ln in lines)
    bad = [dict(r) for r in base]
    bad[1]["neighborhood"] = 0.1  # 0.4x the baseline: must trip the bar
    with pytest.raises(AssertionError, match="neighborhood"):
        _check(bad)
    slow = [dict(r) for r in base]
    slow[1]["iterations_run"] = 400  # over the half-cap budget
    with pytest.raises(AssertionError, match="budget"):
        _check(slow)
    recompiled = [dict(r) for r in base]
    recompiled[2]["compile_delta"] = 3
    with pytest.raises(AssertionError, match="recompile"):
        _check(recompiled)


def test_warn_fallback_warns_once_per_reason():
    fa2._FALLBACK_WARNED.clear()
    with pytest.warns(UserWarning, match="reason-a"):
        fa2._warn_fallback("reason-a")
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        fa2._warn_fallback("reason-a")  # second time: silent
    with pytest.warns(UserWarning, match="reason-b"):
        fa2._warn_fallback("reason-b")
    fa2._FALLBACK_WARNED.clear()
