"""Multi-device sharded detect+layout pipeline: bit-identity to the
single-device path, divisibility fallbacks, and the StreamRunner chunk
padding fix.

These tests adapt to the available device count: on the tier-1 single
device the sharded entry points take their graceful-degradation fallbacks
(API coverage), and the CI ``shard-smoke`` matrix re-runs the same file
under ``XLA_FLAGS=--xla_force_host_platform_device_count={2,8}`` where the
collectives actually engage. One subprocess test forces 4 devices so real
multi-device coverage exists even in the tier-1 run.
"""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap
from dataclasses import replace
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import forceatlas2 as fa2
from repro.core.pipeline import biggraphvis, default_config
from repro.core.stream import StreamConfig
from repro.graph import mode_degree, planted_partition
from repro.kernels.grid.ref import bin_and_sort, near_field_ref, near_field_rows
from repro.kernels.repulsion import ops as rep_ops
from repro.launch.mesh import make_stream_mesh
from repro.launch.stream_runner import StreamRunner, StreamRunnerConfig

N = 768
COMMUNITIES = 16
multi_device = pytest.mark.skipif(
    jax.device_count() < 2,
    reason="needs >1 device (XLA_FLAGS=--xla_force_host_platform_device_count)",
)


def _graph():
    edges, _ = planted_partition(N, COMMUNITIES, 0.3, 0.002, seed=11)
    return edges


def _cfg(edges, iterations=5, block=128):
    cfg = default_config(N, len(edges), mode_degree(edges, N),
                         rounds=2, iterations=iterations)
    return replace(cfg, scoda=replace(cfg.scoda, block_size=block))


def _assert_same(a, b):
    assert np.array_equal(np.asarray(a.labels), np.asarray(b.labels))
    assert np.array_equal(np.asarray(a.supergraph.edges),
                          np.asarray(b.supergraph.edges))
    assert np.array_equal(np.asarray(a.supergraph.weights),
                          np.asarray(b.supergraph.weights))
    assert np.array_equal(a.sizes, b.sizes)
    assert a.n_supernodes == b.n_supernodes
    assert a.n_superedges == b.n_superedges
    assert a.modularity == b.modularity
    assert np.array_equal(a.positions, b.positions)


def test_sharded_pipeline_matches_unsharded():
    """Full streamed pipeline, sharded vs plain, whatever the device count.

    block 128 divides by any power-of-two mesh up to 8 and chunk 256 holds
    whole blocks, so the sharded path engages whenever devices allow.
    """
    edges = _graph()
    cfg = _cfg(edges)
    res_plain = biggraphvis(edges, N, cfg, stream=StreamConfig(chunk_size=256))
    mesh = make_stream_mesh()
    runner = StreamRunner(cfg, StreamRunnerConfig(
        stream=StreamConfig(chunk_size=256, shard_detect=True,
                            shard_layout=True),
        shard_chunks=True,
    ), mesh=mesh)
    res_shard = runner.run(edges, N)
    _assert_same(res_plain, res_shard)
    assert res_shard.stream.devices == mesh.size
    assert res_shard.stream.peak_local_bytes <= res_shard.stream.peak_device_bytes


def test_sharded_pipeline_lexsort_backend():
    edges = _graph()
    cfg = _cfg(edges)
    scfg = StreamConfig(chunk_size=256, agg_backend="lexsort")
    res_plain = biggraphvis(edges, N, cfg, stream=scfg)
    res_shard = biggraphvis(
        edges, N, cfg,
        stream=replace(scfg, mesh=make_stream_mesh(), shard_detect=True),
    )
    _assert_same(res_plain, res_shard)


def test_divisibility_fallback_is_silent_and_identical():
    """Extents that can't split across devices → unsharded path, same
    result, and StreamStats reports the fallback (devices == 1). 81 is odd,
    so both the detect (block) and supergraph (chunk) gates trip on any
    multi-device mesh (device counts are powers of two here)."""
    edges = _graph()
    cfg = _cfg(edges, block=81)
    scfg = StreamConfig(chunk_size=81)
    res_plain = biggraphvis(edges, N, cfg, stream=scfg)
    res_shard = biggraphvis(
        edges, N, cfg,
        stream=replace(scfg, mesh=make_stream_mesh(), shard_detect=True),
    )
    _assert_same(res_plain, res_shard)
    if 81 % jax.device_count() != 0:
        assert res_shard.stream.devices == 1


@pytest.mark.parametrize("repulsion", ["exact", "grid"])
def test_layout_sharded_matches_layout(repulsion):
    edges = jnp.asarray(_graph()[:512])
    w = jnp.ones(edges.shape[0], jnp.float32)
    mass = jnp.zeros(N, jnp.float32).at[edges[:, 0]].add(1.0) + 1.0
    cfg = fa2.FA2Config(iterations=4, repulsion=repulsion, grid_size=8,
                        grid_window=8)
    pos, trace, it = fa2.layout(edges, w, mass, N, cfg)
    mesh = make_stream_mesh()
    pos_s, trace_s, it_s = fa2.layout_sharded(edges, w, mass, N, cfg, mesh)
    assert np.array_equal(np.asarray(pos), np.asarray(pos_s))
    assert np.array_equal(np.asarray(trace), np.asarray(trace_s))
    assert int(it) == int(it_s) == cfg.iterations


def test_layout_sharded_fallbacks():
    """Non-divisible n and no-sharded-form backends fall back to layout,
    warning once (regression: the fallback used to be silent, so a
    configured mesh could quietly never engage)."""
    import warnings

    n = 99  # prime-ish: only divides a 1/3/9/11/33/99-device mesh
    edges = jnp.asarray([[0, 1], [1, 2], [2, 3]], jnp.int32)
    w = jnp.ones(3, jnp.float32)
    mass = jnp.ones(n, jnp.float32)
    cfg = fa2.FA2Config(iterations=2, repulsion="exact")
    pos, _, _ = fa2.layout(edges, w, mass, n, cfg)
    fa2._FALLBACK_WARNED.clear()
    with pytest.warns(UserWarning, match="falling back to single-device"):
        pos_s, _, _ = fa2.layout_sharded(
            edges, w, mass, n, cfg, make_stream_mesh())
    assert np.array_equal(np.asarray(pos), np.asarray(pos_s))
    # Warn-once: the same reason does not warn again.
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        fa2.layout_sharded(edges, w, mass, n, cfg, make_stream_mesh())
    # mesh=None is the caller opting out — silent, no warning.
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        pos_n, _, _ = fa2.layout_sharded(edges, w, mass, n, cfg, None)
    assert np.array_equal(np.asarray(pos), np.asarray(pos_n))


def test_layout_sharded_nonfloat32_grid_falls_back():
    """Regression: the sharded grid path computed in hardcoded float32
    whatever cfg.dtype asked for. It now refuses (warn + fall back to the
    single-device path, which keeps its cast-in/cast-out semantics) rather
    than silently produce a layout in the wrong precision."""
    edges = jnp.asarray(_graph()[:256])
    w = jnp.ones(edges.shape[0], jnp.float32)
    mass = jnp.zeros(N, jnp.float32).at[edges[:, 0]].add(1.0) + 1.0
    cfg = fa2.FA2Config(iterations=3, repulsion="grid", grid_size=8,
                        grid_window=8, dtype="bfloat16")
    mesh = make_stream_mesh()
    if mesh.size > 1:
        reason = fa2._sharded_fallback_reason(N, cfg, mesh)
        assert reason is not None and "float32" in reason
    fa2._FALLBACK_WARNED.clear()
    pos, trace, it = fa2.layout(edges, w, mass, N, cfg)
    with pytest.warns(UserWarning):
        pos_s, trace_s, it_s = fa2.layout_sharded(edges, w, mass, N, cfg, mesh)
    assert pos_s.dtype == jnp.bfloat16
    assert np.array_equal(
        np.asarray(pos, np.float32), np.asarray(pos_s, np.float32))
    # float32 grid keeps its sharded form (no reason to refuse).
    f32 = replace(cfg, dtype="float32")
    if mesh.size > 1:
        assert fa2._sharded_fallback_reason(N, f32, mesh) is None


def test_repulsion_chunked_rows_bitwise():
    """Row slices of the chunked j-scan are bitwise equal to the full run
    (the sharded layout's correctness rests on this; chunk 64 forces
    multiple j-chunks and a padded tail)."""
    rng = np.random.default_rng(0)
    n = 200
    pos = jnp.asarray(rng.normal(size=(n, 2)), jnp.float32)
    mass = jnp.asarray(rng.uniform(0.5, 2.0, size=n), jnp.float32)
    radii = jnp.asarray(rng.uniform(0.1, 1.0, size=n), jnp.float32)
    full = rep_ops.repulsion_chunked(pos, mass, 9.0, radii=radii, chunk=64)
    for i0, nl in ((0, 50), (50, 50), (150, 50), (64, 8)):
        part = rep_ops.repulsion_chunked_rows(
            pos, mass, i0, nl, 9.0, radii=radii, chunk=64)
        assert np.array_equal(np.asarray(full[i0:i0 + nl]), np.asarray(part))


@pytest.mark.parametrize("backend,n,rows", [
    ("ref", 256, ((0, 64), (192, 64))),
    ("chunked", 2304, ((0, 576), (1728, 576))),
    ("interpret", 512, ((0, 128), (384, 128), (128, 16))),
])
def test_repulsion_rows_bitwise(backend, n, rows):
    """``repulsion_rows`` (what each device of the sharded layout calls)
    is bitwise the matching rows of ``repulsion`` on the same backend —
    including the Pallas kernel's target-row form, here interpreted."""
    rng = np.random.default_rng(n)
    pos = jnp.asarray(rng.uniform(-50, 50, size=(n, 2)), jnp.float32)
    mass = jnp.asarray(rng.uniform(1.0, 9.0, size=n), jnp.float32)
    radii = jnp.sqrt(mass)
    full = rep_ops.repulsion(pos, mass, 80.0, radii=radii, backend=backend,
                             tile=128)
    for i0, nl in rows:
        part = rep_ops.repulsion_rows(pos, mass, jnp.int32(i0), nl, 80.0,
                                      radii=radii, backend=backend, tile=128)
        assert np.array_equal(np.asarray(full[i0:i0 + nl]), np.asarray(part))


def test_near_field_rows_bitwise():
    """Halo near field on row blocks == slicing the full banded near field."""
    rng = np.random.default_rng(1)
    n = 160
    pos = jnp.asarray(rng.uniform(-10, 10, size=(n, 2)), jnp.float32)
    mass = jnp.asarray(rng.uniform(0.5, 2.0, size=n), jnp.float32)
    cell, order = bin_and_sort(pos, 4)
    pos_s, mass_s, cell_s = pos[order], mass[order], cell[order]
    full = near_field_ref(pos_s, mass_s, cell_s, 7.0, 16)
    for i0, nl in ((0, 40), (40, 40), (120, 40), (8, 16)):
        part = near_field_rows(pos_s, mass_s, cell_s, 7.0, 16, i0, nl)
        assert np.array_equal(np.asarray(full[i0:i0 + nl]), np.asarray(part))


@multi_device
def test_runner_put_pads_non_divisible_chunks():
    """Regression: a chunk whose rows don't divide by the device count used
    to crash the sharded ``device_put``; it must now pad with the trash
    sentinel (after ``run`` set it) and still row-shard."""
    edges = _graph()
    cfg = _cfg(edges)
    mesh = make_stream_mesh()
    runner = StreamRunner(
        cfg, StreamRunnerConfig(shard_chunks=True), mesh=mesh)

    # Before any run there is no sentinel: fall back to replication.
    odd = np.asarray(edges[: mesh.size + 1], np.int32)
    arr = runner.put(odd)
    assert arr.shape == odd.shape
    assert np.array_equal(np.asarray(arr), odd)

    runner._trash = N  # what run() sets before streaming
    arr = runner.put(odd)
    assert arr.shape[0] % mesh.size == 0
    got = np.asarray(arr)
    assert np.array_equal(got[: len(odd)], odd)
    assert (got[len(odd):] == N).all()  # padding is all trash rows
    shard_rows = {s.data.shape[0] for s in arr.addressable_shards}
    assert shard_rows == {arr.shape[0] // mesh.size}  # evenly row-sharded

    # End to end: a chunk size indivisible by any multi-device count streams
    # through the padding path and yields a valid result. (Chunks-only
    # sharding is a placement mode: the auto-partitioned detect scatter may
    # break ties differently than one device, so unlike shard_detect it
    # does not promise bit-identity — see StreamRunner's docstring.)
    runner = StreamRunner(cfg, StreamRunnerConfig(
        stream=StreamConfig(chunk_size=255), shard_chunks=True), mesh=mesh)
    res = runner.run(edges, N)
    labels = np.asarray(res.labels)
    assert labels.shape == (N,) and (labels >= 0).all()
    assert res.n_supernodes > 0
    assert np.isfinite(res.modularity)


def test_multi_device_subprocess_bit_identity():
    """Force 4 host devices in a subprocess and check the sharded pipeline
    reproduces this process's single-device result bit for bit — real
    multi-device coverage even when the parent test run has one device."""
    edges = _graph()
    cfg = _cfg(edges)
    res = biggraphvis(edges, N, cfg, stream=StreamConfig(chunk_size=256))
    script = textwrap.dedent("""
        import json, sys
        import numpy as np
        from dataclasses import replace
        import jax
        from repro.core.pipeline import default_config
        from repro.core.stream import StreamConfig
        from repro.graph import mode_degree, planted_partition
        from repro.launch.mesh import make_stream_mesh
        from repro.launch.stream_runner import StreamRunner, StreamRunnerConfig

        assert jax.device_count() == 4, jax.device_count()
        N, COMMUNITIES = {n}, {communities}
        edges, _ = planted_partition(N, COMMUNITIES, 0.3, 0.002, seed=11)
        cfg = default_config(N, len(edges), mode_degree(edges, N),
                             rounds=2, iterations=5)
        cfg = replace(cfg, scoda=replace(cfg.scoda, block_size=128))
        runner = StreamRunner(cfg, StreamRunnerConfig(
            stream=StreamConfig(chunk_size=256, shard_detect=True,
                                shard_layout=True),
            shard_chunks=True,
        ), mesh=make_stream_mesh())
        res = runner.run(edges, N)
        assert res.stream.devices == 4, res.stream.devices
        json.dump({{
            "labels": np.asarray(res.labels).tolist(),
            "sg_edges": np.asarray(res.supergraph.edges).tolist(),
            "positions_bytes": np.asarray(res.positions).tobytes().hex(),
            "modularity": res.modularity,
        }}, sys.stdout)
    """).format(n=N, communities=COMMUNITIES)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = str(Path(__file__).resolve().parents[1] / "src")
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr
    got = __import__("json").loads(out.stdout)
    assert got["labels"] == np.asarray(res.labels).tolist()
    assert got["sg_edges"] == np.asarray(res.supergraph.edges).tolist()
    assert got["modularity"] == res.modularity
    assert got["positions_bytes"] == np.asarray(res.positions).tobytes().hex()


_STAGES_SCRIPT = """
import json, sys, tempfile
from dataclasses import replace
import numpy as np
import jax
from repro.core.pipeline import biggraphvis, default_config
from repro.core.stream import StreamConfig
from repro.data.edge_store import write_npy
from repro.graph import mode_degree, planted_partition
from repro.launch.mesh import make_stream_mesh
from repro.launch.stream_runner import StreamRunner, StreamRunnerConfig
from repro.obs import Tracer
from repro.obs.metrics import REGISTRY

assert jax.device_count() == 4, jax.device_count()
N, COMMUNITIES, SUPER_EDGES, REPULSION = {n}, {communities}, {super_edges}, {repulsion!r}
edges, _ = planted_partition(N, COMMUNITIES, 0.3, 0.002, seed=11)
path = write_npy(tempfile.mkdtemp() + "/edges.npy", edges)
cfg = default_config(N, len(edges), mode_degree(edges, N), rounds=2,
                     iterations=5)
cfg = replace(cfg, scoda=replace(cfg.scoda, block_size=128),
              layout=replace(cfg.layout, repulsion=REPULSION),
              max_super_edges=SUPER_EDGES)
plain = biggraphvis(path, N, cfg, stream=StreamConfig(chunk_size=256))
tr = Tracer()
runner = StreamRunner(replace(cfg, obs=tr), StreamRunnerConfig(
    stream=StreamConfig(chunk_size=256, shard_detect=True, shard_layout=True),
    shard_chunks=True,
), mesh=make_stream_mesh(4))
res = runner.run(path, N)
(span,) = [s for s in tr.spans() if s.name == "layout.supergraph"]
stages = ("detect", "supergraph", "layout")
json.dump({{
    "same": {{
        "labels": bool(np.array_equal(plain.labels, res.labels)),
        "superedges": bool(
            np.array_equal(np.asarray(plain.supergraph.edges),
                           np.asarray(res.supergraph.edges))
            and np.array_equal(np.asarray(plain.supergraph.weights),
                               np.asarray(res.supergraph.weights))
            and plain.n_superedges == res.n_superedges),
        "sizes": bool(np.array_equal(plain.sizes, res.sizes)),
        "modularity": plain.modularity == res.modularity,
        "positions": bool(np.array_equal(plain.positions, res.positions)),
    }},
    "n_edges": len(edges),
    "n_superedges": int(res.n_superedges),
    "stream_devices": res.stream.devices,
    "stage_devices": res.stream.stage_devices,
    "gauge_devices": {{s: REGISTRY.value("shard.devices." + s) for s in stages}},
    "gauge_bytes": {{s: REGISTRY.value("shard.collective_bytes." + s)
                    for s in stages}},
    "span": span.attrs,
}}, sys.stdout)
"""


@pytest.mark.parametrize("repulsion,layout_devices", [("exact", 4),
                                                      ("grid_pallas", 1)])
def test_runner_npy_records_stage_devices_subprocess(repulsion, layout_devices):
    """``StreamRunner`` over a ``.npy`` file with every shard flag on 4 forced
    host devices, the superedge state (64 slots) filled by a chunk's
    gathered dedupe: bit-identical to the unsharded run of the same file,
    and each stage's devices and collective bytes recorded in the
    ``shard.*`` gauges and ``StreamStats``. ``grid_pallas`` has no sharded
    layout, so that stage and ``StreamStats.devices`` read 1."""
    super_edges = 64
    script = textwrap.dedent(_STAGES_SCRIPT).format(
        n=N, communities=COMMUNITIES, super_edges=super_edges,
        repulsion=repulsion)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = str(Path(__file__).resolve().parents[1] / "src")
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr
    got = __import__("json").loads(out.stdout)
    assert all(got["same"].values()), got["same"]
    assert got["n_superedges"] >= super_edges  # the state is full
    want = {"detect": 4, "supergraph": 4, "layout": layout_devices}
    assert got["stage_devices"] == want
    assert got["gauge_devices"] == want
    assert got["stream_devices"] == layout_devices
    assert got["span"]["sharded"] is (layout_devices > 1)
    assert got["span"]["devices"] == layout_devices
    # Bytes from shapes: 2 rounds over 24 chunks of 2 blocks of 128 edges.
    n_state, chunks = (N + 1) * 4, -(-got["n_edges"] // 256)
    per_block = 3 * n_state + 128 // 4 * 2 * 4  # pmax, pmin, psum; the gather
    assert got["gauge_bytes"]["detect"] == (2 * chunks * 2 * per_block
                                            + chunks * n_state)
    sketch, modularity = 4 * 256 * 4, (1 + 2 * (N + 1)) * 4
    assert got["gauge_bytes"]["supergraph"] == sketch + chunks * (
        3 * 256 // 4 * 4 + modularity)
    layout = 5 * 64 // 4 * 2 * 4 if layout_devices > 1 else 0  # s_layout 64
    assert got["gauge_bytes"]["layout"] == layout
