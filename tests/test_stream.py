"""Streaming chunked-edge engine: chunked init/update/finalize over K chunks
must match the one-shot SCoDA/CMS/supergraph results bit-for-bit, including
with chunk size ≪ |E| (multi-pass streaming)."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import biggraphvis, default_config
from repro.core.modularity import (
    modularity,
    modularity_finalize,
    modularity_init,
    modularity_update,
)
from repro.core.scoda import ScodaConfig, detect_communities, dense_labels
from repro.core.stream import (
    EdgeChunkStream,
    StreamConfig,
    oneshot_device_bytes,
    stream_detect,
    stream_pipeline,
    stream_supergraph,
)
from repro.core.supergraph import (
    agg_finalize,
    agg_init,
    agg_update,
    aggregate_edges,
    build_supergraph,
)
from repro.graph import mode_degree, pad_edges, planted_partition
from repro.graph.utils import degrees


@pytest.fixture(scope="module")
def graph():
    edges, _ = planted_partition(300, 6, 0.25, 0.005, seed=7)
    return edges, 300


def _scoda_cfg(edges, n, block_size=64, rounds=4):
    dt = max(2, mode_degree(edges, n))
    return ScodaConfig(degree_threshold=dt, rounds=rounds, block_size=block_size)


# ------------------------------------------------------- EdgeChunkStream unit


def test_disk_staging_spans_cover_the_copy_stall(graph, tmp_path):
    """On the disk path each chunk's store read, transfer and staging wait
    are spans of the tracer handed in; the ``stream.stall`` spans bracket
    exactly the waits ``copy_stall_s`` adds up."""
    from repro.core.stream import StreamStats
    from repro.obs.trace import Tracer

    edges, n = graph
    path = tmp_path / "edges.npy"
    np.save(path, edges)
    st = EdgeChunkStream(str(path), n, 128)
    assert st.n_chunks > st.staging_buffers(1)  # the ring wraps
    tr, stats = Tracer(), StreamStats()
    got = [np.asarray(c) for c in st.device_chunks(stats=stats, tracer=tr)]
    np.testing.assert_array_equal(np.concatenate(got)[: len(edges)], edges)
    by_name = {}
    for sp in tr.spans():
        by_name.setdefault(sp.name, []).append(sp)
    assert len(by_name["stream.fill"]) == st.n_chunks
    assert len(by_name["stream.put"]) == st.n_chunks
    stalls = by_name["stream.stall"]
    assert len(stalls) == st.n_chunks - st.staging_buffers(1)
    assert sum(sp.duration for sp in stalls) == pytest.approx(
        stats.copy_stall_s, abs=1e-3)


def test_chunk_stream_shapes_and_padding(graph):
    edges, n = graph
    st = EdgeChunkStream(edges, n, 100, block_size=64)
    assert st.chunk_size == 128  # rounded up to a block_size multiple
    chunks = list(st)
    assert len(chunks) == st.n_chunks == -(-len(edges) // 128)
    flat = np.concatenate(chunks)
    assert flat.shape == (st.n_chunks * 128, 2)
    np.testing.assert_array_equal(flat[: len(edges)], edges)
    assert (flat[len(edges):] == n).all()  # tail padded with the trash node


def test_chunk_stream_counts_passes(graph):
    edges, n = graph
    st = EdgeChunkStream(edges, n, 128)
    assert st.passes == 0
    list(st)
    list(st)
    assert st.passes == 2


def test_chunk_stream_single_chunk_covers_all(graph):
    edges, n = graph
    st = EdgeChunkStream(edges, n, 10 * len(edges))
    (chunk,) = list(st)
    np.testing.assert_array_equal(chunk[: len(edges)], edges)


# --------------------------------------------------- stage-level equivalence


def test_chunked_scoda_matches_oneshot(graph):
    """Chunked update over K chunks == one-shot, bit-for-bit (labels + deg)."""
    edges, n = graph
    cfg = _scoda_cfg(edges, n)
    ej = jnp.asarray(pad_edges(edges, len(edges), n))
    lab1, deg1 = detect_communities(ej, n, cfg)
    st = EdgeChunkStream(edges, n, 128, block_size=cfg.block_size)
    assert st.n_chunks >= 4  # a real multi-chunk stream, chunk < |E|/4
    lab2, deg2, gdeg = stream_detect(st, n, cfg)
    np.testing.assert_array_equal(np.asarray(lab1), np.asarray(lab2))
    np.testing.assert_array_equal(np.asarray(deg1), np.asarray(deg2))
    np.testing.assert_array_equal(
        np.asarray(degrees(ej, n)), np.asarray(gdeg)
    )


def test_chunked_agg_matches_oneshot(graph):
    """Superedge aggregation: merging K chunks == one-shot lexsort-dedupe."""
    edges, n = graph
    rng = np.random.default_rng(3)
    labels = jnp.asarray(rng.integers(0, 40, n).astype(np.int32))
    labels_dense, _ = dense_labels(labels, n)
    # capacity must hold every unique pair (≤ 40·39/2): overflow truncation
    # is lossy and chunk-order-dependent, so equality only holds below it.
    s_cap, cap = 64, 1024
    ej = jnp.asarray(pad_edges(edges, len(edges), n))
    se1, sw1, n1 = aggregate_edges(ej, labels_dense, s_cap, cap)

    labels_ext = jnp.concatenate([labels_dense, jnp.array([s_cap], jnp.int32)])
    state = agg_init(s_cap, cap)
    for chunk in EdgeChunkStream(edges, n, 97):  # deliberately odd chunk size
        state = agg_update(state, jnp.asarray(chunk), labels_ext, s_cap, cap)
    se2, sw2, n2 = agg_finalize(state)
    assert int(n1) == int(n2)
    np.testing.assert_array_equal(np.asarray(se1), np.asarray(se2))
    np.testing.assert_array_equal(np.asarray(sw1), np.asarray(sw2))


def test_stream_agg_backends_identical_and_timed(graph):
    """Engine-level: merge vs lexsort aggregation produce the same
    supergraph through stream_pipeline, and the supergraph stage is timed
    in StreamStats (the device time of each aggregation update is the
    profiler's ``jit__agg_update_body``)."""
    edges, n = graph
    cfg = _scoda_cfg(edges, n, rounds=2)
    from repro.core.cms import CMSConfig

    out = {}
    for backend in ("lexsort", "merge"):
        labels, gdeg, sg, q, stats = stream_pipeline(
            edges, n, cfg, CMSConfig(rows=4, cols=256), 512, 2048,
            StreamConfig(chunk_size=128, agg_backend=backend),
        )
        out[backend] = sg
        assert stats.stage_seconds["supergraph_s"] > 0.0
    for field in ("edges", "weights", "sizes", "labels"):
        np.testing.assert_array_equal(
            np.asarray(getattr(out["lexsort"], field)),
            np.asarray(getattr(out["merge"], field)),
        )
    assert int(out["lexsort"].n_superedges) == int(out["merge"].n_superedges)


def test_chunked_modularity_matches_oneshot(graph):
    edges, n = graph
    rng = np.random.default_rng(4)
    labels = jnp.asarray(rng.integers(0, 30, n).astype(np.int32))
    ej = jnp.asarray(pad_edges(edges, len(edges), n))
    q1 = modularity(ej, labels, n)
    labels_ext = jnp.concatenate([labels, jnp.array([-1], jnp.int32)])
    state = modularity_init(n)
    for chunk in EdgeChunkStream(edges, n, 64):
        state = modularity_update(state, jnp.asarray(chunk), labels_ext)
    q2 = modularity_finalize(state)
    assert float(q1) == float(q2)


def test_stream_supergraph_matches_build_supergraph(graph):
    edges, n = graph
    cfg = _scoda_cfg(edges, n)
    ej = jnp.asarray(pad_edges(edges, len(edges), n))
    labels, _ = detect_communities(ej, n, cfg)
    deg = degrees(ej, n)
    s_cap, cap = 512, 2048
    from repro.core.cms import CMSConfig

    cms_cfg = CMSConfig(rows=4, cols=256)
    sg1 = build_supergraph(ej, labels, deg, n, s_cap, cap, cms_cfg)
    st = EdgeChunkStream(edges, n, 128, block_size=cfg.block_size)
    sg2, q = stream_supergraph(st, labels, deg, n, s_cap, cap, cms_cfg)
    np.testing.assert_array_equal(np.asarray(sg1.edges), np.asarray(sg2.edges))
    np.testing.assert_array_equal(np.asarray(sg1.weights), np.asarray(sg2.weights))
    np.testing.assert_array_equal(np.asarray(sg1.sizes), np.asarray(sg2.sizes))
    np.testing.assert_array_equal(np.asarray(sg1.labels), np.asarray(sg2.labels))
    assert int(sg1.n_supernodes) == int(sg2.n_supernodes)
    assert int(sg1.n_superedges) == int(sg2.n_superedges)
    assert np.isfinite(float(q))


# ------------------------------------------------- pipeline-level equivalence


def test_stream_pipeline_matches_oneshot(graph):
    """Full driver: streamed (chunk < |E|/4) == one-shot, bit-for-bit."""
    edges, n = graph
    from dataclasses import replace

    cfg = default_config(n, len(edges), max(2, mode_degree(edges, n)),
                         rounds=4, iterations=20, s_cap=512)
    cfg = replace(cfg, scoda=replace(cfg.scoda, block_size=64))
    assert 128 < len(edges) / 4
    r1 = biggraphvis(edges, n, cfg)
    r2 = biggraphvis(edges, n, cfg, stream=StreamConfig(chunk_size=128))
    np.testing.assert_array_equal(r1.labels, r2.labels)
    np.testing.assert_array_equal(r1.sizes, r2.sizes)
    np.testing.assert_array_equal(r1.groups, r2.groups)
    np.testing.assert_array_equal(
        np.asarray(r1.supergraph.edges), np.asarray(r2.supergraph.edges)
    )
    np.testing.assert_array_equal(
        np.asarray(r1.supergraph.weights), np.asarray(r2.supergraph.weights)
    )
    np.testing.assert_array_equal(r1.positions, r2.positions)
    assert r1.modularity == r2.modularity
    assert r1.n_supernodes == r2.n_supernodes
    assert r1.n_superedges == r2.n_superedges


def test_multi_pass_stats_and_residency(graph):
    """Chunk ≪ |E|: rounds+1 passes over the stream, and the engine's peak
    device residency is below the one-shot full-edge materialization."""
    edges, n = graph
    cfg = _scoda_cfg(edges, n, block_size=64, rounds=3)
    from repro.core.cms import CMSConfig

    labels, gdeg, sg, q, stats = stream_pipeline(
        edges, n, cfg, CMSConfig(rows=4, cols=256), 512, 2048,
        StreamConfig(chunk_size=64),
    )
    st = EdgeChunkStream(edges, n, 64, block_size=64)
    assert stats.passes == cfg.rounds + 1
    assert stats.chunks == (cfg.rounds + 1) * st.n_chunks
    assert stats.edges_streamed == stats.chunks * 64
    assert stats.chunk_size == 64

    _, _, _, _, stats_one = stream_pipeline(
        edges, n, cfg, CMSConfig(rows=4, cols=256), 512, 2048, None,
    )
    assert stats_one.passes == cfg.rounds + 1
    assert stats.peak_device_bytes < stats_one.peak_device_bytes


def test_prefetch_depth_zero_identical(graph):
    edges, n = graph
    cfg = _scoda_cfg(edges, n, rounds=2)
    lab1, _, _ = stream_detect(
        EdgeChunkStream(edges, n, 128, block_size=64), n, cfg, prefetch=0
    )
    lab2, _, _ = stream_detect(
        EdgeChunkStream(edges, n, 128, block_size=64), n, cfg, prefetch=3
    )
    np.testing.assert_array_equal(np.asarray(lab1), np.asarray(lab2))


def test_oneshot_device_bytes_scales_with_edges():
    assert oneshot_device_bytes(10**6, 10**4) > oneshot_device_bytes(10**5, 10**4)


def test_memory_path_host_bytes_and_overlap_stats(graph):
    """In-memory sources pin the edge list on the host and never stage, so
    fill/stall time stays zero and peak_host_bytes covers the array."""
    edges, n = graph
    cfg = _scoda_cfg(edges, n, rounds=2)
    from repro.core.cms import CMSConfig

    _, _, _, _, stats = stream_pipeline(
        edges, n, cfg, CMSConfig(rows=4, cols=256), 512, 2048,
        StreamConfig(chunk_size=128),
    )
    assert stats.peak_host_bytes >= edges.size * 4
    assert stats.host_fill_s == 0.0
    assert stats.copy_stall_s == 0.0


def test_stream_rejects_wrong_dtype_at_construction(graph):
    """A float edge array must fail up front with a clear message, not deep
    inside a kernel (and not silently truncate node ids)."""
    edges, n = graph
    with pytest.raises(ValueError, match="integer dtype"):
        EdgeChunkStream(edges.astype(np.float64), n, 128)
    with pytest.raises(ValueError, match=r"shape \[E, 2\]"):
        EdgeChunkStream(edges.reshape(-1), n, 128)


def test_device_put_copied_survives_buffer_refill():
    """The staging ring refills a host buffer once the array made from it
    is ready; that array must keep the old contents (a bare device_put
    may alias the host buffer on CPU)."""
    from repro.kernels.compat import device_put_copied

    buf = np.empty((128, 2), np.int32)
    for t in range(64):
        buf[:] = t
        dev = device_put_copied(buf)
        dev.block_until_ready()
        buf[:] = -1
        assert (np.asarray(dev) == t).all()
