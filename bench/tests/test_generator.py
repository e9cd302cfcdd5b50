"""The benchmark's generator is deterministic in its seed, keeps the
planted-partition distribution, and at each configuration's full size
stays under the supernode and superedge capacities on a dozen seeds, in
the same power-of-two layout shapes (so no seed compiles new programs)."""
import numpy as np
import pytest

from bench import graphgen, harness
from bench.reference import fa2, scoda, supergraph

SEEDS = [1, 7, 42, 1000, 65_537, 123_456_789, 2**31 - 1, 2**31 + 11,
         3_000_000_019, 4_000_000_007, 4_294_967_291, 5_000_000_000]


def test_same_seed_same_graph_other_seed_other_graph():
    a = graphgen.planted_partition(5000, 10, 0.02, 1e-4, seed=2**31 + 5)
    b = graphgen.planted_partition(5000, 10, 0.02, 1e-4, seed=2**31 + 5)
    c = graphgen.planted_partition(5000, 10, 0.02, 1e-4, seed=2**31 + 6)
    assert np.array_equal(a, b)
    assert not np.array_equal(a[: min(len(a), len(c))], c[: min(len(a), len(c))])


def test_distribution_matches_the_planted_partition():
    n, blocks, p_in, p_out = 4000, 8, 0.02, 1e-3
    e = graphgen.planted_partition(n, blocks, p_in, p_out, seed=3)
    assert e.dtype == np.int32 and np.all(e[:, 0] < e[:, 1])
    assert len(np.unique(e[:, 0].astype(np.int64) * n + e[:, 1])) == len(e)
    block = np.repeat(np.arange(blocks), n // blocks)
    intra = block[e[:, 0]] == block[e[:, 1]]
    m = n // blocks
    want_in = blocks * m * (m - 1) / 2 * p_in
    want_out = (n * (n - 1) / 2 - blocks * m * (m - 1) / 2) * p_out
    assert abs(intra.sum() - want_in) < 5 * np.sqrt(want_in)
    assert abs((~intra).sum() - want_out) < 5 * np.sqrt(want_out)


@pytest.mark.parametrize("name", ["berkstan", "webgoogle"])
def test_full_size_fits_capacity_in_stable_shapes(name):
    cfg = harness.config(name)
    g = cfg["generator"]
    shapes = set()
    for seed in SEEDS:
        edges = graphgen.planted_partition(cfg["nodes"], g["blocks"], g["p_in"],
                                           g["p_out"], seed)
        assert abs(len(edges) - cfg["edges"]) / cfg["edges"] < 0.02
        labels = scoda.detect(edges, cfg["nodes"],
                              cfg["scoda"]["degree_threshold"],
                              cfg["scoda"]["rounds"], cfg["scoda"]["block_size"])
        dense, n_super = supergraph.dense(labels)
        n_superedges = len(supergraph.superedges(edges, dense)[1])
        assert n_super <= cfg["s_cap"], (seed, n_super)
        assert n_superedges <= cfg["max_super_edges"], (seed, n_superedges)
        shapes.add(fa2.layout_slots(n_super, n_superedges, cfg["s_cap"],
                                    cfg["max_super_edges"]))
    assert len(shapes) == 1, shapes
