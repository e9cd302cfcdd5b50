"""The roofline work functions count the work at live sizes."""
import numpy as np
import pytest

from bench import harness


def test_merge_runs_and_bytes_match_a_direct_count():
    batch = harness.load_module("drivers", "batch")
    rng = np.random.default_rng(0)
    edges = rng.integers(0, 300, (5000, 2)).astype(np.int32)
    labels = rng.integers(0, 40, 300)
    runs = batch.merge_runs(edges, labels, 1024)
    seen = set()
    for c, (before, distinct, after) in enumerate(runs):
        chunk = edges[c * 1024:(c + 1) * 1024]
        a, b = labels[chunk[:, 0]], labels[chunk[:, 1]]
        pairs = {(min(x, y), max(x, y)) for x, y in zip(a, b) if x != y}
        assert before == len(seen) and distinct == len(pairs)
        seen |= pairs
        assert after == len(seen)
    work = harness.load_module("roofline", "merge_scatter_combine").work
    flops, nbytes = work({"work": {"jobs": 2, "merge_runs": runs}})
    assert nbytes == 2 * 12 * sum(b + d + a for b, d, a in runs)
    assert flops == 2 * sum(b + d - a for b, d, a in runs)


def test_nbody_work_is_pairs_times_iterations():
    work = harness.load_module("roofline", "nbody_repulsion").work
    flops, nbytes = work({"work": {"jobs": 1, "n_supernodes": 1000,
                                   "iterations": 100}})
    assert flops == 18.0 * 1000 * 999 * 100
    assert nbytes == 16.0 * 1000 * 100


def test_roofline_share_uses_the_larger_bound_and_no_kernel_reads_nothing():
    ctx = {"work": {"jobs": 1, "n_supernodes": 1000, "iterations": 100},
           "peaks": {"flops_per_s": 1e12, "bytes_per_s": 1e9},
           "kernel_seconds": {"nbody_repulsion": 3.6}}
    share = harness.roofline_share(ctx, "nbody_repulsion")
    assert share == pytest.approx(100 * 1.7982e-3 / 3.6, rel=1e-3)  # flops bound
    ctx["kernel_seconds"] = {"nbody_repulsion": 0.0}
    assert harness.roofline_share(ctx, "nbody_repulsion") is None
    assert harness.roofline_share({"kernel_seconds": {}}, "nbody_repulsion") is None

