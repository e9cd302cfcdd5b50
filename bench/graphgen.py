"""The benchmark's own planted-partition generator (host NumPy).

A copy of ``repro.graph.planted_partition``'s distribution kept with the
benchmark, so the inputs cannot move when the program's generator does.
Every pair inside block ``c`` is an edge with probability ``p_in`` and
every pair across two blocks with probability ``p_out``, independently;
edges are listed once, ``u < v`` before the shuffle, which then fixes the
stream order SCoDA sees.

The program's generator loops over all ``blocks²/2`` block pairs in
Python for the inter-block edges. Here they come from one geometric-skip
draw over the whole strict upper triangle at ``p_out``, keeping the pairs
that cross blocks: each cross pair is still drawn independently with
probability ``p_out``, which is the same distribution, at a cost linear
in the number of draws. Deterministic in the seed.
"""
from __future__ import annotations

import numpy as np


def _gnp_indices(rng: np.random.Generator, n_pairs: int, p: float) -> np.ndarray:
    """Sorted indices of the successes among ``n_pairs`` Bernoulli(p) trials,
    by geometric skipping (cost proportional to the successes)."""
    if p <= 0.0 or n_pairs <= 0:
        return np.empty(0, np.int64)
    out = []
    last = -1
    batch = int(n_pairs * p * 1.05) + 64
    while True:
        pos = last + np.cumsum(rng.geometric(p, size=batch))
        keep = pos[pos < n_pairs]
        out.append(keep)
        if len(keep) < len(pos):
            break
        last = int(pos[-1])
        batch = max(64, batch // 8)
    return np.concatenate(out)


def _upper_pair(idx: np.ndarray, n: int) -> tuple[np.ndarray, np.ndarray]:
    """Strict-upper-triangle linear index of an n×n matrix → (i, j), i < j."""
    b = 2 * n - 1
    i = np.floor((b - np.sqrt(b * b - 8.0 * idx.astype(np.float64))) / 2)
    i = i.astype(np.int64)
    for _ in range(4):  # exact integer fix-up of float rounding at row ends
        start = i * (2 * n - i - 1) // 2
        i = i + (idx >= start + (n - i - 1)).astype(np.int64) - (idx < start)
    start = i * (2 * n - i - 1) // 2
    if np.any((idx < start) | (idx >= start + (n - i - 1))):
        raise ArithmeticError("upper-triangle index did not settle")
    return i, idx - start + i + 1


def planted_partition(n: int, blocks: int, p_in: float, p_out: float,
                      seed: int) -> np.ndarray:
    """Planted-partition graph → shuffled ``[E, 2]`` int32 edge list."""
    rng = np.random.default_rng(seed)
    sizes = np.full(blocks, n // blocks, np.int64)
    sizes[: n % blocks] += 1
    starts = np.concatenate([[0], np.cumsum(sizes)])
    block_of = np.repeat(np.arange(blocks), sizes)

    parts = []
    for c in range(blocks):
        m = int(sizes[c])
        idx = _gnp_indices(rng, m * (m - 1) // 2, p_in)
        i, j = _upper_pair(idx, m)
        parts.append(np.stack([i, j], 1) + starts[c])
    idx = _gnp_indices(rng, n * (n - 1) // 2, p_out)
    i, j = _upper_pair(idx, n)
    cross = block_of[i] != block_of[j]
    parts.append(np.stack([i[cross], j[cross]], 1))
    edges = np.concatenate(parts).astype(np.int32)
    return edges[rng.permutation(len(edges))]


def mode_degree(edges: np.ndarray, n: int) -> int:
    """Most common nonzero degree: the paper's SCoDA threshold δ."""
    deg = np.bincount(edges.reshape(-1), minlength=n)
    counts = np.bincount(deg[deg > 0])
    counts[0] = 0
    return int(np.argmax(counts))
