"""Plain NumPy reference of the supergraph stage.

* dense community ids: the sorted distinct labels, numbered from 0;
* community sizes: a count-min sketch (``rows`` × ``cols``) of the sum of
  graph degrees per community, hashed by multiply-shift in 32-bit
  unsigned arithmetic, ``((a·k + b) mod 2^32) >> 5 mod cols`` with odd
  ``a`` and ``b`` drawn from ``numpy.random.default_rng(seed)`` as the
  configuration's ``cms`` group states; the estimate is the row minimum,
  and slots past the live communities hold 0;
* superedges: every edge between two communities, as the sorted distinct
  pairs ``(a < b)`` with their multiplicities;
* modularity: Σ_c e_c/m − (d_c/2m)², in float64;
* colour groups: the smallest communities holding half the total size
  share group 0, the rest split by rank into 10 groups of equal count.
"""
from __future__ import annotations

import numpy as np


def dense(labels: np.ndarray) -> tuple[np.ndarray, int]:
    uniq, inv = np.unique(labels, return_inverse=True)
    return inv.astype(np.int64), len(uniq)


def cms_sizes(dense_labels, degree, n_super, s_cap, rows, cols, seed):
    rng = np.random.default_rng(seed)
    a = rng.integers(1, 2**31, size=rows, dtype=np.uint32) * np.uint32(2) + np.uint32(1)
    b = rng.integers(0, 2**31, size=rows, dtype=np.uint32)

    def buckets(keys):
        k = keys.astype(np.uint64)
        h = ((a.astype(np.uint64)[:, None] * k[None, :] + b[:, None]) % 2**32) >> 5
        return (h % cols).astype(np.int64)

    h = buckets(dense_labels)
    sketch = np.zeros((rows, cols), np.int64)
    for r in range(rows):
        sketch[r] = np.bincount(h[r], weights=degree, minlength=cols)
    q = buckets(np.arange(s_cap))
    est = sketch[np.arange(rows)[:, None], q].min(axis=0)
    return np.where(np.arange(s_cap) < n_super, est, 0)


def superedges(edges, dense_labels):
    a = dense_labels[edges[:, 0]]
    b = dense_labels[edges[:, 1]]
    lo, hi = np.minimum(a, b), np.maximum(a, b)
    keep = lo != hi
    key = lo[keep] * (1 << 32) + hi[keep]
    uniq, counts = np.unique(key, return_counts=True)
    return np.stack([uniq >> 32, uniq & 0xFFFFFFFF], 1), counts


def modularity(edges, dense_labels, n_super, dtype=np.float64) -> float:
    """Computed in ``dtype`` (float64; the control passes bfloat16)."""
    a = dense_labels[edges[:, 0]]
    b = dense_labels[edges[:, 1]]
    m = np.asarray(len(edges), dtype)
    intra = np.bincount(a[a == b], minlength=n_super).astype(dtype)
    dcom = (np.bincount(a, minlength=n_super)
            + np.bincount(b, minlength=n_super)).astype(dtype)
    two = np.asarray(2, dtype)
    return float(np.sum(intra / m - (dcom / (two * m)) ** 2, dtype=dtype))


def colour_groups(sizes: np.ndarray, dtype=np.int64) -> np.ndarray:
    """Exact in int64; the control passes bfloat16 for the running sum."""
    s = len(sizes)
    order = np.argsort(sizes, kind="stable")
    sized = sizes.astype(dtype)
    csum = np.cumsum(sized[order], dtype=dtype)
    bulk = csum <= np.sum(sized, dtype=dtype) * 0.5
    n_bulk = int(bulk.sum())
    rank = np.arange(s)
    rest = 1 + ((rank - n_bulk) * 10) // max(s - n_bulk, 1)
    g_sorted = np.where(bulk, 0, np.clip(rest, 1, 10))
    groups = np.empty(s, np.int64)
    groups[order] = g_sorted
    return np.where(sizes > 0, groups, 0)


def build(edges, labels, n, cfg, modularity_dtype=np.float64,
          sum_dtype=np.int64):
    """Everything the supergraph stage promises, from the reference labels.
    ``modularity_dtype`` and ``sum_dtype`` (the colour groups' running sum)
    are lowered by the control."""
    dl, n_super = dense(labels)
    degree = np.bincount(edges.reshape(-1), minlength=n)[:n]
    sizes = cms_sizes(dl, degree, n_super, cfg["s_cap"], cfg["cms_rows"],
                      cfg["cms_cols"], cfg["cms_seed"])
    pairs, counts = superedges(edges, dl)
    return {
        "labels": dl,
        "n_supernodes": n_super,
        "sizes": sizes,
        "pairs": pairs,
        "weights": counts,
        "n_superedges": len(counts),
        "modularity": modularity(edges, dl, n_super, modularity_dtype),
        "groups": colour_groups(sizes, sum_dtype),
    }
