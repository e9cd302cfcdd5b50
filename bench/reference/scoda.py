"""Plain NumPy reference of block-parallel SCoDA community detection.

Semantics (the configuration's ``scoda`` group): the edge stream is cut
into blocks of ``block_size`` edges; within a block every edge sees the
block-start communities and the degrees ``deg + 1 + (earlier slots of
the block naming the same node)``; an edge whose two degrees are both at
most the round's threshold makes the endpoint of lower degree adopt the
other endpoint's community (equal degrees: nothing); among several
donors for one node the highest donor degree wins, then the smallest
community id; afterwards both endpoint degrees of every valid edge grow
by one. Rounds re-stream the list with threshold ``δ^(r+1)``, capped at
2^30. Padded slots name the trash node ``n`` and do nothing.
"""
from __future__ import annotations

import numpy as np


def round_threshold(delta: int, r: int) -> int:
    return int(min(float(delta) ** (r + 1), 2**30))


def _prior_occurrences(flat: np.ndarray) -> np.ndarray:
    """For each slot, how many earlier slots hold the same value."""
    n = len(flat)
    shift = max(1, (n - 1).bit_length())
    key = np.sort((flat.astype(np.int64) << shift) | np.arange(n))
    value, slot = key >> shift, key & ((1 << shift) - 1)
    pos = np.arange(n)
    start = np.where(np.concatenate([[True], value[1:] != value[:-1]]), pos, 0)
    rank = np.empty(n, np.int64)
    rank[slot] = pos - np.maximum.accumulate(start)
    return rank


def _block(com, deg, u, v, threshold, trash):
    """One block, in place: only the block's nodes are read or written."""
    valid = (u != trash) & (v != trash) & (u != v)
    rank = _prior_occurrences(np.stack([u, v], 1).reshape(-1))
    rank = np.where(np.repeat(valid, 2), rank, 0)
    du = deg[u] + 1 + rank[0::2]
    dv = deg[v] + 1 + rank[1::2]
    elig = valid & (du <= threshold) & (dv <= threshold)
    adopt_v = elig & (du > dv)
    adopting = adopt_v | (elig & (dv > du))
    adoptee = np.where(adopt_v, v, u)[adopting]
    donor_com = com[np.where(adopt_v, u, v)[adopting]]
    donor_deg = np.where(adopt_v, du, dv)[adopting]
    # Per adoptee: highest donor degree, then smallest community id.
    order = np.lexsort((donor_com, -donor_deg, adoptee))
    a = adoptee[order]
    first = np.concatenate([[True], a[1:] != a[:-1]]) if len(a) else a.astype(bool)
    com[a[first]] = donor_com[order][first]
    np.add.at(deg, u[valid], 1)
    np.add.at(deg, v[valid], 1)


def detect(edges: np.ndarray, n: int, delta: int, rounds: int,
           block_size: int) -> np.ndarray:
    """Labels [n] (community = a representative node id)."""
    trash = n
    e = len(edges)
    pad = (-e) % block_size
    u_all = np.concatenate([edges[:, 0], np.full(pad, trash)]).astype(np.int64)
    v_all = np.concatenate([edges[:, 1], np.full(pad, trash)]).astype(np.int64)
    com = np.arange(n + 1, dtype=np.int64)
    deg = np.zeros(n + 1, np.int64)
    for r in range(rounds):
        thr = round_threshold(delta, r)
        for s in range(0, len(u_all), block_size):
            _block(com, deg, u_all[s:s + block_size],
                   v_all[s:s + block_size], thr, trash)
    return com[:n]
