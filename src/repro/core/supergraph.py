"""Supergraph construction (paper §4.1): communities → weighted supernodes,
inter-community edges → weighted superedges.

Static-shape implementation: superedges are deduplicated into a fixed
``max_super_edges`` capacity, kept sorted by canonicalized (min, max)
community pair. Two jittable aggregation backends share the contract
(``agg_backend``): the original ``"lexsort"`` full re-sort, and the
default ``"merge"`` two-level scheme built on ``kernels/merge``.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.core import cms as cms_lib
from repro.core.scoda import dense_labels
from repro.kernels.cms import ops as cms_ops
from repro.kernels.merge import ops as merge_ops
from repro.kernels.merge.ref import SENTINEL, pack_keys, unpack_keys


@dataclass
class Supergraph:
    """Padded supergraph. Padded superedge slots point at ``s_cap`` (trash)."""

    edges: jnp.ndarray  # [max_super_edges, 2] int32, dense community ids
    weights: jnp.ndarray  # [max_super_edges] float32 (edge multiplicity)
    sizes: jnp.ndarray  # [s_cap] float32 supernode weights (CMS estimate)
    n_supernodes: jnp.ndarray  # scalar int32
    n_superedges: jnp.ndarray  # scalar int32
    labels: jnp.ndarray  # [n_nodes] int32 node → dense community id


# --------------------------------------------------------------------------
# Chunk-incremental superedge aggregation (core/stream.py engine).
#
# State is the *partially aggregated* superedge set: three [cap] arrays
# (a, b, w) sorted by (a, b) with padded slots at (s_cap, s_cap, 0), plus
# the live count. Each update maps a chunk of node edges through the
# community labels and combines it into the state through one of two
# backends that keep the sorted-state invariant (``agg_backend``):
#
#   * "merge" (default) — two-level scheme: (1) the persistent state stays
#     sorted by (a, b); (2) the incoming chunk is deduped *locally*, one
#     sort of only the C chunk entries; (3) the deduped run merges into the
#     state by the ``kernels/merge`` sorted-merge-and-combine kernel, which
#     ranks the two already-sorted runs against each other (a banded walk
#     on TPU, binary searches elsewhere) — O(C log C + cap + C) per chunk.
#   * "lexsort" — the original baseline: concatenate state + chunk, one
#     full lexsort, segment-sum back into capacity — O((cap + C)·
#     log(cap + C)) per chunk.
#
# Both are bit-for-bit identical below capacity (weights are edge counts,
# exactly representable, and both keep the same sorted layout), and both
# skip all-invalid chunks (every edge intra-community or trash-padded)
# without touching the state. After the final chunk the state IS the
# deduplicated superedge list, identical to a one-shot aggregation of the
# full edge list (aggregation is order-independent: a sorted multiset
# sum). ``aggregate_edges`` is the one-shot wrapper over a single chunk.
#
# Capacity overflow (> max_super_edges unique pairs) truncates the sorted
# tail in both backends — every update keeps the lexicographically
# smallest ``cap`` pairs and drops the weight of the rest, while
# ``n_superedges`` still counts every unique pair of the latest update's
# union. The truncation point then depends on chunk order, so chunked ==
# one-shot is guaranteed only below capacity (the backends still agree
# with *each other* for any fixed chunk sequence; see
# tests/test_supergraph.py overflow-contract tests).
# --------------------------------------------------------------------------


def agg_init(s_cap: int, max_super_edges: int):
    """Empty aggregation state: (a [cap], b [cap], w [cap], n_superedges)."""
    return (
        jnp.full((max_super_edges,), s_cap, jnp.int32),
        jnp.full((max_super_edges,), s_cap, jnp.int32),
        jnp.zeros((max_super_edges,), jnp.float32),
        jnp.zeros((), jnp.int32),
    )


def _chunk_pairs(chunk, labels_ext, s_cap: int):
    """Map node edges → canonical community pairs; invalid → (s_cap, s_cap, 0).

    ``chunk`` [C,2] int32 node edges (padded slots point at the trash node);
    ``labels_ext`` [n_nodes+1] dense community per node with the trash slot
    mapped to ``s_cap``.
    """
    trash = labels_ext.shape[0] - 1
    cu = labels_ext[jnp.minimum(chunk[:, 0], trash)]
    cv = labels_ext[jnp.minimum(chunk[:, 1], trash)]
    a = jnp.minimum(cu, cv)
    b = jnp.maximum(cu, cv)
    valid = (a != b) & (a < s_cap) & (b < s_cap)
    a = jnp.where(valid, a, s_cap)
    b = jnp.where(valid, b, s_cap)
    w = jnp.where(valid, 1.0, 0.0).astype(jnp.float32)
    return a, b, w


def _agg_update_lexsort(state, a, b, w, s_cap: int, max_super_edges: int):
    """Baseline: one full lexsort of state + chunk, segment-sum re-dedupe."""
    pa, pb, pw, _ = state
    ca = jnp.concatenate([pa, a])
    cb = jnp.concatenate([pb, b])
    cw = jnp.concatenate([pw, w])

    # Lexsort by (a, b); invalid slots (s_cap, s_cap) sort last.
    order = jnp.lexsort((cb, ca))
    a_s, b_s, w_s = ca[order], cb[order], cw[order]
    new_pair = jnp.concatenate(
        [jnp.array([True]), (a_s[1:] != a_s[:-1]) | (b_s[1:] != b_s[:-1])]
    )
    new_pair = new_pair & (a_s != s_cap)
    seg = jnp.cumsum(new_pair) - 1  # dense superedge id per sorted slot (or -1 prefix)
    seg = jnp.where(a_s != s_cap, seg, max_super_edges)

    sw = jnp.zeros(max_super_edges + 1, jnp.float32).at[seg].add(w_s)
    sa = jnp.full((max_super_edges + 1,), s_cap, jnp.int32).at[seg].set(a_s)
    sb = jnp.full((max_super_edges + 1,), s_cap, jnp.int32).at[seg].set(b_s)
    n_superedges = jnp.sum(new_pair).astype(jnp.int32)
    return (
        sa[:max_super_edges],
        sb[:max_super_edges],
        sw[:max_super_edges],
        n_superedges,
    )


def _dedupe_chunk(a, b, w, s_cap: int):
    """Level one of the merge scheme: sort + combine only the C chunk pairs.

    Returns (ca, cb, cw): a sorted run of the chunk's unique valid pairs
    with summed multiplicities, padded with (s_cap, s_cap, 0) slots.
    """
    c = a.shape[0]
    key = pack_keys(a, b, s_cap)
    order = jnp.argsort(key)
    k_s, w_s = key[order], w[order]
    first = jnp.concatenate([jnp.array([True]), k_s[1:] != k_s[:-1]])
    first = first & (k_s != SENTINEL)
    seg = jnp.cumsum(first) - 1  # dense local id per sorted slot (or -1 prefix)
    seg = jnp.where(k_s != SENTINEL, seg, c)
    cw = jnp.zeros((c + 1,), jnp.float32).at[seg].add(w_s)
    ck = jnp.full((c + 1,), SENTINEL, jnp.uint32).at[seg].set(k_s)
    ca, cb = unpack_keys(ck[:c], s_cap)
    return ca, cb, cw[:c]


def _agg_update_merge(state, a, b, w, s_cap: int, kernel_backend: str):
    """Two-level scheme: local chunk dedupe, then sorted-merge into state."""
    pa, pb, pw, _ = state
    ca, cb, cw = _dedupe_chunk(a, b, w, s_cap)
    return merge_ops.merge_combine(
        pa, pb, pw, ca, cb, cw, s_cap, backend=kernel_backend
    )


def _agg_update_body(
    state,
    chunk,
    labels_ext,
    s_cap: int,
    max_super_edges: int,
    agg_backend: str = "merge",
    kernel_backend: str = "auto",
):
    """Combine one edge chunk into the aggregation state (jittable).

    ``agg_backend`` selects the combine algorithm ("merge" default,
    "lexsort" baseline — bit-identical below capacity); ``kernel_backend``
    is forwarded to ``kernels/merge/ops.py`` on the merge path.
    """
    a, b, w = _chunk_pairs(chunk, labels_ext, s_cap)
    if agg_backend == "lexsort":
        def run(st):
            return _agg_update_lexsort(st, a, b, w, s_cap, max_super_edges)
    elif agg_backend == "merge":
        def run(st):
            return _agg_update_merge(st, a, b, w, s_cap, kernel_backend)
    else:
        raise ValueError(f"unknown agg_backend {agg_backend!r}")
    # An all-invalid chunk (every edge intra-community or trash-padded)
    # is a no-op for any backend: short-circuit it instead of paying a
    # full state rewrite.
    return jax.lax.cond(jnp.any(a != s_cap), run, lambda st: st, state)


agg_update = functools.partial(
    jax.jit,
    static_argnames=("s_cap", "max_super_edges", "agg_backend", "kernel_backend"),
    donate_argnums=(0,),
)(_agg_update_body)


@functools.lru_cache(maxsize=None)
def sharded_agg_update(mesh, s_cap: int, max_super_edges: int,
                       agg_backend: str = "merge",
                       kernel_backend: str = "auto"):
    """Compiled sharded ``agg_update`` over ``mesh``.

    The chunk arrives row-sharded (``row_chunk_spec``), state and labels
    replicated. Merge path: each shard maps + dedupes its own C/D rows (the
    sort is the expensive step, now D-way parallel), one ``all_gather``
    concatenates the local runs back in row order, and a second dedupe
    restores the single sorted run — bit-identical input to
    ``merge_combine`` even at capacity overflow, because the unique pair
    set and the (integer-valued float) summed weights match the one-device
    dedupe exactly. Lexsort path: the gather of contiguous row shards
    reproduces the original chunk arrays verbatim, then runs the baseline
    unchanged. Requires ``chunk_len % mesh.size == 0`` — callers gate.
    """
    from jax.sharding import PartitionSpec as P

    from repro.kernels.compat import shard_map_compat
    from repro.sharding.rules import row_chunk_spec

    axes = tuple(mesh.axis_names)

    def body(state, chunk, labels_ext):
        a, b, w = _chunk_pairs(chunk, labels_ext, s_cap)
        if agg_backend == "lexsort":
            ga = jax.lax.all_gather(a, axes, axis=0, tiled=True)
            gb = jax.lax.all_gather(b, axes, axis=0, tiled=True)
            gw = jax.lax.all_gather(w, axes, axis=0, tiled=True)

            def run(st):
                return _agg_update_lexsort(st, ga, gb, gw, s_cap, max_super_edges)
        elif agg_backend == "merge":
            la, lb, lw = _dedupe_chunk(a, b, w, s_cap)
            ga = jax.lax.all_gather(la, axes, axis=0, tiled=True)
            gb = jax.lax.all_gather(lb, axes, axis=0, tiled=True)
            gw = jax.lax.all_gather(lw, axes, axis=0, tiled=True)
            ca, cb, cw = _dedupe_chunk(ga, gb, gw, s_cap)

            def run(st):
                pa, pb, pw, _ = st
                return merge_ops.merge_combine(
                    pa, pb, pw, ca, cb, cw, s_cap, backend=kernel_backend
                )
        else:
            raise ValueError(f"unknown agg_backend {agg_backend!r}")
        # Same short-circuit as the single-device path; the predicate is
        # over the gathered (replicated) pairs, so every device agrees.
        return jax.lax.cond(jnp.any(ga != s_cap), run, lambda st: st, state)

    mapped = shard_map_compat(
        body,
        mesh,
        in_specs=((P(), P(), P(), P()), row_chunk_spec(mesh), P()),
        out_specs=(P(), P(), P(), P()),
    )
    return jax.jit(mapped, donate_argnums=(0,))


def sharded_agg_collective_bytes(chunk_len: int, devices: int) -> int:
    """Bytes one device contributes to the collectives of one
    ``sharded_agg_update`` call: the all-gathers of its C/D-long pair
    columns (int32 a and b, float32 weights), on either backend."""
    return 3 * (chunk_len // devices) * 4


def agg_finalize(state):
    """(sedges [cap,2], sweights [cap], n_superedges) from aggregation state."""
    a, b, w, n = state
    return jnp.stack([a, b], axis=1), w, n


@functools.partial(
    jax.jit, static_argnames=("s_cap", "max_super_edges", "agg_backend")
)
def aggregate_edges(
    edges: jnp.ndarray,
    labels_dense: jnp.ndarray,
    s_cap: int,
    max_super_edges: int,
    agg_backend: str = "merge",
):
    """Map node edges through community labels, drop intra edges, dedupe
    (one-shot wrapper: the whole edge list as a single chunk).

    Returns (sedges [cap,2], sweights [cap], n_superedges).
    """
    labels_ext = jnp.concatenate([labels_dense, jnp.array([s_cap], jnp.int32)])
    state = agg_init(s_cap, max_super_edges)
    state = _agg_update_body(
        state, edges, labels_ext, s_cap, max_super_edges, agg_backend
    )
    return agg_finalize(state)


def community_sizes(
    labels_dense: jnp.ndarray,
    node_deg: jnp.ndarray,
    n_supernodes: jnp.ndarray,
    s_cap: int,
    cms_cfg: cms_lib.CMSConfig,
    mesh=None,
) -> jnp.ndarray:
    """CMS-estimated community sizes (paper §4.1): one sketch update per node,
    weight = its true graph degree; queries beyond the live count are masked.
    The update is ``kernels/cms`` (the one-hot Pallas kernel on TPU).

    With ``mesh`` the node keys are sharded over devices (padded to a
    multiple of the device count with the masked key -1) and the sketch is
    merged by one ``psum`` — exact, since degrees are integer-valued.
    """
    sketch = cms_lib.init(cms_cfg)
    weights = node_deg.astype(jnp.float32)
    if mesh is not None and mesh.size > 1:
        pad = (-labels_dense.shape[0]) % mesh.size
        keys = jnp.concatenate(
            [labels_dense, jnp.full((pad,), -1, jnp.int32)]
        )
        weights = jnp.concatenate([weights, jnp.zeros((pad,), jnp.float32)])
        sketch = cms_lib.sharded_update(mesh, cms_cfg)(sketch, keys, weights)
    else:
        sketch = cms_ops.update(sketch, labels_dense, weights, cms_cfg)
    sizes = cms_lib.query(cms_lib.finalize(sketch), jnp.arange(s_cap, dtype=jnp.int32), cms_cfg)
    return jnp.where(jnp.arange(s_cap) < n_supernodes, sizes, 0.0)


@functools.partial(
    jax.jit,
    static_argnames=("n_nodes", "s_cap", "max_super_edges", "cms_cfg", "agg_backend"),
)
def build_supergraph(
    edges: jnp.ndarray,
    labels: jnp.ndarray,
    node_deg: jnp.ndarray,
    n_nodes: int,
    s_cap: int,
    max_super_edges: int,
    cms_cfg: cms_lib.CMSConfig,
    agg_backend: str = "merge",
) -> Supergraph:
    """Full paper path: dense-relabel communities, CMS-size them, dedupe edges.

    Community size (paper §4.1): sum of *graph* degrees of member nodes
    (≈ 2×intra edges), accumulated through the count–min sketch keyed by
    community id — never an exact counter.
    """
    labels_dense, n_supernodes = dense_labels(labels, n_nodes)
    sizes = community_sizes(labels_dense, node_deg, n_supernodes, s_cap, cms_cfg)

    sedges, sweights, n_superedges = aggregate_edges(
        edges, labels_dense, s_cap, max_super_edges, agg_backend
    )
    return Supergraph(
        edges=sedges,
        weights=sweights,
        sizes=sizes,
        n_supernodes=n_supernodes,
        n_superedges=n_superedges,
        labels=labels_dense,
    )


jax.tree_util.register_dataclass(
    Supergraph,
    data_fields=["edges", "weights", "sizes", "n_supernodes", "n_superedges", "labels"],
    meta_fields=[],
)
