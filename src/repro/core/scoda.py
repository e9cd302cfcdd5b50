"""TPU-adapted parallel streaming community detection (SCoDA, paper §3.2.1).

The paper's GPU variant assigns one CUDA thread per edge and lets degree
updates / community writes race through atomics. TPUs have no such atomics;
the adaptation (documented in DESIGN.md §2) processes the edge stream in
fixed-size *blocks* via ``lax.scan``:

  * inside a block every edge is evaluated in parallel against the
    block-start degree/community snapshot (vectorized),
  * conflicting community writes to the same node are resolved by a
    deterministic min-reduction (``.at[].min``) — replacing the GPU's
    nondeterministic last-write-wins,
  * degree increments land via scatter-add (``.at[].add``), the TPU's
    native "atomic add".

``block_size`` is the parallelism/fidelity dial: block_size=1 is exactly
the sequential SCoDA; larger blocks = more parallelism, coarser snapshot —
mirroring the paper's GPU trade-off but deterministic and replayable.

Rounds follow the paper's Algorithm 3: each round re-streams the edge list
with persistent (community, degree) state and a threshold that grows
geometrically (δ^i) so larger communities can keep absorbing smaller ones
("hierarchical community detection").
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp

INT32_MAX = jnp.iinfo(jnp.int32).max


@dataclass(frozen=True)
class ScodaConfig:
    degree_threshold: int  # δ — paper default: mode degree of the graph
    rounds: int = 4
    block_size: int = 4096
    threshold_growth: float = 2.0  # threshold at round i: δ * growth^(i-1) (δ^i capped)
    threshold_schedule: str = "paper"  # "paper": δ^i ; "geometric": δ·g^(i-1)
    tie_break: str = "skip"  # paper Algorithm 3 skips equal-degree edges
    # Paper Algorithm 3 as printed increments degrees only on adoption — but
    # then every degree stays 0 and no edge ever adopts (deadlock). Hollocou's
    # SCoDA increments BOTH endpoint degrees for every processed edge; that is
    # the only functional reading, so it is the default ("scoda").
    degree_update: str = "scoda"  # "scoda": both endpoints every edge; "paper": adoptee++ only
    compress_labels: bool = False  # beyond-paper: pointer-jump label compression
    # Beyond-paper fidelity recovery (DESIGN.md §2): with exact_block_degrees
    # each edge sees deg(snapshot) + (its endpoint's prior occurrences within
    # the block), computed by a vectorized cumulative count — the *exact*
    # sequential degree trajectory at full block parallelism (degrees only;
    # labels still come from the block snapshot).
    exact_block_degrees: bool = True
    # Conflict resolution among same-block donors: "min" = smallest community
    # id wins (simple, biased toward low ids); "max_degree" = highest-degree
    # donor wins (paper §3.2.1: big communities absorb small ones).
    conflict: str = "max_degree"
    # Beyond-paper fidelity recovery #2: sequential SCoDA propagates labels
    # transitively through the stream (w adopts com(u) AFTER u adopted
    # com(v)); a block snapshot loses those chains and fragments communities
    # into stars. ``propagate_jumps`` pointer-jumping passes at block end
    # collapse chains of length ≤ 2^jumps. Adoption points strictly up the
    # degree order under snapshot degrees, so chains are acyclic; rare cycles
    # under exact_block_degrees are bounded by the fixed jump count.
    # Default 0: measured against the sequential oracle, jumping over-merges
    # (chains cross community borders); see EXPERIMENTS.md §Reproduction.
    propagate_jumps: int = 0


def round_threshold(cfg: ScodaConfig, i: int) -> int:
    if cfg.threshold_schedule == "paper":
        t = float(cfg.degree_threshold) ** (i + 1)
    else:
        t = float(cfg.degree_threshold) * (cfg.threshold_growth ** i)
    return int(min(t, 2**30))


_round_threshold = round_threshold  # back-compat alias


def _cumcount_endpoints(u, v, valid):
    """Per-edge prior-occurrence counts of each endpoint within the block.

    Flattens endpoints in stream order [u0,v0,u1,v1,...] and counts, for each
    slot, how many earlier slots name the same node — a vectorized sort +
    rank-in-group. O(B log B), fully parallel.
    """
    bs = u.shape[0]
    flat = jnp.stack([u, v], axis=1).reshape(-1)  # [2B] stream order
    order = jnp.argsort(flat, stable=True)
    sorted_vals = flat[order]
    is_start = jnp.concatenate(
        [jnp.array([True]), sorted_vals[1:] != sorted_vals[:-1]]
    )
    idx = jnp.arange(2 * bs, dtype=jnp.int32)
    group_start = jax.lax.associative_scan(jnp.maximum, jnp.where(is_start, idx, 0))
    rank_sorted = idx - group_start
    rank = jnp.zeros(2 * bs, jnp.int32).at[order].set(rank_sorted)
    rank = jnp.where(valid.repeat(2), rank, 0)
    return rank[0::2], rank[1::2]


def _block_update(state, block, *, threshold, tie_break, degree_update,
                  exact_block_degrees, conflict, propagate_jumps,
                  mesh_axes=None, mesh_sizes=None):
    """Process one block of edges against the block-start snapshot.

    With ``mesh_axes`` set the body runs inside a ``shard_map``: ``block``
    is this device's slice of the block (``block_chunk_spec`` placement),
    state stays replicated, and every scatter reduction is completed by the
    matching integer all-reduce (min/max/sum are order-free, so the result
    is bitwise identical to the single-device block update).
    """
    com, deg = state
    u, v = block[:, 0], block[:, 1]
    trash = com.shape[0] - 1  # index n_nodes = trash slot
    valid = (u != trash) & (v != trash) & (u != v)

    if degree_update == "scoda":
        # Hollocou semantics: degrees bump for every processed edge, and the
        # join test sees the post-increment values. Under block-parallel
        # streaming the snapshot approximates this (DESIGN.md §2).
        if exact_block_degrees:
            if mesh_axes is None:
                cu, cv = _cumcount_endpoints(u, v, valid)
            else:
                # The cumulative occurrence count is a prefix over the FULL
                # block in stream order — gather the block (tiled order ==
                # the row order of the sharding) and slice back our rows.
                from repro.sharding.rules import linear_axis_index

                bsl = u.shape[0]
                full = jax.lax.all_gather(block, mesh_axes, axis=0, tiled=True)
                uf, vf = full[:, 0], full[:, 1]
                validf = (uf != trash) & (vf != trash) & (uf != vf)
                cuf, cvf = _cumcount_endpoints(uf, vf, validf)
                i0 = bsl * linear_axis_index(mesh_axes, mesh_sizes)
                cu = jax.lax.dynamic_slice_in_dim(cuf, i0, bsl)
                cv = jax.lax.dynamic_slice_in_dim(cvf, i0, bsl)
        else:
            cu = cv = 0
        du = deg[u] + 1 + cu
        dv = deg[v] + 1 + cv
    else:
        du = deg[u]
        dv = deg[v]
    elig = valid & (du <= threshold) & (dv <= threshold)

    adopt_v = elig & (du > dv)  # v adopts com[u]
    adopt_u = elig & (dv > du)  # u adopts com[v]
    if tie_break == "join":
        adopt_u = adopt_u | (elig & (du == dv))

    adoptee = jnp.where(adopt_v, v, jnp.where(adopt_u, u, trash))
    donor = jnp.where(adopt_v, u, v)
    donor_com = com[donor]
    any_adopt = adopt_u | adopt_v
    donor_com = jnp.where(any_adopt, donor_com, INT32_MAX)

    if conflict == "max_degree":
        # Highest-degree donor wins (big communities absorb small, §3.2.1);
        # ties broken toward the smaller community id. Two scatters:
        # 1) winning donor degree per adoptee, 2) min com among winners.
        donor_deg = jnp.where(any_adopt, jnp.where(adopt_v, du, dv), -1)
        win_deg = jnp.full_like(com, -1).at[adoptee].max(donor_deg)
        if mesh_axes is not None:  # winners are decided across ALL shards
            win_deg = jax.lax.pmax(win_deg, mesh_axes)
        is_winner = any_adopt & (donor_deg == win_deg[adoptee])
        cand_val = jnp.where(is_winner, donor_com, INT32_MAX)
        cand = jnp.full_like(com, INT32_MAX).at[adoptee].min(cand_val)
    else:  # "min": smallest donor community id wins
        cand = jnp.full_like(com, INT32_MAX).at[adoptee].min(donor_com)
    if mesh_axes is not None:
        cand = jax.lax.pmin(cand, mesh_axes)
    new_com = jnp.where(cand != INT32_MAX, cand, com)
    new_com = new_com.at[trash].set(trash)
    for _ in range(propagate_jumps):  # collapse intra-block adoption chains
        new_com = new_com[new_com]

    if degree_update == "paper":
        if mesh_axes is None:
            new_deg = deg.at[adoptee].add(jnp.where(any_adopt, 1, 0))
        else:
            inc = jnp.zeros_like(deg).at[adoptee].add(jnp.where(any_adopt, 1, 0))
            new_deg = deg + jax.lax.psum(inc, mesh_axes)
    else:  # original SCoDA: both endpoints bump on every processed edge
        if mesh_axes is None:
            new_deg = deg.at[u].add(jnp.where(valid, 1, 0)).at[v].add(jnp.where(valid, 1, 0))
        else:
            ones = jnp.where(valid, 1, 0)
            inc = jnp.zeros_like(deg).at[u].add(ones).at[v].add(ones)
            new_deg = deg + jax.lax.psum(inc, mesh_axes)
    new_deg = new_deg.at[trash].set(0)
    return (new_com, new_deg), None


# --------------------------------------------------------------------------
# Chunk-incremental API (core/stream.py engine): init → update×chunks → finalize.
# The one-shot ``detect_communities`` below is a thin wrapper that feeds the
# whole edge list through the same update body as a single chunk, so chunked
# and one-shot execution are bit-for-bit identical whenever the chunk size is
# a multiple of ``block_size`` (identical block partition of the stream).
# --------------------------------------------------------------------------


def scoda_init(n_nodes: int):
    """Fresh SCoDA state: (com, deg), each [n_nodes+1] (last slot = trash)."""
    com = jnp.arange(n_nodes + 1, dtype=jnp.int32)
    deg = jnp.zeros(n_nodes + 1, dtype=jnp.int32)
    return com, deg


def _scoda_update_body(state, chunk, threshold, cfg: ScodaConfig):
    """One pass of one round over a chunk of the edge stream (jittable).

    ``chunk`` [C,2] int32 with padded slots pointing at the trash node;
    ``threshold`` may be a python int or a traced int32 scalar. The chunk is
    scanned in blocks of ``cfg.block_size`` exactly like the one-shot path.
    """
    trash = state[0].shape[0] - 1
    e = chunk.shape[0]
    bs = min(cfg.block_size, e)
    n_blocks = (e + bs - 1) // bs
    pad = n_blocks * bs - e
    blocks = jnp.concatenate(
        [chunk, jnp.full((pad, 2), trash, dtype=chunk.dtype)], axis=0
    ).reshape(n_blocks, bs, 2)
    step = functools.partial(
        _block_update,
        threshold=threshold,
        tie_break=cfg.tie_break,
        degree_update=cfg.degree_update,
        exact_block_degrees=cfg.exact_block_degrees,
        conflict=cfg.conflict,
        propagate_jumps=cfg.propagate_jumps,
    )
    state, _ = jax.lax.scan(step, state, blocks)
    return state


# Threshold is a traced scalar so all rounds share one executable; state is
# donated — the engine holds exactly one (com, deg) copy on device.
scoda_update = functools.partial(jax.jit, static_argnames=("cfg",),
                                 donate_argnums=(0,))(_scoda_update_body)


@functools.lru_cache(maxsize=None)
def sharded_scoda_update(mesh, cfg: ScodaConfig):
    """Compiled sharded chunk update over ``mesh``.

    Takes (state, blocks [n_blocks, block_size, 2], threshold): blocks must
    arrive sharded per ``block_chunk_spec`` (every device owns the same
    within-block slice of every block), state/threshold replicated; returns
    the replicated updated state. Bit-identical to ``scoda_update`` on the
    equivalent flat chunk: the block scan runs in lockstep across devices
    and every cross-device reduction is an integer min/max/sum (order-free).
    Requires ``block_size % mesh.size == 0`` — callers gate on that.
    """
    from jax.sharding import PartitionSpec as P

    from repro.kernels.compat import shard_map_compat
    from repro.sharding.rules import block_chunk_spec

    axes = tuple(mesh.axis_names)
    sizes = tuple(mesh.shape[a] for a in axes)

    def body(state, blocks, threshold):
        step = functools.partial(
            _block_update,
            threshold=threshold,
            tie_break=cfg.tie_break,
            degree_update=cfg.degree_update,
            exact_block_degrees=cfg.exact_block_degrees,
            conflict=cfg.conflict,
            propagate_jumps=cfg.propagate_jumps,
            mesh_axes=axes,
            mesh_sizes=sizes,
        )
        state, _ = jax.lax.scan(step, state, blocks)
        return state

    mapped = shard_map_compat(
        body,
        mesh,
        in_specs=((P(), P()), block_chunk_spec(mesh), P()),
        out_specs=(P(), P()),
    )
    return jax.jit(mapped, donate_argnums=(0,))


def sharded_update_collective_bytes(state, blocks, devices: int,
                                    cfg: ScodaConfig) -> int:
    """Bytes one device contributes to the collectives of one
    ``sharded_scoda_update`` call on ``blocks`` [n_blocks, block_size, 2]
    over ``devices``: per block, the all-gather of its block_size/D slice
    (exact block degrees), and the all-reduces of the node-length winner
    degrees (``max_degree``), candidates and degree increments."""
    com, deg = state
    n_blocks, bs, width = blocks.shape
    per_block = com.nbytes + deg.nbytes  # pmin of candidates, psum of degrees
    if cfg.conflict == "max_degree":
        per_block += com.nbytes  # pmax of winner degrees
    if cfg.degree_update == "scoda" and cfg.exact_block_degrees:
        per_block += bs // devices * width * blocks.dtype.itemsize
    return n_blocks * per_block


def _scoda_finalize_body(state, n_nodes: int, cfg: ScodaConfig):
    com, deg = state
    if cfg.compress_labels:
        # Pointer jumping: compose the node→representative map to a fixpoint.
        def cond_fn(carry):
            c, it = carry
            return it < 32

        def body_fn(carry):
            c, it = carry
            return c[c], it + 1

        # log2(n) pointer jumps always reach the fixpoint; 32 covers any int32 n.
        com, _ = jax.lax.while_loop(cond_fn, body_fn, (com, 0))
    return com[:n_nodes], deg[:n_nodes]


scoda_finalize = functools.partial(
    jax.jit, static_argnames=("n_nodes", "cfg")
)(_scoda_finalize_body)


@functools.partial(jax.jit, static_argnames=("n_nodes", "cfg"))
def detect_communities(edges: jnp.ndarray, n_nodes: int, cfg: ScodaConfig):
    """Run multi-round block-streamed SCoDA (one-shot wrapper over the
    chunk-incremental API: the whole edge list is a single chunk per round).

    edges: [E, 2] int32 (padded slots = n_nodes).
    Returns (labels [n_nodes] int32 — community = representative node id,
             deg [n_nodes] int32 — SCoDA working degrees).
    """
    state = scoda_init(n_nodes)
    for i in range(cfg.rounds):
        state = _scoda_update_body(state, edges, round_threshold(cfg, i), cfg)
    return _scoda_finalize_body(state, n_nodes, cfg)


@functools.partial(jax.jit, static_argnames=("n_labels",))
def dense_labels(labels: jnp.ndarray, n_labels: int):
    """Relabel arbitrary int community ids to dense [0, S).

    Returns (dense [N] int32, n_communities scalar int32). Capacity =
    ``n_labels`` (≥ true community count; N always works).
    """
    uniq = jnp.unique(labels, size=n_labels, fill_value=INT32_MAX)
    dense = jnp.searchsorted(uniq, labels).astype(jnp.int32)
    n_communities = jnp.sum(uniq != INT32_MAX).astype(jnp.int32)
    return dense, n_communities
