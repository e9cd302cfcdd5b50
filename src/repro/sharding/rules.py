"""Stream-chunk placements for the sharded streaming engine (core/stream.py,
core/scoda.py, launch/stream_runner.py).

Chunks are edge buffers, so they shard over EVERY mesh axis: a chunk row
belongs to exactly one device. ``row_chunk_spec`` places rows for the
supergraph/degree/modularity passes, ``block_chunk_spec`` the within-block
axis for the detect pass, and ``linear_axis_index`` recovers a device's
row order inside a ``shard_map`` body.
"""
from __future__ import annotations

import jax
from jax.sharding import Mesh, PartitionSpec as P


def row_chunk_spec(mesh: Mesh) -> P:
    """Row-shard an [C, 2] edge chunk over all mesh axes (contiguous rows
    per device — the supergraph/degree/modularity pass placement)."""
    return P(tuple(mesh.axis_names), None)


def block_chunk_spec(mesh: Mesh) -> P:
    """Shard a [B, block_size, 2] chunk view on the within-block axis, so
    every device owns the same slice of EVERY SCoDA block (the detect-pass
    placement — the block scan then runs in lockstep across devices with
    per-block all-reduces, preserving the sequential block order that
    bit-exactness requires)."""
    return P(None, tuple(mesh.axis_names), None)


def linear_axis_index(axis_names: tuple, axis_sizes: tuple):
    """Traced linearized device index inside a ``shard_map`` body, matching
    the row order of ``P(tuple(axis_names))`` sharding (row-major over the
    mesh axes, the same order ``lax.all_gather`` tiles shards in)."""
    idx = jax.lax.axis_index(axis_names[0])
    for name, size in zip(axis_names[1:], axis_sizes[1:]):
        idx = idx * size + jax.lax.axis_index(name)
    return idx
