"""Jit'd public wrappers for the uniform-grid repulsion family: Pallas on
TPU, the chunked/shifted XLA path elsewhere (auto/ref/pallas/interpret
dispatch mirrors kernels/repulsion, kernels/merge and kernels/raster).

``grid_repulsion`` is the whole stage — bin → sort → monopole stats →
far field + banded near field → unsort — with ``cell``/``order`` optionally
precomputed so the FA2 scan can rebuild them every ``grid_rebuild``
iterations instead of every step (core/forceatlas2.layout). The monopole
stats ride the sorted order through a ``kernels/segment`` segment-sum
(``indices_are_sorted`` fast path). All math runs in float32 regardless of
the caller's position dtype; the result is cast back.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.compat import resolve_backend
from repro.kernels.grid.ref import (
    bin_and_sort,
    bin_nodes,  # noqa: F401  (re-exported: binning shared by every backend)
    far_field_ref,
    near_field_ref,
    near_field_rows,  # noqa: F401  (re-exported: sharded-layout halo path)
)
from repro.kernels.grid.tiled import far_field_pallas, near_field_pallas
from repro.kernels.segment import ops as segment_ops



def cell_stats(
    pos_s: jnp.ndarray,
    mass_s: jnp.ndarray,
    cell_s: jnp.ndarray,
    n_cells: int,
    backend: str = "auto",
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(centroids [C, 2], masses [C]) per cell from cell-sorted nodes.

    One fused sorted segment-sum over [Σm·x, Σm·y, Σm]; empty cells get
    mass 0 (force-dead) and centroid 0.
    """
    backend, _ = resolve_backend(backend)
    data = jnp.concatenate(
        [pos_s * mass_s[:, None], mass_s[:, None]], axis=1)
    sums = segment_ops.segment_sum(
        data, cell_s, n_cells, backend=backend, indices_are_sorted=True)
    cmass = sums[:, 2]
    ccent = sums[:, :2] / jnp.maximum(cmass, 1e-9)[:, None]
    return ccent, cmass


def far_field(pos, mass, cell, ccent, cmass, kr: float, backend: str = "auto"):
    """Monopole far field (own cell excluded) → [n, 2]."""
    backend, interpret = resolve_backend(backend)
    if backend == "ref":
        return far_field_ref(pos, mass, cell, ccent, cmass, kr)
    return far_field_pallas(pos, mass, cell, ccent, cmass, kr,
                            interpret=interpret)


def near_field_sorted(pos_s, mass_s, cell_s, kr: float, window: int,
                      backend: str = "auto"):
    """Banded same-cell near field over the sorted order → [n, 2] (sorted)."""
    backend, interpret = resolve_backend(backend)
    if backend == "ref":
        return near_field_ref(pos_s, mass_s, cell_s, kr, window)
    return near_field_pallas(pos_s, mass_s, cell_s, kr, window,
                             interpret=interpret)


def grid_repulsion(
    pos: jnp.ndarray,  # [n, 2]
    mass: jnp.ndarray,  # [n] (padding must carry mass 0)
    kr: float,
    grid_size: int,
    window: int,
    cell: jnp.ndarray | None = None,
    order: jnp.ndarray | None = None,
    backend: str = "auto",
) -> jnp.ndarray:
    """Uniform-grid FA2 repulsion forces, pos [n,2] → [n,2].

    ``cell``/``order`` (from ``bin_and_sort``) may be stale by up to
    ``grid_rebuild`` iterations; monopole stats are always recomputed from
    the current positions, so staleness only blurs the cell *partition*,
    never the masses.
    """
    pos32 = pos.astype(jnp.float32)
    mass32 = mass.astype(jnp.float32)
    if cell is None or order is None:
        cell, order = bin_and_sort(pos32, grid_size)
    pos_s = pos32[order]
    mass_s = mass32[order]
    cell_s = cell[order]
    ccent, cmass = cell_stats(pos_s, mass_s, cell_s, grid_size * grid_size,
                              backend=backend)
    # Both fields run in sorted order → one unsorting scatter at the end.
    force_s = far_field(pos_s, mass_s, cell_s, ccent, cmass, kr,
                        backend=backend)
    force_s = force_s + near_field_sorted(pos_s, mass_s, cell_s, kr, window,
                                          backend=backend)
    out = jnp.zeros_like(force_s).at[order].set(force_s)
    return out.astype(pos.dtype)
