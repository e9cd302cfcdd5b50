"""Public wrappers for the raster primitives: Pallas on TPU, XLA scatter
elsewhere (dispatch mirrors kernels/segment and kernels/merge ops.py).
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.compat import resolve_backend
from repro.kernels.raster.ref import (
    count_scatter_into_ref,
    count_scatter_ref,
    disk_accum_ref,
)
from repro.kernels.raster.splat import count_scatter_pallas, disk_accum_pallas



def count_scatter(
    pos: jnp.ndarray,
    inc: jnp.ndarray,
    size: int,
    backend: str = "auto",
) -> jnp.ndarray:
    """[N] positions + [N] increments → [size] int32 counts (edge splat)."""
    backend, interpret = resolve_backend(backend)
    if backend == "ref":
        return count_scatter_ref(pos, inc, size)
    return count_scatter_pallas(pos, inc, size, interpret=interpret)


def count_scatter_into(
    acc: jnp.ndarray,
    pos: jnp.ndarray,
    inc: jnp.ndarray | None = None,
    backend: str = "auto",
) -> jnp.ndarray:
    """Accumulating ``count_scatter``: adds into ``acc`` instead of
    returning a fresh buffer (hot path of the renderer's chunk loop —
    in place when the caller donates ``acc``). ``inc=None`` = unit
    increments (takes the faster pre-sorted scatter on the ref path)."""
    backend, interpret = resolve_backend(backend)
    if backend == "ref":
        return count_scatter_into_ref(acc, pos, inc)
    if inc is None:
        inc = jnp.ones(pos.shape, jnp.int32)
    return count_scatter_pallas(
        pos, inc, acc.shape[0], acc=acc, interpret=interpret
    )


def disk_accum(
    cx: jnp.ndarray,
    cy: jnp.ndarray,
    r: jnp.ndarray,
    group: jnp.ndarray,
    n_groups: int,
    h: int,
    w: int,
    backend: str = "auto",
) -> jnp.ndarray:
    """Per-pixel disk coverage counts by color group, [n_groups, h, w]."""
    backend, interpret = resolve_backend(backend)
    if backend == "ref":
        return disk_accum_ref(cx, cy, r, group, n_groups, h, w)
    return disk_accum_pallas(cx, cy, r, group, n_groups, h, w, interpret=interpret)
