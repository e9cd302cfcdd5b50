"""Pallas TPU kernel: tiled FA2 n-body repulsion.

Adaptation of the paper's Barnes–Hut GPU repulsion (DESIGN.md §2): on a
supergraph (n ≤ ~2·10⁵) exact O(n²) pairwise interaction evaluated in
VMEM tiles is faster on TPU than a pointer-chasing tree — the pair tile
is a dense [TI, TJ] elementwise block that maps onto the VPU, streamed
FlashAttention-style.

Grid = (m/TI, n/TJ) for m target rows against n sources: the i axis is
parallel; the j axis revisits the same output block and accumulates
(``dimension_semantics=("parallel", "arbitrary")``). The target rows may
be any slice of the sources (their global ids ride along for the
self-interaction mask), so a device of the sharded layout computes its
own rows with the same blocks, and the same bits, as one device computing
all of them. Working set per step: 2·(TI+TJ) pos/mass/radii vectors +
four [TI, TJ] pair blocks ≈ 1.3 MB at TI=TJ=512 — comfortably in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu  # noqa: F401

from repro.kernels.compat import CompilerParams

EPS = 1e-4


def _kernel(ids_i_ref, pos_i_ref, mass_i_ref, rad_i_ref, pos_j_ref, mass_j_ref,
            rad_j_ref, out_ref, *, kr: float, ti: int, tj: int, use_radii: bool):
    j_step = pl.program_id(1)

    xi = pos_i_ref[:, 0:1]  # [TI, 1]
    yi = pos_i_ref[:, 1:2]
    xj = pos_j_ref[:, 0:1].T  # [1, TJ]
    yj = pos_j_ref[:, 1:2].T
    dx = xi - xj  # [TI, TJ]
    dy = yi - yj
    d2 = dx * dx + dy * dy
    d = jnp.sqrt(jnp.maximum(d2, EPS * EPS))

    mi = mass_i_ref[:, 0:1]
    mj = mass_j_ref[:, 0:1].T
    if use_radii:
        eff = jnp.maximum(d - rad_i_ref[:, 0:1] - rad_j_ref[:, 0:1].T, EPS)
    else:
        eff = jnp.maximum(d, EPS)

    gj = j_step * tj + jax.lax.broadcasted_iota(jnp.int32, (ti, tj), 1)
    mag = jnp.where(ids_i_ref[:, 0:1] == gj, 0.0, kr * mi * mj / (eff * d))

    fx = jnp.sum(mag * dx, axis=1, keepdims=True)  # [TI, 1]
    fy = jnp.sum(mag * dy, axis=1, keepdims=True)
    partial = jnp.concatenate([fx, fy], axis=1)  # [TI, 2]

    @pl.when(j_step == 0)
    def _init():
        out_ref[...] = partial

    @pl.when(j_step != 0)
    def _acc():
        out_ref[...] += partial


@functools.partial(
    jax.jit, static_argnames=("kr", "ti", "tj", "use_radii", "rows", "interpret")
)
def repulsion_pallas(
    pos: jnp.ndarray,
    mass: jnp.ndarray,
    radii: jnp.ndarray,
    kr: float,
    ti: int = 512,
    tj: int = 512,
    use_radii: bool = True,
    i0=0,
    rows: int | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """pos [n,2] f32, mass/radii [n] f32 → forces [rows,2] on target rows
    [i0, i0+rows) (default: all n). ``i0`` may be traced. n must divide by
    tj and rows by ti (ops.py pads; padded slots carry mass 0 so they are
    force-neutral)."""
    n = pos.shape[0]
    m = n if rows is None else rows
    assert n % tj == 0 and m % ti == 0, (n, m, ti, tj)
    grid = (m // ti, n // tj)
    m2 = mass[:, None]
    r2 = radii[:, None]
    ids = (i0 + jnp.arange(m, dtype=jnp.int32))[:, None]

    def target(x):
        return x if rows is None else jax.lax.dynamic_slice_in_dim(x, i0, m)

    return pl.pallas_call(
        functools.partial(_kernel, kr=kr, ti=ti, tj=tj, use_radii=use_radii),
        name="nbody_repulsion",
        grid=grid,
        in_specs=[
            pl.BlockSpec((ti, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((ti, 2), lambda i, j: (i, 0)),
            pl.BlockSpec((ti, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((ti, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((tj, 2), lambda i, j: (j, 0)),
            pl.BlockSpec((tj, 1), lambda i, j: (j, 0)),
            pl.BlockSpec((tj, 1), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((ti, 2), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, 2), pos.dtype),
        compiler_params=CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(ids, target(pos), target(m2), target(r2), pos, m2, r2)
