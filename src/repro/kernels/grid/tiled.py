"""Pallas TPU kernels: tiled uniform-grid repulsion (far + near field).

``far_field_pallas`` — node-tile × cell-tile monopole accumulation,
FlashAttention-style: grid = (n/TI, C/TC) with the cell axis revisiting
and accumulating the same [TI, 2] output block
(``dimension_semantics=("parallel", "arbitrary")``), so no [n, C] pair
block ever exists outside VMEM — the dense baseline's [n, G², 2] HBM
tensor becomes a [TI, TC] register-resident tile. The own-cell monopole
is masked inside the pair block (fused subtraction — the dense baseline
adds it and subtracts it again afterwards).

``near_field_pallas`` — exact same-cell interaction over a ±W band of the
cell-sorted order. The band-skip idiom from ``kernels/merge`` /
``kernels/raster`` becomes *static* block overlap here: with W ≤ TI a
node tile's band only ever touches tiles (i−1, i, i+1), so the same
packed (x, y, mass, cell) array is passed three times with shifted index
maps and the kernel evaluates one masked [TI, 3·TI] pair block per tile,
entirely in VMEM. Working set per step ≈ 4·TI·4 B inputs + TI·3TI pair
blocks ≈ 2.5 MB at TI=256.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu  # noqa: F401

from repro.kernels.compat import CompilerParams
from repro.kernels.grid.ref import EPS2


def _pad_to(n: int, t: int) -> int:
    return ((n + t - 1) // t) * t


def _far_kernel(pos_ref, mass_ref, cell_ref, cent_ref, cmass_ref, out_ref,
                *, kr: float, ti: int, tc: int):
    j = pl.program_id(1)

    xi = pos_ref[:, 0:1]  # [TI, 1]
    yi = pos_ref[:, 1:2]
    cx = cent_ref[:, 0:1].T  # [1, TC]
    cy = cent_ref[:, 1:2].T
    dx = xi - cx  # [TI, TC]
    dy = yi - cy
    d2 = dx * dx + dy * dy

    mi = mass_ref[:, 0:1]
    mj = cmass_ref[:, 0:1].T
    # Own-cell monopole masked in place (empty/padded cells die via mj=0).
    gj = j * tc + jax.lax.broadcasted_iota(jnp.int32, (ti, tc), 1)
    own = cell_ref[:, 0:1] == gj
    mag = jnp.where(own, 0.0, kr * mi * mj / jnp.maximum(d2, EPS2))

    fx = jnp.sum(mag * dx, axis=1, keepdims=True)  # [TI, 1]
    fy = jnp.sum(mag * dy, axis=1, keepdims=True)
    partial = jnp.concatenate([fx, fy], axis=1)  # [TI, 2]

    @pl.when(j == 0)
    def _init():
        out_ref[...] = partial

    @pl.when(j != 0)
    def _acc():
        out_ref[...] += partial


@functools.partial(jax.jit, static_argnames=("kr", "ti", "tc", "interpret"))
def far_field_pallas(
    pos: jnp.ndarray,  # [n, 2] f32 (any order)
    mass: jnp.ndarray,  # [n] f32
    cell: jnp.ndarray,  # [n] int32 cell id per node
    ccent: jnp.ndarray,  # [C, 2] f32 cell centroids
    cmass: jnp.ndarray,  # [C] f32 cell masses
    kr: float,
    ti: int = 256,
    tc: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    """Monopole far field, own cell excluded → [n, 2]. Padded node slots
    carry mass 0 / cell −1, padded cell slots mass 0 — all force-neutral."""
    n = pos.shape[0]
    c = ccent.shape[0]
    n_pad = _pad_to(n, ti)
    c_pad = _pad_to(c, tc)
    npad = (0, n_pad - n)
    cpad = (0, c_pad - c)
    pos_p = jnp.pad(pos, (npad, (0, 0)))
    mass_p = jnp.pad(mass, npad)[:, None]
    cell_p = jnp.pad(cell, npad, constant_values=-1)[:, None]
    cent_p = jnp.pad(ccent, (cpad, (0, 0)))
    cmass_p = jnp.pad(cmass, cpad)[:, None]
    grid = (n_pad // ti, c_pad // tc)
    out = pl.pallas_call(
        functools.partial(_far_kernel, kr=kr, ti=ti, tc=tc),
        name="grid_far_field",
        grid=grid,
        in_specs=[
            pl.BlockSpec((ti, 2), lambda i, j: (i, 0)),
            pl.BlockSpec((ti, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((ti, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((tc, 2), lambda i, j: (j, 0)),
            pl.BlockSpec((tc, 1), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((ti, 2), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, 2), jnp.float32),
        compiler_params=CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(pos_p, mass_p, cell_p, cent_p, cmass_p)
    return out[:n]


def _near_kernel(prev_ref, cur_ref, next_ref, out_ref,
                 *, kr: float, ti: int, window: int, nt: int):
    i = pl.program_id(0)

    xi = cur_ref[:, 0:1]  # [TI, 1]
    yi = cur_ref[:, 1:2]
    mi = cur_ref[:, 2:3]
    ci = cur_ref[:, 3:4]
    # Row of the three neighbor tiles along lanes: [1, 3·TI].
    xj = jnp.concatenate(
        [prev_ref[:, 0:1].T, cur_ref[:, 0:1].T, next_ref[:, 0:1].T], axis=1)
    yj = jnp.concatenate(
        [prev_ref[:, 1:2].T, cur_ref[:, 1:2].T, next_ref[:, 1:2].T], axis=1)
    mj = jnp.concatenate(
        [prev_ref[:, 2:3].T, cur_ref[:, 2:3].T, next_ref[:, 2:3].T], axis=1)
    cj = jnp.concatenate(
        [prev_ref[:, 3:4].T, cur_ref[:, 3:4].T, next_ref[:, 3:4].T], axis=1)

    dx = xi - xj  # [TI, 3TI]
    dy = yi - yj
    d2 = dx * dx + dy * dy

    # Global sorted indices: rows live in tile i, columns span tiles
    # (i−1, i, i+1). Edge tiles load a clamped duplicate block; the seg
    # masks kill it (there is no tile −1 / nt).
    cols = jax.lax.broadcasted_iota(jnp.int32, (ti, 3 * ti), 1)
    gi = i * ti + jax.lax.broadcasted_iota(jnp.int32, (ti, 3 * ti), 0)
    gj = (i - 1) * ti + cols
    seg = cols // ti
    edge_ok = jnp.logical_not(
        ((i == 0) & (seg == 0)) | ((i == nt - 1) & (seg == 2))
    )
    band = (gj >= gi - window) & (gj <= gi + window) & (gj != gi)
    ok = edge_ok & band & (cj == ci) & (cj >= 0)  # cell −1 = padding
    mag = jnp.where(ok, kr * mi * mj / jnp.maximum(d2, EPS2), 0.0)

    out_ref[...] = jnp.concatenate(
        [jnp.sum(mag * dx, axis=1, keepdims=True),
         jnp.sum(mag * dy, axis=1, keepdims=True)], axis=1)


@functools.partial(jax.jit, static_argnames=("kr", "window", "ti", "interpret"))
def near_field_pallas(
    pos_s: jnp.ndarray,  # [n, 2] f32, cell-sorted order
    mass_s: jnp.ndarray,  # [n] f32, cell-sorted
    cell_s: jnp.ndarray,  # [n] int32, sorted
    kr: float,
    window: int,
    ti: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    """Banded same-cell near field over the sorted order → [n, 2] (sorted).

    Same band semantics as ``ref.near_field_ref``. The tile size is raised
    to cover the window when needed (the 3-tile overlap covers ±W only
    for W ≤ TI).
    """
    n = pos_s.shape[0]
    ti = max(ti, ((window + 7) // 8) * 8)
    n_pad = _pad_to(n, ti)
    nt = n_pad // ti
    npad = (0, n_pad - n)
    # Packed (x, y, mass, cell): cell ids are exact in f32 up to 2²⁴ —
    # far beyond any practical G². Padding: mass 0, cell −1.
    packed = jnp.concatenate(
        [
            jnp.pad(pos_s.astype(jnp.float32), (npad, (0, 0))),
            jnp.pad(mass_s.astype(jnp.float32), npad)[:, None],
            jnp.pad(cell_s.astype(jnp.float32), npad, constant_values=-1.0)[:, None],
        ],
        axis=1,
    )
    out = pl.pallas_call(
        functools.partial(_near_kernel, kr=kr, ti=ti, window=window, nt=nt),
        name="grid_near_field",
        grid=(nt,),
        in_specs=[
            pl.BlockSpec((ti, 4), lambda i: (jnp.maximum(i - 1, 0), 0)),
            pl.BlockSpec((ti, 4), lambda i: (i, 0)),
            pl.BlockSpec((ti, 4), lambda i: (jnp.minimum(i + 1, nt - 1), 0)),
        ],
        out_specs=pl.BlockSpec((ti, 2), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, 2), jnp.float32),
        compiler_params=CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        interpret=interpret,
    )(packed, packed, packed)
    return out[:n]
