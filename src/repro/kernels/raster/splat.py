"""Pallas TPU kernels for the rasterization family (render/).

``count_scatter_pallas`` — the edge-splat scatter. XLA's gather/scatter is
the weak spot on TPU, so the wrapper sorts the sample positions once
(cheap, vectorized) and the kernel scatters the sorted run a (tile, block)
pair at a time: each step compares one ``blk`` block of positions with one
``tn``-slot output tile as [tn, blk] masks, O(rows) mask-reductions instead
of O(rows × tiles). Counts accumulate in int32 — exact and
order-independent, which is what makes the renderer's chunked==one-shot
contract bit-exact.

The grid walks only the pairs that can overlap. After the sort, block b
spans the tiles from ``tile(pos[b·blk])`` to ``tile(pos[b·blk+blk−1])``,
and consecutive blocks share at most one tile, so the overlapping pairs lie
on one staircase, monotone in tile and in block. XLA builds that walk from
the blocks' first and last positions alone — each tile's range of blocks
by two ``searchsorted`` calls, at least one step per tile so that every
tile is seeded from the accumulator — as two int32 step tables
(``tile_of``, ``block_of``), scalar-prefetched into the index maps. The
grid is (tiles + blocks,): steps past the walk's end repeat its last pair
and add nothing. A dense (tiles × blocks) grid would take tiles × blocks
steps, nearly all of them skipped but each still paid for. The fixed band
of ``kernels/merge`` (ceil(tn/blk) + 1 blocks a tile) does not apply:
merge positions are unique, splat positions repeat, so a hot pixel or a
dense cluster of disks puts any number of blocks into one tile.

``disk_accum_pallas`` — per-pixel disk coverage as a one-hot matmul (the
``kernels/segment`` trick pointed at the image plane): for an image tile
of TP flattened pixels and a block of BLK nodes, the [BLK, TP] inside-disk
mask contracts with the [G, BLK] one-hot of color groups on the MXU,
accumulating [G, TP] per-channel coverage. Pixel coordinates are
precomputed host-side and streamed per tile, so the kernel does no
integer div/mod. Masks are the same float32 ops as the ref path — parity
is bit-exact.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.compat import CompilerParams
from repro.obs.metrics import REGISTRY

_INT32_MAX = jnp.iinfo(jnp.int32).max


def _scatter_kernel(tile_ref, block_ref, acc_ref, pos_ref, w_ref, o_ref,
                    *, tn: int, blk: int):
    s = pl.program_id(0)
    t = tile_ref[s]
    prev = jnp.maximum(s - 1, 0)
    new_tile = (s == 0) | (tile_ref[prev] != t)

    @pl.when(new_tile)
    def _init():
        # Seed each output tile from the carried-in accumulator (aliased
        # to the output buffer, so the combine is in place in HBM).
        o_ref[...] = acc_ref[...]

    pos = pos_ref[0, :]  # [blk], sorted within the block
    base = t * tn
    # Steps past the walk's end repeat its last pair; a tile no block
    # reaches gets one step whose block misses it.
    repeat = ~new_tile & (block_ref[prev] == block_ref[s])
    overlap = ~repeat & (pos[blk - 1] >= base) & (pos[0] < base + tn)

    @pl.when(overlap)
    def _scatter():
        local = pos - base
        rows = jax.lax.broadcasted_iota(jnp.int32, (tn, blk), 0)
        hit = rows == local[None, :]
        o_ref[0, :] += jnp.sum(
            jnp.where(hit, w_ref[0, :][None, :], 0), axis=1
        )


def _walk(pos_p: jnp.ndarray, tiles: int, tn: int, blk: int):
    """Step tables (tile_of, block_of), each [tiles + blocks] int32, of the
    monotone walk over the (tile, block) pairs a sorted run can overlap.

    Reads only the blocks' first and last positions. Tile t gets
    max(1, overlapping blocks) consecutive steps, which sum to at most
    tiles + blocks − 1; later steps repeat the last pair.
    """
    blocks = pos_p.shape[0] // blk
    bases = jnp.arange(tiles, dtype=jnp.int32) * tn
    # lo[t]: the first block whose last position reaches tile t; hi[t]: the
    # first block that starts past it. Blocks lo[t]..hi[t]-1 overlap it.
    lo = jnp.searchsorted(pos_p[blk - 1 :: blk], bases, side="left")
    hi = jnp.searchsorted(pos_p[::blk], bases + tn, side="left")
    lo, hi = lo.astype(jnp.int32), hi.astype(jnp.int32)
    n_steps = jnp.maximum(hi - lo, 1)
    ends = jnp.cumsum(n_steps)
    s = jnp.arange(tiles + blocks, dtype=jnp.int32)
    tile_of = jnp.minimum(
        jnp.searchsorted(ends, s, side="right").astype(jnp.int32), tiles - 1
    )
    k = jnp.minimum(s - (ends - n_steps)[tile_of], n_steps[tile_of] - 1)
    block_of = jnp.minimum(lo[tile_of] + k, blocks - 1)
    return tile_of, block_of


@functools.partial(jax.jit, static_argnames=("size", "tn", "blk", "interpret"))
def count_scatter_pallas(
    pos: jnp.ndarray,  # [N] int32 flat positions (out of range = dropped)
    inc: jnp.ndarray,  # [N] int32 increments
    size: int,
    acc: jnp.ndarray | None = None,  # [size] int32 to accumulate into
    tn: int = 2048,
    blk: int = 1024,
    interpret: bool = False,
) -> jnp.ndarray:
    """Pallas counterpart of ``ref.count_scatter_ref`` (same contract).

    With ``acc`` the kernel accumulates into it in place (the buffer is
    aliased input→output), the counterpart of ``count_scatter_into_ref``
    — no second image-sized buffer or separate add on the streamed path.
    """
    # Negative positions would break the per-block band test after the
    # sort, so remap them onto the dropped marker before ordering.
    pos = jnp.where(pos < 0, _INT32_MAX, pos)
    order = jnp.argsort(pos)
    pos_s = pos[order]
    inc_s = inc[order]
    n = pos.shape[0]
    n_pad = ((n + blk - 1) // blk) * blk
    size_pad = ((size + tn - 1) // tn) * tn
    # INT32_MAX pad keeps the tail block sorted and outside every tile.
    pos_s = jnp.pad(pos_s, (0, n_pad - n), constant_values=_INT32_MAX)
    inc_p = jnp.pad(inc_s, (0, n_pad - n))[None, :]
    # The accumulator is one [1, size_pad] row blocked (1, tn) — the TPU
    # lowering's block rule (as in kernels/merge).
    if acc is None:
        acc2d = jnp.zeros((1, size_pad), jnp.int32)
    else:
        acc2d = jnp.pad(acc, (0, size_pad - size))[None, :]
    tiles, blocks = size_pad // tn, n_pad // blk
    tile_of, block_of = _walk(pos_s, tiles, tn, blk)
    REGISTRY.gauge("raster.grid_steps").set(tiles + blocks)
    REGISTRY.gauge("raster.dense_grid_steps").set(tiles * blocks)
    spec_tile = pl.BlockSpec((1, tn), lambda s, t_of, b_of: (0, t_of[s]))
    spec_block = pl.BlockSpec((1, blk), lambda s, t_of, b_of: (0, b_of[s]))
    out = pl.pallas_call(
        functools.partial(_scatter_kernel, tn=tn, blk=blk),
        name="raster_count_scatter",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(tiles + blocks,),
            in_specs=[spec_tile, spec_block, spec_block],
            out_specs=spec_tile,
        ),
        out_shape=jax.ShapeDtypeStruct((1, size_pad), jnp.int32),
        # Operand 2 (after the two step tables) is the accumulator.
        input_output_aliases={2: 0},
        # One axis: a tile's steps must stay consecutive.
        compiler_params=CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(tile_of, block_of, acc2d, pos_s[None, :], inc_p)
    return out[0, :size]


def _disk_kernel(px_ref, py_ref, cx_ref, cy_ref, r_ref, g_ref, o_ref, *, gp: int, blk: int):
    b = pl.program_id(1)

    @pl.when(b == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref[...])

    px = px_ref[0, :]  # [tp] pixel x coords of this image tile
    py = py_ref[0, :]
    cx = cx_ref[0, :]  # [blk] node block
    cy = cy_ref[0, :]
    r = r_ref[0, :]
    g = g_ref[0, :]
    dx = px[None, :] - cx[:, None]  # [blk, tp]
    dy = py[None, :] - cy[:, None]
    inside = (dx * dx + dy * dy) <= (r * r)[:, None]
    inside = inside & (r[:, None] > 0)
    groups = jax.lax.broadcasted_iota(jnp.int32, (gp, blk), 0)
    onehot = jnp.where(groups == g[None, :], 1.0, 0.0)  # [gp, blk]
    o_ref[...] += jnp.dot(
        onehot, inside.astype(jnp.float32), preferred_element_type=jnp.float32
    )


@functools.partial(
    jax.jit, static_argnames=("n_groups", "h", "w", "tp", "blk", "interpret")
)
def disk_accum_pallas(
    cx: jnp.ndarray,  # [n] float32 pixel-space centers
    cy: jnp.ndarray,  # [n] float32
    r: jnp.ndarray,  # [n] float32 pixel radii (≤ 0 = skip)
    group: jnp.ndarray,  # [n] int32 color group (out of range = skip)
    n_groups: int,
    h: int,
    w: int,
    tp: int = 1024,
    blk: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """Pallas counterpart of ``ref.disk_accum_ref`` (same contract)."""
    n = cx.shape[0]
    n_pad = ((n + blk - 1) // blk) * blk
    p = h * w
    p_pad = ((p + tp - 1) // tp) * tp
    gp = max(8, ((n_groups + 7) // 8) * 8)  # sublane-aligned channel dim
    flat = jnp.arange(p_pad, dtype=jnp.int32)
    px = (flat % w).astype(jnp.float32)[None, :]
    py = (flat // w).astype(jnp.float32)[None, :]
    npad = (0, n_pad - n)
    cx_p = jnp.pad(cx, npad)[None, :]
    cy_p = jnp.pad(cy, npad)[None, :]
    r_p = jnp.pad(r, npad)[None, :]  # pad radius 0 ⇒ dead
    g_p = jnp.pad(group, npad, constant_values=-1)[None, :]
    grid = (p_pad // tp, n_pad // blk)
    out = pl.pallas_call(
        functools.partial(_disk_kernel, gp=gp, blk=blk),
        name="raster_disk_accum",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, tp), lambda t, b: (0, t)),
            pl.BlockSpec((1, tp), lambda t, b: (0, t)),
            pl.BlockSpec((1, blk), lambda t, b: (0, b)),
            pl.BlockSpec((1, blk), lambda t, b: (0, b)),
            pl.BlockSpec((1, blk), lambda t, b: (0, b)),
            pl.BlockSpec((1, blk), lambda t, b: (0, b)),
        ],
        out_specs=pl.BlockSpec((gp, tp), lambda t, b: (0, t)),
        out_shape=jax.ShapeDtypeStruct((gp, p_pad), jnp.float32),
        compiler_params=CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(px, py, cx_p, cy_p, r_p, g_p)
    # Coverage counts are small integers, exact in f32 — cast is lossless.
    return out.astype(jnp.int32)[:n_groups, :p].reshape(n_groups, h, w)
