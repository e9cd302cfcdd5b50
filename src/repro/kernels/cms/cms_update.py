"""Pallas TPU kernel: count–min sketch update as one-hot × matmul.

GPU BigGraphVis updates the sketch with atomicAdd — random-access writes.
The TPU adaptation (DESIGN.md §2) converts a block of B hashed keys into a
one-hot [B, TC] matrix per row and column tile and accumulates

    sketch[r, tile] += wᵀ @ onehot(h[r] − tile·TC)    ([1,B]·[B,TC] → MXU)

Grid = (column tiles, key blocks): the column axis is parallel, the key
axis revisits and accumulates the same [rows, TC] output block. Tiling the
columns keeps the one-hot at B·TC·4 bytes of VMEM whatever the sketch
width (34,681 columns at the LiveJournal shape would otherwise need a
[B, 34,681] one-hot per step). Each hash row accumulates into its own
static row slice of the output block — the TPU lowering has no in-kernel
scatter.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu  # noqa: F401

from repro.kernels.compat import CompilerParams


def _kernel(h_ref, w_ref, o_ref, *, rows: int, tc: int, blk: int):
    c = pl.program_id(0)

    @pl.when(pl.program_id(1) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    w = w_ref[0, :]  # [blk]
    wv = jnp.where(h_ref[0, :] >= 0, w, 0.0)  # padding mask (h<0)
    col_ids = c * tc + jax.lax.broadcasted_iota(jnp.int32, (blk, tc), 1)
    for r in range(rows):  # rows ≤ 4: unrolled
        h = h_ref[r, :]  # [blk]
        onehot = jnp.where(col_ids == h[:, None], 1.0, 0.0)  # [blk, tc]
        # HIGHEST: a single bf16 MXU pass would round degree weights > 256.
        o_ref[r:r + 1, :] += jnp.dot(
            wv[None, :], onehot, preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )  # [1, tc] on the MXU


@functools.partial(jax.jit, static_argnames=("cols", "tc", "blk", "interpret"))
def cms_update_pallas(
    sketch: jnp.ndarray,  # [rows, cols] f32
    h: jnp.ndarray,  # [rows, n] int32 bucket ids (negative = padding)
    w: jnp.ndarray,  # [n] f32
    cols: int,
    tc: int = 1024,
    blk: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    rows, n = h.shape
    assert sketch.shape == (rows, cols)
    tc = min(tc, ((cols + 127) // 128) * 128)
    cols_pad = ((cols + tc - 1) // tc) * tc
    n_pad = ((n + blk - 1) // blk) * blk
    h_p = jnp.pad(h, ((0, 0), (0, n_pad - n)), constant_values=-1)
    w_p = jnp.pad(w, (0, n_pad - n))[None, :]  # [1, n_pad]
    grid = (cols_pad // tc, n_pad // blk)
    delta = pl.pallas_call(
        functools.partial(_kernel, rows=rows, tc=tc, blk=blk),
        name="cms_update",
        grid=grid,
        in_specs=[
            pl.BlockSpec((rows, blk), lambda c, i: (0, i)),
            pl.BlockSpec((1, blk), lambda c, i: (0, i)),
        ],
        out_specs=pl.BlockSpec((rows, tc), lambda c, i: (0, c)),
        out_shape=jax.ShapeDtypeStruct((rows, cols_pad), jnp.float32),
        compiler_params=CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(h_p, w_p)
    return sketch + delta[:, :cols]
