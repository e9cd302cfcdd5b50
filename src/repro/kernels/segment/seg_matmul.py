"""Pallas TPU kernel: segment-sum as one-hot matmul (message passing / GNN
scatter and supergraph aggregation share this primitive).

GPU graph frameworks scatter edge messages with atomicAdd; the TPU
adaptation reformulates a block of E edge messages aggregating into an
N-node tile as

    out[t] += onehot(seg)ᵀ @ msgs       ([TN, B]·[B, D] matmul → MXU)

Grid = (node_tiles, edge_blocks): node axis parallel, edge axis revisits
and accumulates the same output tile. Messages stream once per node tile;
the one-hot never leaves VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu  # noqa: F401

from repro.kernels.compat import CompilerParams


def _kernel(seg_ref, data_ref, o_ref, *, tn: int, blk: int):
    t = pl.program_id(0)
    b = pl.program_id(1)

    @pl.when(b == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    seg = seg_ref[0, :]  # [blk]
    local = seg - t * tn  # position inside this node tile (or out of range)
    row_ids = jax.lax.broadcasted_iota(jnp.int32, (tn, blk), 0)
    onehot = jnp.where(row_ids == local[None, :], 1.0, 0.0)  # [tn, blk]
    # Accumulate in f32 regardless of input dtype (production practice);
    # the wrapper casts back once at the end.
    # HIGHEST: a single bf16 MXU pass would round the f32 messages.
    o_ref[...] += jnp.dot(
        onehot, data_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )


@functools.partial(jax.jit, static_argnames=("n_segments", "tn", "blk", "interpret"))
def segment_sum_pallas(
    data: jnp.ndarray,  # [E, D]
    seg_ids: jnp.ndarray,  # [E] int32 (out of [0, n_segments) = dropped)
    n_segments: int,
    tn: int = 256,
    blk: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    e, d = data.shape
    e_pad = ((e + blk - 1) // blk) * blk
    n_pad = ((n_segments + tn - 1) // tn) * tn
    data_p = jnp.pad(data, ((0, e_pad - e), (0, 0)))
    seg_p = jnp.pad(seg_ids, (0, e_pad - e), constant_values=-1)[None, :]
    grid = (n_pad // tn, e_pad // blk)
    out = pl.pallas_call(
        functools.partial(_kernel, tn=tn, blk=blk),
        name="segment_sum",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, blk), lambda t, b: (0, b)),
            pl.BlockSpec((blk, d), lambda t, b: (b, 0)),
        ],
        out_specs=pl.BlockSpec((tn, d), lambda t, b: (t, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, d), jnp.float32),
        compiler_params=CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(seg_p, data_p)
    return out[:n_segments].astype(data.dtype)
