"""Pallas TPU kernel: sorted-merge scatter-combine for superedge aggregation.

The merge-path ranks (where each input row's key lands in the merged
output) are cheap vectorized binary searches and stay in XLA
(``ref.merge_positions``); what XLA does poorly on TPU is the scatter
itself. This kernel is the scatter, and it exploits the one structural
fact the lexsort baseline throws away: both input runs are sorted, so
their output positions are monotone and every fixed-size input block
touches one contiguous band of output tiles. The grid enumerates
(out_tiles × in_blocks) like ``kernels/segment``, but a block's position
bounds skip every non-overlapping pair with ``pl.when``, so the work per
update is O(rows) mask-reductions instead of O(rows × tiles).

Weights accumulate by +, keys by max (each live output slot is hit by
exactly one key value — a state row, a chunk row, or both with equal
keys — so max is exact placement, and unhit slots stay at the -1 init).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu  # noqa: F401

from repro.kernels.compat import CompilerParams
from repro.kernels.merge.ref import SENTINEL, merge_positions, pack_keys

_INT32_MAX = jnp.iinfo(jnp.int32).max


def _kernel(pos_ref, a_ref, b_ref, w_ref, oa_ref, ob_ref, ow_ref, *, tn: int, blk: int):
    t = pl.program_id(0)
    b = pl.program_id(1)

    @pl.when(b == 0)
    def _init():
        oa_ref[...] = jnp.full_like(oa_ref[...], -1)
        ob_ref[...] = jnp.full_like(ob_ref[...], -1)
        ow_ref[...] = jnp.zeros_like(ow_ref[...])

    pos = pos_ref[0, :]  # [blk], sorted within the block
    base = t * tn
    # Sorted block ⇒ its output span is [pos[0], pos[blk-1]]; skip tiles
    # outside it (this is where sortedness buys the linear-work scatter).
    overlap = (pos[blk - 1] >= base) & (pos[0] < base + tn)

    @pl.when(overlap)
    def _scatter():
        local = pos - base
        rows = jax.lax.broadcasted_iota(jnp.int32, (tn, blk), 0)
        hit = rows == local[None, :]
        ow_ref[0, :] += jnp.sum(
            jnp.where(hit, w_ref[0, :][None, :], 0.0), axis=1
        )
        oa_ref[0, :] = jnp.maximum(
            oa_ref[0, :], jnp.max(jnp.where(hit, a_ref[0, :][None, :], -1), axis=1)
        )
        ob_ref[0, :] = jnp.maximum(
            ob_ref[0, :], jnp.max(jnp.where(hit, b_ref[0, :][None, :], -1), axis=1)
        )


@functools.partial(
    jax.jit, static_argnames=("cap", "tn", "blk", "interpret")
)
def scatter_combine_pallas(
    pos: jnp.ndarray,  # [N] int32 output positions, sorted per blk-block
    a: jnp.ndarray,  # [N] int32
    b: jnp.ndarray,  # [N] int32
    w: jnp.ndarray,  # [N] float32
    cap: int,
    tn: int = 512,
    blk: int = 512,
    interpret: bool = False,
):
    """Place rows at their output positions: w by +, keys by max.

    ``pos`` must be sorted within every ``blk``-sized block (not globally);
    rows with ``pos ≥ cap`` land in the sliced-off pad region or miss every
    tile. Unhit slots return keys -1 and weight 0.
    """
    n = pos.shape[0]
    n_pad = ((n + blk - 1) // blk) * blk
    cap_pad = ((cap + tn - 1) // tn) * tn
    pad = (0, n_pad - n)
    # INT32_MAX pad keeps the tail block sorted and outside every tile.
    pos_p = jnp.pad(pos, pad, constant_values=_INT32_MAX)[None, :]
    a_p = jnp.pad(a, pad, constant_values=-1)[None, :]
    b_p = jnp.pad(b, pad, constant_values=-1)[None, :]
    w_p = jnp.pad(w, pad)[None, :]
    grid = (cap_pad // tn, n_pad // blk)
    spec_in = pl.BlockSpec((1, blk), lambda t, b: (0, b))
    # Outputs are one [1, cap_pad] row blocked (1, tn): the TPU lowering
    # wants a block's last two dims divisible by (8, 128) or equal to the
    # array's, which a (1, tn) block of a [tiles, tn] array is not.
    spec_out = pl.BlockSpec((1, tn), lambda t, b: (0, t))
    oa, ob, ow = pl.pallas_call(
        functools.partial(_kernel, tn=tn, blk=blk),
        name="merge_scatter_combine",
        grid=grid,
        in_specs=[spec_in] * 4,
        out_specs=[spec_out] * 3,
        out_shape=(
            jax.ShapeDtypeStruct((1, cap_pad), jnp.int32),
            jax.ShapeDtypeStruct((1, cap_pad), jnp.int32),
            jax.ShapeDtypeStruct((1, cap_pad), jnp.float32),
        ),
        compiler_params=CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
    )(pos_p, a_p, b_p, w_p)
    return oa[0, :cap], ob[0, :cap], ow[0, :cap]


def _pad_block(pos, a, b, w, blk: int):
    """Pad one sorted run to a block multiple so concatenated runs keep
    every block internally sorted (pad positions sort last)."""
    m = pos.shape[0]
    m_pad = ((m + blk - 1) // blk) * blk
    pad = (0, m_pad - m)
    return (
        jnp.pad(pos, pad, constant_values=_INT32_MAX),
        jnp.pad(a, pad, constant_values=-1),
        jnp.pad(b, pad, constant_values=-1),
        jnp.pad(w, pad),
    )


@functools.partial(
    jax.jit, static_argnames=("s_cap", "tn", "blk", "interpret")
)
def merge_combine_pallas(
    sa: jnp.ndarray,
    sb: jnp.ndarray,
    sw: jnp.ndarray,
    ca: jnp.ndarray,
    cb: jnp.ndarray,
    cw: jnp.ndarray,
    s_cap: int,
    tn: int = 512,
    blk: int = 512,
    interpret: bool = False,
):
    """Pallas counterpart of ``ref.merge_combine_ref`` (same contract)."""
    cap = sa.shape[0]
    sk = pack_keys(sa, sb, s_cap)
    ck = pack_keys(ca, cb, s_cap)
    pos_s, pos_c, new_c = merge_positions(sk, ck)
    parts = [
        _pad_block(pos_s, sa, sb, sw, blk),
        _pad_block(pos_c, ca, cb, cw, blk),
    ]
    pos, a, b, w = (jnp.concatenate(cols) for cols in zip(*parts))
    oa, ob, ow = scatter_combine_pallas(
        pos, a, b, w, cap, tn=tn, blk=blk, interpret=interpret
    )
    oa = jnp.where(oa < 0, s_cap, oa)
    ob = jnp.where(ob < 0, s_cap, ob)
    n = (jnp.sum(sk != SENTINEL) + jnp.sum(new_c)).astype(jnp.int32)
    return oa, ob, ow, n
