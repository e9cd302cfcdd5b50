"""Pallas TPU kernel: sorted-merge scatter-combine for superedge aggregation.

The merge-path ranks (where each input row's key lands in the merged
output) are cheap vectorized binary searches and stay in XLA
(``ref.merge_positions``); what XLA does poorly on TPU is the scatter
itself. This kernel is the scatter, and it exploits the one structural
fact the lexsort baseline throws away: both input runs are sorted, so
each run's output positions are non-decreasing, its valid positions
strictly increase, and an output tile of ``tn`` slots receives at most
``tn`` rows of each run. Those rows fill at most ``band = ceil(tn/blk) +
1`` consecutive ``blk`` blocks of the run, starting at the first block
whose last position reaches the tile. XLA finds that block per (tile,
run) with a ``searchsorted`` over the blocks' last positions; the table
is scalar-prefetched and the grid is (out_tiles × 2 runs × band), so a
merge takes O(capacity / tn) grid steps and block copies, not the
O(capacity × rows / (tn · blk)) of a dense (out_tiles × in_blocks) grid.

Weights accumulate by +, keys by max (each live output slot is hit by
exactly one key value — a state row, a chunk row, or both with equal
keys — so max is exact placement, and unhit slots stay at the -1 init).
The state band is visited before the chunk band, so a slot's weight is
``0 + sw + cw``, the order of ``ref.merge_combine_ref``'s two scatters.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.compat import CompilerParams
from repro.kernels.merge.ref import SENTINEL, merge_positions, pack_keys
from repro.obs.metrics import REGISTRY

_INT32_MAX = jnp.iinfo(jnp.int32).max


def _kernel(start_ref, pos_ref, a_ref, b_ref, w_ref, oa_ref, ob_ref, ow_ref,
            *, tn: int, blk: int, n_state: int, n_chunk: int):
    t = pl.program_id(0)
    r = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when((r == 0) & (k == 0))
    def _init():
        oa_ref[...] = jnp.full_like(oa_ref[...], -1)
        ob_ref[...] = jnp.full_like(ob_ref[...], -1)
        ow_ref[...] = jnp.zeros_like(ow_ref[...])

    pos = pos_ref[0, :]  # [blk], non-decreasing
    base = t * tn
    # A step past its run's last block re-reads that block (the index map
    # clamps it) and must not add it again.
    in_run = start_ref[2 * t + r] + k < n_state + r * n_chunk
    overlap = in_run & (pos[blk - 1] >= base) & (pos[0] < base + tn)

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


def _pad_block(pos, a, b, w, blk: int):
    """Pad one run to a block multiple; pad positions sort last and miss
    every tile."""
    m = pos.shape[0]
    pad = (0, -m % blk)
    return (
        jnp.pad(pos, pad, constant_values=_INT32_MAX),
        jnp.pad(a, pad, constant_values=-1),
        jnp.pad(b, pad, constant_values=-1),
        jnp.pad(w, pad),
    )


@functools.partial(
    jax.jit, static_argnames=("cap", "tn", "blk", "interpret")
)
def scatter_combine_pallas(
    state: tuple,  # (pos, a, b, w), each [cap]: int32 ×3, float32
    chunk: tuple,  # (pos, a, b, w), each [C]
    cap: int,
    tn: int = 512,
    blk: int = 512,
    interpret: bool = False,
):
    """Place both runs' rows at their output positions: w by +, keys by max.

    Each run's ``pos`` must be non-decreasing, and strictly increasing
    below ``cap``: at most ``tn`` of its rows land in any ``tn``-slot tile
    (``ref.merge_positions`` gives both runs this). Rows with
    ``pos ≥ cap`` land in the sliced-off pad region or miss every tile.
    Unhit slots return keys -1 and weight 0.
    """
    state = _pad_block(*state, blk)
    chunk = _pad_block(*chunk, blk)
    n_state = state[0].shape[0] // blk
    n_chunk = chunk[0].shape[0] // blk
    tiles = -(-cap // tn)
    band = -(-tn // blk) + 1
    # start[2t + r]: the first block of run r (numbered across both runs,
    # the chunk's after the state's) whose last position reaches tile t;
    # n_state + r * n_chunk when none does.
    bases = jnp.arange(tiles, dtype=jnp.int32) * tn
    start = jnp.stack(
        [
            jnp.searchsorted(state[0][blk - 1 :: blk], bases, side="left"),
            n_state
            + jnp.searchsorted(chunk[0][blk - 1 :: blk], bases, side="left"),
        ],
        axis=1,
    ).astype(jnp.int32).reshape(-1)
    pos, a, b, w = (
        jnp.concatenate([s, c])[None, :] for s, c in zip(state, chunk)
    )
    grid = (tiles, 2, band)
    REGISTRY.gauge("merge.grid_steps").set(tiles * 2 * band)
    REGISTRY.gauge("merge.dense_grid_steps").set(tiles * (n_state + n_chunk))

    def in_block(t, r, k, start):
        last = n_state + r * n_chunk - 1
        return 0, jnp.minimum(start[2 * t + r] + k, last)

    spec_in = pl.BlockSpec((1, blk), in_block)
    # Outputs are one [1, cap_pad] row blocked (1, tn): the TPU lowering
    # wants a block's last two dims divisible by (8, 128) or equal to the
    # array's, which a (1, tn) block of a [tiles, tn] array is not.
    spec_out = pl.BlockSpec((1, tn), lambda t, r, k, start: (0, t))
    oa, ob, ow = pl.pallas_call(
        functools.partial(
            _kernel, tn=tn, blk=blk, n_state=n_state, n_chunk=n_chunk
        ),
        name="merge_scatter_combine",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[spec_in] * 4,
            out_specs=[spec_out] * 3,
        ),
        out_shape=(
            jax.ShapeDtypeStruct((1, tiles * tn), jnp.int32),
            jax.ShapeDtypeStruct((1, tiles * tn), jnp.int32),
            jax.ShapeDtypeStruct((1, tiles * tn), jnp.float32),
        ),
        compiler_params=CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")
        ),
        interpret=interpret,
    )(start, pos, a, b, w)
    return oa[0, :cap], ob[0, :cap], ow[0, :cap]


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
    oa, ob, ow = scatter_combine_pallas(
        (pos_s, sa, sb, sw), (pos_c, ca, cb, cw), cap,
        tn=tn, blk=blk, interpret=interpret,
    )
    oa = jnp.where(oa < 0, s_cap, oa)
    ob = jnp.where(ob < 0, s_cap, ob)
    n = (jnp.sum(sk != SENTINEL) + jnp.sum(new_c)).astype(jnp.int32)
    return oa, ob, ow, n
