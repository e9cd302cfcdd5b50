"""Pallas TPU kernels: sorted-merge ranks and scatter-combine for superedge
aggregation.

``merge_ranks`` gives the merge-path ranks (where each input row's key
lands in the merged output). XLA's ``jnp.searchsorted`` (``ref``'s path)
spends one round of random gathers per level of its binary search, ~20
dependent rounds a query at the cells' sizes, and never uses the fact
that the queries are sorted too. The kernel ranks one sorted run against
the other a (query block, table block) pair at a time: each step compares
a ``[bt, bq]`` block pair on the VPU and adds int32 counts into the query
block's output tile; table blocks wholly below a query block count
wholesale from a block prefix. The pairs whose key ranges overlap lie on
one staircase, monotone in both block indices, of at most q_blocks +
t_blocks − 1 steps; XLA builds it from the blocks' first and last keys as
two scalar-prefetched step tables (the construction of
``raster/splat._walk``, with a block prefix per query block).

``scatter_combine_pallas`` is the scatter. It exploits the same fact,
which the lexsort baseline throws away: both input runs are sorted, so
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
from repro.kernels.merge.ref import SENTINEL, pack_keys
from repro.obs.metrics import REGISTRY

_INT32_MAX = jnp.iinfo(jnp.int32).max


def _ranks_kernel(q_of_ref, t_of_ref, base_ref, q_ref, t_ref, w_ref,
                  cnt_ref, eq_ref):
    s = pl.program_id(0)
    qi = q_of_ref[s]
    prev = jnp.maximum(s - 1, 0)
    new_block = (s == 0) | (q_of_ref[prev] != qi)

    @pl.when(new_block)
    def _init():
        # Table blocks below the walk's first are counted wholesale.
        cnt_ref[...] = jnp.full_like(cnt_ref[...], base_ref[qi])
        eq_ref[...] = jnp.zeros_like(eq_ref[...])

    # Steps past the walk's end repeat its last pair and must not add it
    # again.
    repeat = ~new_block & (t_of_ref[prev] == t_of_ref[s])

    @pl.when(~repeat)
    def _compare():
        q = q_ref[0, :][None, :]  # [1, bq]
        t = t_ref[0, :][:, None]  # [bt, 1]
        below = jnp.where(t < q, w_ref[0, :][:, None], 0)  # [bt, bq]
        cnt_ref[0, :] += jnp.sum(below, axis=0)
        eq_ref[0, :] = jnp.maximum(
            eq_ref[0, :], jnp.max(jnp.where(t == q, 1, 0), axis=0)
        )


def _rank_walk_len(m: int, n: int, bq: int, bt: int) -> int:
    """Grid of ``merge_ranks`` for [m] queries into an [n] table."""
    return -(-m // bq) + -(-n // bt) - 1


def _rank_walk(qp, tp, n_valid, bq: int, bt: int):
    """Step tables (q_of, t_of) of the walk, each [q_blocks + t_blocks − 1]
    int32, and lo [q_blocks]: the walk's first table block per query block.

    Reads only the blocks' first and last keys. A query block's band runs
    from the first table block whose last key reaches its first key to the
    last table block that starts at or below its last valid key; a block
    with no valid key gets a band of one. Consecutive bands share at most
    one block (valid query keys are unique and the table is sorted), so
    the walk, max(1, band) steps a query block, fits the grid; later steps
    repeat the last pair.
    """
    q_blocks, t_blocks = qp.shape[0] // bq, tp.shape[0] // bt
    starts = jnp.arange(q_blocks, dtype=jnp.int32) * bq
    last_valid = qp[jnp.minimum(starts + bq - 1, jnp.maximum(n_valid - 1, 0))]
    lo = jnp.searchsorted(tp[bt - 1 :: bt], qp[::bq], side="left")
    hi = jnp.searchsorted(tp[::bt], last_valid, side="right")
    lo = jnp.minimum(lo.astype(jnp.int32), t_blocks - 1)
    hi = jnp.where(starts < n_valid, hi.astype(jnp.int32), lo)
    n_steps = jnp.maximum(hi - lo, 1)
    ends = jnp.cumsum(n_steps)
    s = jnp.arange(q_blocks + t_blocks - 1, dtype=jnp.int32)
    q_of = jnp.minimum(
        jnp.searchsorted(ends, s, side="right").astype(jnp.int32), q_blocks - 1
    )
    k = jnp.minimum(s - (ends - n_steps)[q_of], n_steps[q_of] - 1)
    return q_of, lo[q_of] + k, lo


def _signed(key: jnp.ndarray) -> jnp.ndarray:
    """uint32 keys → int32 in the same order (Mosaic compares signed)."""
    return jax.lax.bitcast_convert_type(key ^ jnp.uint32(0x80000000), jnp.int32)


@functools.partial(jax.jit, static_argnames=("bq", "bt", "interpret"))
def merge_ranks(
    q: jnp.ndarray,  # [M] uint32, sorted, valid keys unique, sentinel last
    t: jnp.ndarray,  # [N] uint32, sorted, sentinel last
    wt: jnp.ndarray | None = None,  # [N] bool or 0/1: rows that count
    bq: int = 512,
    bt: int = 512,
    interpret: bool = False,
):
    """Rank sorted queries in a sorted table: ``(count [M] int32, eq [M]
    bool)``, the (``wt``-weighted) table rows with key < q, and whether the
    table holds key == q — ``jnp.searchsorted(t, q, side="left")`` and
    membership, exact for every query.

    The kernel answers the valid queries; a sentinel query's answers (all
    valid rows' weight; whether the table holds the sentinel) come from
    two reductions here, so the walk never spans the sentinel padding.
    """
    m, n = q.shape[0], t.shape[0]
    w = jnp.ones((n,), jnp.int32) if wt is None else wt.astype(jnp.int32)
    qp = jnp.pad(_signed(q), (0, -m % bq), constant_values=_INT32_MAX)
    tp = jnp.pad(_signed(t), (0, -n % bt), constant_values=_INT32_MAX)
    wp = jnp.pad(w, (0, -n % bt))
    q_blocks, t_blocks = qp.shape[0] // bq, tp.shape[0] // bt
    valid_q = q != SENTINEL
    q_of, t_of, lo = _rank_walk(qp, tp, jnp.sum(valid_q), bq, bt)
    block_w = jnp.concatenate([
        jnp.zeros((1,), jnp.int32),
        jnp.cumsum(wp.reshape(t_blocks, bt).sum(axis=1)).astype(jnp.int32),
    ])
    spec_q = pl.BlockSpec((1, bq), lambda s, q_of, t_of, base: (0, q_of[s]))
    spec_t = pl.BlockSpec((1, bt), lambda s, q_of, t_of, base: (0, t_of[s]))
    cnt, eq = pl.pallas_call(
        _ranks_kernel,
        name="merge_ranks",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(_rank_walk_len(m, n, bq, bt),),
            in_specs=[spec_q, spec_t, spec_t],
            out_specs=[spec_q, spec_q],
        ),
        out_shape=(
            jax.ShapeDtypeStruct((1, q_blocks * bq), jnp.int32),
            jax.ShapeDtypeStruct((1, q_blocks * bq), jnp.int32),
        ),
        # One axis: a query block's steps must stay consecutive.
        compiler_params=CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(q_of, t_of, block_w[lo], qp[None, :], tp[None, :], wp[None, :])
    valid_t = t != SENTINEL
    cnt = jnp.where(valid_q, cnt[0, :m], jnp.sum(jnp.where(valid_t, w, 0)))
    eq = jnp.where(valid_q, eq[0, :m] > 0, ~valid_t[n - 1])
    return cnt, eq


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
    (``merge_positions_pallas`` gives both runs this). Rows with
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


def merge_positions_pallas(sk: jnp.ndarray, ck: jnp.ndarray, blk: int = 512,
                           interpret: bool = False):
    """``ref.merge_positions`` (same contract, bit-identical) from two
    ``merge_ranks`` passes and no gathers.

    Pass 1 ranks the chunk in the state: a chunk key's state rank, plus
    its chunk row, less the duplicates before it in the chunk, is its rank
    in the union — for a duplicate, exactly its state partner's. Pass 2
    ranks the state in the chunk, counting only the chunk's new keys.
    """
    cap, c = sk.shape[0], ck.shape[0]
    REGISTRY.gauge("merge.rank_grid_steps").set(
        _rank_walk_len(c, cap, blk, blk) + _rank_walk_len(cap, c, blk, blk)
    )
    REGISTRY.gauge("merge.rank_search_steps").set(
        c * cap.bit_length() + cap * c.bit_length()
    )
    valid_s = sk != SENTINEL
    valid_c = ck != SENTINEL
    ins_s, held = merge_ranks(ck, sk, bq=blk, bt=blk, interpret=interpret)
    dup = valid_c & held
    dup_before = jnp.cumsum(dup).astype(jnp.int32) - dup
    drop = jnp.int32(cap + c)
    pos_c = jnp.where(
        valid_c, ins_s + jnp.arange(c, dtype=jnp.int32) - dup_before, drop
    )
    new_c = valid_c & ~dup
    ins_c, _ = merge_ranks(sk, ck, new_c, bq=blk, bt=blk, interpret=interpret)
    pos_s = jnp.where(valid_s, jnp.arange(cap, dtype=jnp.int32) + ins_c, drop)
    return pos_s, pos_c, new_c


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
    pos_s, pos_c, new_c = merge_positions_pallas(
        sk, ck, blk=blk, interpret=interpret
    )
    oa, ob, ow = scatter_combine_pallas(
        (pos_s, sa, sb, sw), (pos_c, ca, cb, cw), cap,
        tn=tn, blk=blk, interpret=interpret,
    )
    oa = jnp.where(oa < 0, s_cap, oa)
    ob = jnp.where(ob < 0, s_cap, ob)
    n = (jnp.sum(sk != SENTINEL) + jnp.sum(new_c)).astype(jnp.int32)
    return oa, ob, ow, n
