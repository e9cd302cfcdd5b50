"""XLA path + pure-jnp oracle for sorted-merge-and-combine.

Merges two (a, b)-sorted deduplicated superedge runs — the persistent
aggregation state [cap] and a locally deduped chunk [C] — into one sorted
deduplicated run of the state's capacity, summing the weights of pairs
present in both. It exploits both inputs already being sorted: output
ranks come from binary searches (``jnp.searchsorted``) and the rows land
with two scatters, so a chunk update costs O(cap + C) moves plus
O((cap + C)·log) comparisons — never the lexsort baseline's full
O((cap + C)·log(cap + C)) re-sort of state + chunk. This is the CPU path
and the tests' oracle. On a TPU each level of a binary search is a round
of random gathers, which made these ranks the aggregation's largest cost;
the Pallas path ranks the two runs against each other by a banded walk
instead (``sorted_merge.merge_ranks``), with bit-identical results.

Pairs compare as packed uint32 keys ``a·s_cap + b``: valid pairs satisfy
``a < b < s_cap ≤ 2¹⁶`` so the packing is collision-free and
order-preserving (identical to lexsorting by (a, b)); padded ``(s_cap,
s_cap)`` slots map to the uint32 max sentinel and sort last.
"""
from __future__ import annotations

import jax.numpy as jnp

SENTINEL = jnp.uint32(0xFFFFFFFF)
MAX_S_CAP = 1 << 16  # packing needs a, b < s_cap ≤ 2^16 to fit 32 bits


def pack_keys(a: jnp.ndarray, b: jnp.ndarray, s_cap: int) -> jnp.ndarray:
    """(a, b) int32 pairs → order-preserving uint32 keys (invalid → sentinel)."""
    if s_cap > MAX_S_CAP:
        raise ValueError(
            f"packed pair keys require s_cap ≤ {MAX_S_CAP}, got {s_cap}; "
            "use agg_backend='lexsort' beyond that"
        )
    key = a.astype(jnp.uint32) * jnp.uint32(s_cap) + b.astype(jnp.uint32)
    return jnp.where(a < s_cap, key, SENTINEL)


def unpack_keys(key: jnp.ndarray, s_cap: int):
    """uint32 keys → (a, b) int32 pairs; sentinel → the (s_cap, s_cap) pad."""
    a = (key // jnp.uint32(s_cap)).astype(jnp.int32)
    b = (key % jnp.uint32(s_cap)).astype(jnp.int32)
    pad = key == SENTINEL
    return jnp.where(pad, s_cap, a), jnp.where(pad, s_cap, b)


def merge_positions(sk: jnp.ndarray, ck: jnp.ndarray):
    """Merge-path ranks for the union of two sorted unique key runs.

    ``sk`` [cap] / ``ck`` [C] are uint32 keys, each sorted ascending with
    every valid key unique and sentinel padding last. Returns
    ``(pos_state [cap], pos_chunk [C], new_chunk [C])``: the rank of each
    row's key in the sorted union (a key's rank = valid state keys below
    it + chunk-only keys below it). Chunk keys already present in the
    state get their state partner's rank (so a scatter-add combines the
    weights) and ``new_chunk`` False; sentinel rows rank at ``cap + C``,
    past any capacity.
    """
    cap, c = sk.shape[0], ck.shape[0]
    valid_s = sk != SENTINEL
    valid_c = ck != SENTINEL
    # Each chunk key's insertion point in the state run, and whether the
    # state already holds it.
    ins_s = jnp.searchsorted(sk, ck, side="left").astype(jnp.int32)  # [C] ∈ [0, cap]
    partner = jnp.minimum(ins_s, cap - 1)
    dup = valid_c & (jnp.take(sk, partner) == ck)
    # Chunk-only (non-duplicate) keys below any probe, queryable by
    # insertion point: dup_cum[k] = duplicates among the first k chunk rows.
    dup_cum = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(dup).astype(jnp.int32)]
    )
    ins_c = jnp.searchsorted(ck, sk, side="left").astype(jnp.int32)  # [cap] ∈ [0, C]
    drop = jnp.int32(cap + c)
    pos_s = jnp.arange(cap, dtype=jnp.int32) + ins_c - dup_cum[ins_c]
    pos_s = jnp.where(valid_s, pos_s, drop)
    new_c = valid_c & ~dup
    arange_c = jnp.arange(c, dtype=jnp.int32)
    pos_new = ins_s + arange_c - dup_cum[arange_c]
    pos_c = jnp.where(
        new_c, pos_new, jnp.where(dup, jnp.take(pos_s, partner), drop)
    )
    return pos_s, pos_c, new_c


def merge_combine_ref(
    sa: jnp.ndarray,  # [cap] int32, sorted by (a, b), pad s_cap
    sb: jnp.ndarray,  # [cap] int32
    sw: jnp.ndarray,  # [cap] float32, pad 0
    ca: jnp.ndarray,  # [C] int32, sorted by (a, b), deduped, pad s_cap
    cb: jnp.ndarray,  # [C] int32
    cw: jnp.ndarray,  # [C] float32, pad 0
    s_cap: int,
):
    """Merge a sorted deduped chunk run into the sorted state run.

    Returns ``(oa [cap], ob [cap], ow [cap], n)`` with the union's
    lexicographically smallest ``cap`` pairs (overflow truncates the
    sorted tail, same contract as the lexsort path) and ``n`` the count
    of unique valid pairs in the union (may exceed ``cap``).
    """
    cap = sa.shape[0]
    sk = pack_keys(sa, sb, s_cap)
    ck = pack_keys(ca, cb, s_cap)
    pos_s, pos_c, new_c = merge_positions(sk, ck)
    # Overflow + sentinel rows route to a scratch slot that is sliced off.
    ps = jnp.minimum(pos_s, cap)
    pc = jnp.minimum(pos_c, cap)
    ok = (
        jnp.full((cap + 1,), SENTINEL, jnp.uint32)
        .at[ps].set(sk, mode="drop")
        .at[pc].set(ck, mode="drop")
    )
    ow = (
        jnp.zeros((cap + 1,), jnp.float32)
        .at[ps].add(sw, mode="drop")
        .at[pc].add(cw, mode="drop")
    )
    oa, ob = unpack_keys(ok[:cap], s_cap)
    n = (jnp.sum(sk != SENTINEL) + jnp.sum(new_c)).astype(jnp.int32)
    return oa, ob, ow[:cap], n
