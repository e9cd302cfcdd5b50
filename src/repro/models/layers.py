"""Shared neural layers: RMSNorm, RoPE, GQA attention (full / sliding-window
/ chunked-prefill / decode), dense GLU MLP, and capacity-based MoE with
sort-dispatch (no [T,E,C] one-hot blowup).

Everything is a pure function over (params dict, inputs); activations use
``act_dtype`` (bf16 by default at scale) with f32 softmax/norm statistics.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.compat import shard_map_compat

# --------------------------------------------------------------- norms / pos

def rms_norm(x, scale, eps: float = 1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    y = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def rope(x, positions, theta: float = 10000.0):
    """x [..., S, H, hd]; positions [..., S] int32."""
    hd = x.shape[-1]
    half = hd // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., :, None].astype(jnp.float32) * freq  # [..., S, half]
    cos = jnp.cos(ang)[..., :, None, :]  # [..., S, 1, half]
    sin = jnp.sin(ang)[..., :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


# ----------------------------------------------------------------- attention

def _attend_grouped(q, k, v, mask, scale):
    """q [B,Sq,KV,G,hd], k [B,Skv,KV,hd], v same → out [B,Sq,KV,G,hd].

    mask [B or 1, Sq, Skv] bool (True = attend). Softmax stats in f32.
    Used by the DECODE path, where the KV cache must stay at KV heads.
    """
    logits = jnp.einsum("bqkgh,bskh->bkgqs", q, k).astype(jnp.float32) * scale
    neg = jnp.finfo(jnp.float32).min
    logits = jnp.where(mask[:, None, None, :, :], logits, neg)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bkgqs,bskh->bqkgh", probs, v)


def _attend_flat(q, k, v, mask, scale):
    """Flat-head attention: q/k/v all [B,S,H,hd].

    Train/prefill path. The grouped [KV,G] factorization is sharding-
    hostile: 96 heads shard 16-way but neither KV=8 nor G=12 divides 16,
    so GSPMD falls back to a 4×4 split and "involuntary full
    rematerialization" — 16.9 TiB of backward all-gathers per device on
    mistral train_4k (EXPERIMENTS §Perf iteration 9). Flat heads shard
    cleanly; K/V are pre-expanded to H heads by the caller.
    """
    logits = jnp.einsum("bqhd,bshd->bhqs", q, k).astype(jnp.float32) * scale
    neg = jnp.finfo(jnp.float32).min
    logits = jnp.where(mask[:, None, :, :], logits, neg)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqs,bshd->bqhd", probs, v)


def expand_kv(k, g: int):
    """[B,S,KV,hd] → [B,S,KV·G,hd], head h ↔ group h // G (matches the
    kv-major flat head order of the fused qkv projection)."""
    return jnp.repeat(k, g, axis=2)


def gqa_attention(
    q,  # [B, Sq, H, hd]
    k,  # [B, Skv, KV, hd] (grouped) or [B, Skv, H, hd] (pre-expanded)
    v,
    q_positions,  # [B, Sq] int32 absolute positions
    kv_positions,  # [B, Skv]
    *,
    causal: bool = True,
    window: int | None = None,  # sliding-window size (None = full)
    kv_valid_len=None,  # [B] decode: number of live cache slots
    q_chunk: int = 0,  # >0: scan over q chunks (bounds score memory)
):
    """Grouped-query attention with optional banded (sliding) masking and
    chunked-prefill scanning. Returns [B, Sq, H, hd]."""
    b, sq, h, hd = q.shape
    kv = k.shape[2]
    scale = hd ** -0.5
    flat = kv == h

    def mask_for(qpos):
        m = jnp.ones((b, qpos.shape[1], k.shape[1]), bool)
        if causal:
            m &= qpos[:, :, None] >= kv_positions[:, None, :]
        if window is not None:
            m &= qpos[:, :, None] - kv_positions[:, None, :] < window
        if kv_valid_len is not None:
            live = jnp.arange(k.shape[1])[None, :] < kv_valid_len[:, None]
            m &= live[:, None, :]
        return m

    if flat:
        if q_chunk and sq > q_chunk and sq % q_chunk == 0:
            nc = sq // q_chunk
            qs = q.reshape(b, nc, q_chunk, h, hd).transpose(1, 0, 2, 3, 4)
            ps = q_positions.reshape(b, nc, q_chunk).transpose(1, 0, 2)

            def body(_, qc_pc):
                qc, pc = qc_pc
                return None, _attend_flat(qc, k, v, mask_for(pc), scale)

            _, outs = jax.lax.scan(body, None, (qs, ps))
            return outs.transpose(1, 0, 2, 3, 4).reshape(b, sq, h, hd)
        return _attend_flat(q, k, v, mask_for(q_positions), scale)

    # grouped (decode): cache stays at KV heads, G queries share each head
    g = h // kv
    qg = q.reshape(b, sq, kv, g, hd)
    out = _attend_grouped(qg, k, v, mask_for(q_positions), scale)
    return out.reshape(b, sq, h, hd)


# ----------------------------------------------------------------------- MLP

def glu_mlp(x, wi, wg, wo):
    """SwiGLU: (silu(x@wg) * (x@wi)) @ wo."""
    h = jax.nn.silu(jnp.einsum("bsd,df->bsf", x, wg.astype(x.dtype)))
    h = h * jnp.einsum("bsd,df->bsf", x, wi.astype(x.dtype))
    return jnp.einsum("bsf,fd->bsd", h, wo.astype(x.dtype))


# ----------------------------------------------------------------------- MoE

def _cumcount(ids, n_buckets):
    """Rank of each element among equal values (stable, vectorized)."""
    order = jnp.argsort(ids, stable=True)
    sorted_ids = ids[order]
    idx = jnp.arange(ids.shape[0], dtype=jnp.int32)
    is_start = jnp.concatenate([jnp.array([True]), sorted_ids[1:] != sorted_ids[:-1]])
    group_start = jax.lax.associative_scan(jnp.maximum, jnp.where(is_start, idx, 0))
    rank_sorted = idx - group_start
    return jnp.zeros_like(ids).at[order].set(rank_sorted)


def moe_mlp(x, router_w, w_gate, w_in, w_out, *, top_k: int, capacity: int,
            shared=None, buf_constraint=None):
    """Capacity-based top-k MoE with sort-dispatch.

    x [B, S, D]; router_w [D, E]; w_* [E, D, F] / [E, F, D].
    Dispatch: flatten (token, choice) pairs, rank tokens per expert by a
    vectorized cumulative count, scatter into an [E·C, D] buffer, run the
    batched per-expert einsum, and combine with gate weights. Tokens past
    capacity are dropped (standard GShard semantics). No [T, E, C] one-hot.
    """
    b, s, d = x.shape
    e = router_w.shape[-1]
    t = b * s
    xf = x.reshape(t, d)

    logits = jnp.einsum("td,de->te", xf, router_w.astype(x.dtype)).astype(jnp.float32)
    gates, choices = jax.lax.top_k(logits, top_k)  # [t, k]
    gates = jax.nn.softmax(gates, axis=-1)

    tok_idx = jnp.repeat(jnp.arange(t, dtype=jnp.int32), top_k)  # [t·k]
    exp_idx = choices.reshape(-1).astype(jnp.int32)
    gate = gates.reshape(-1)

    rank = _cumcount(exp_idx, e)
    keep = rank < capacity
    slot = jnp.where(keep, exp_idx * capacity + rank, e * capacity)  # trash slot

    buf = jnp.zeros((e * capacity + 1, d), x.dtype).at[slot].set(xf[tok_idx])
    xs = buf[:-1].reshape(e, capacity, d)
    if buf_constraint is not None:  # expert dim → model axis (EP)
        xs = jax.lax.with_sharding_constraint(xs, buf_constraint)

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xs, w_gate.astype(x.dtype)))
    h = h * jnp.einsum("ecd,edf->ecf", xs, w_in.astype(x.dtype))
    ys = jnp.einsum("ecf,efd->ecd", h, w_out.astype(x.dtype))
    if buf_constraint is not None:
        ys = jax.lax.with_sharding_constraint(ys, buf_constraint)

    ys_flat = ys.reshape(e * capacity, d)
    contrib = jnp.where(keep[:, None], ys_flat[jnp.minimum(slot, e * capacity - 1)], 0.0)
    out = jnp.zeros((t, d), x.dtype).at[tok_idx].add(contrib * gate[:, None].astype(x.dtype))

    # Router z-loss + load-balance aux (returned for the training loss).
    probs = jax.nn.softmax(logits, axis=-1)
    load = jnp.mean(probs, axis=0)
    importance = jnp.zeros(e, jnp.float32).at[exp_idx].add(1.0) / (t * top_k)
    aux = e * jnp.sum(load * importance)
    if shared is not None:  # shared-expert branch (DeepSeek/Kimi style)
        sw_gate, sw_in, sw_out = shared
        hs = jax.nn.silu(jnp.einsum("td,df->tf", xf, sw_gate.astype(x.dtype)))
        hs = hs * jnp.einsum("td,df->tf", xf, sw_in.astype(x.dtype))
        out = out + jnp.einsum("tf,fd->td", hs, sw_out.astype(x.dtype))
    return out.reshape(b, s, d), aux


def moe_mlp_shmap(x, router_w, w_gate, w_in, w_out, *, top_k: int,
                  capacity_local: int, mesh, expert_axis: str,
                  token_axes) -> tuple:
    """Expert-parallel MoE under shard_map (DESIGN.md §4).

    Plain-GSPMD dispatch scatters over *global* tokens, which XLA
    replicates (measured: ~95 GiB/device on granite train_4k — see
    EXPERIMENTS.md §Perf). Here tokens never leave their data shard:
    every model shard owns an expert block [E_loc], dispatches its local
    tokens into a local [E_loc, C_loc, D] buffer, runs the batched expert
    einsum, combines with gate weights, and one psum over the expert axis
    sums the per-block partial outputs (tokens' other experts live on
    other shards). Collectives: a single all-reduce of [T_loc, D] per
    layer — no all-to-all, no replicated scatter.

    x must be sharded P(token_axes, None, None); router_w replicated;
    w_* sharded P(expert_axis, None, None).
    """
    from jax.sharding import PartitionSpec as P

    e = w_gate.shape[0]
    b, s, d = x.shape

    def local_fn(x_l, rw, wg_l, wi_l, wo_l):
        e_loc = wg_l.shape[0]
        m_idx = jax.lax.axis_index(expert_axis)
        bl, sl, dl = x_l.shape
        t = bl * sl
        xf = x_l.reshape(t, dl)
        logits = jnp.einsum("td,de->te", xf, rw.astype(x_l.dtype)).astype(jnp.float32)
        gates, choices = jax.lax.top_k(logits, top_k)
        gates = jax.nn.softmax(gates, axis=-1)

        tok_idx = jnp.repeat(jnp.arange(t, dtype=jnp.int32), top_k)
        exp_idx = choices.reshape(-1).astype(jnp.int32)
        gate = gates.reshape(-1).astype(x_l.dtype)

        owned = (exp_idx >= m_idx * e_loc) & (exp_idx < (m_idx + 1) * e_loc)
        local_e = jnp.where(owned, exp_idx - m_idx * e_loc, e_loc)
        rank = _cumcount(jnp.where(owned, local_e, e_loc + 1), e_loc)
        keep = owned & (rank < capacity_local)
        n_slots = e_loc * capacity_local
        slot = jnp.where(keep, local_e * capacity_local + rank, n_slots)

        # Capacity-sized dispatch: materializing xf[tok_idx] is a [T·k, D]
        # gather (kimi train_4k: 7.5 GiB ×live-copies ⇒ 173 GiB/dev,
        # EXPERIMENTS §Perf). Invert the map instead — every buffer is
        # [E_loc·C, D], never token-count-sized.
        token_for_slot = jnp.full((n_slots + 1,), t, jnp.int32).at[slot].set(tok_idx)
        gate_for_slot = jnp.zeros((n_slots + 1,), x_l.dtype).at[slot].set(
            jnp.where(keep, gate, 0.0)
        )
        xf_ext = jnp.concatenate([xf, jnp.zeros((1, dl), x_l.dtype)])
        xs = xf_ext[token_for_slot[:-1]].reshape(e_loc, capacity_local, dl)

        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xs, wg_l.astype(x_l.dtype)))
        h = h * jnp.einsum("ecd,edf->ecf", xs, wi_l.astype(x_l.dtype))
        ys = jnp.einsum("ecf,efd->ecd", h, wo_l.astype(x_l.dtype))

        ys_flat = ys.reshape(n_slots, dl) * gate_for_slot[:-1, None]
        out = jnp.zeros((t + 1, dl), x_l.dtype).at[token_for_slot[:-1]].add(ys_flat)
        out = jax.lax.psum(out[:t], expert_axis)

        # load-balance aux, reduced over every mesh axis so it is truly
        # replicated (out_specs P() demands it)
        red = tuple(token_axes or ()) + (expert_axis,)
        probs = jax.nn.softmax(logits, axis=-1)
        load = jax.lax.pmean(jnp.mean(probs, axis=0), red)
        imp = jnp.zeros(e, jnp.float32).at[exp_idx].add(1.0) / (t * top_k)
        imp = jax.lax.pmean(imp, red)
        aux = e * jnp.sum(load * imp)
        return out.reshape(bl, sl, dl), aux

    tok = tuple(token_axes) if token_axes else None
    out, aux = shard_map_compat(
        local_fn,
        mesh,
        in_specs=(P(tok, None, None), P(), P(expert_axis, None, None),
                  P(expert_axis, None, None), P(expert_axis, None, None)),
        out_specs=(P(tok, None, None), P()),
    )(x, router_w, w_gate, w_in, w_out)
    return out, aux
