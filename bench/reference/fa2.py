"""Plain jax.numpy reference of the supergraph ForceAtlas2 layout.

The layout runs over ``s_layout`` slots: the live supernodes padded to a
power of two (at least 64, at most ``s_cap``). Slot ``i`` has mass
``size_i + 1`` when live and 0 otherwise, radius √mass, and starts at
``uniform(PRNGKey(layout_seed), (s_layout, 2), -1000, 1000)``. Each
iteration adds, per node,

* gravity ``-kg·m_i·x_i/|x_i|``,
* attraction ``Σ_e w_e·(x_other − x_i)`` over its superedges,
* repulsion ``Σ_{j≠i} kr·m_i·m_j·(x_i − x_j)/(d'·d)`` with
  ``d = max(|x_i − x_j|, 1e-4)`` and ``d' = max(d − r_i − r_j, 1e-4)``,

then moves by FA2's speed rule: swing ``|f − f_prev|``, traction
``|f + f_prev|/2``, global speed ``min(τ·Σm·traction / (Σm·swing + 1e-9),
1.5·speed + 1e-3)`` from 1.0, local speed ``speed/(1 + speed·√swing)``
capped at ``10/|f|``.

Computed in ``dtype`` throughout (float32 as the configuration states;
the control passes bfloat16). Repulsion is taken in row blocks so the
pairwise terms of one block are the only large transient.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-4


def layout_slots(n_super: int, n_superedges: int, s_cap: int, e_cap: int):
    s = max(n_super, 2)
    s_layout = min(max(1 << (s - 1).bit_length(), 64), s_cap)
    e = max(n_superedges, 1)
    e_layout = min(1 << (e - 1).bit_length(), e_cap)
    return s_layout, e_layout


@functools.partial(jax.jit, static_argnames=("n", "iterations", "block",
                                             "dtype", "seed", "kr", "kg",
                                             "tau"))
def _run(edges, weights, mass, *, n, iterations, block, dtype, seed, kr, kg,
         tau):
    dt = jnp.dtype(dtype)
    with jax.default_matmul_precision("highest"):
        pos = jax.random.uniform(jax.random.PRNGKey(seed), (n, 2),
                                 minval=-1000.0, maxval=1000.0,
                                 dtype=jnp.float32).astype(dt)
        mass = mass.astype(dt)
        w = weights.astype(dt)
        radii = jnp.sqrt(mass)
        u, v = edges[:, 0], edges[:, 1]
        live_e = (u < n) & (v < n)
        u = jnp.where(live_e, u, 0)
        v = jnp.where(live_e, v, 0)
        w = jnp.where(live_e, w, 0)
        idx = jnp.arange(n)

        def repulsion(p):
            def rows(i0):
                pi = jax.lax.dynamic_slice_in_dim(p, i0, block)
                mi = jax.lax.dynamic_slice_in_dim(mass, i0, block)
                ri = jax.lax.dynamic_slice_in_dim(radii, i0, block)
                ii = jax.lax.dynamic_slice_in_dim(idx, i0, block)
                dx = pi[:, 0:1] - p[None, :, 0]
                dy = pi[:, 1:2] - p[None, :, 1]
                d = jnp.sqrt(jnp.maximum(dx * dx + dy * dy,
                                         jnp.asarray(EPS * EPS, dt)))
                eff = jnp.maximum(d - ri[:, None] - radii[None, :],
                                  jnp.asarray(EPS, dt))
                mag = kr * mi[:, None] * mass[None, :] / (eff * d)
                mag = jnp.where(ii[:, None] == idx[None, :], 0, mag)
                return jnp.stack([jnp.sum(mag * dx, 1), jnp.sum(mag * dy, 1)], 1)

            out = jax.lax.map(rows, jnp.arange(0, n, block))
            return out.reshape(n, 2)

        def forces(p):
            norm = jnp.sqrt(jnp.sum(p * p, 1, keepdims=True))
            grav = -kg * mass[:, None] * p / jnp.maximum(norm, jnp.asarray(1e-9, dt))
            f_uv = w[:, None] * (p[v] - p[u])
            att = jnp.zeros_like(p).at[u].add(f_uv).at[v].add(-f_uv)
            return grav + att + repulsion(p)

        def step(state, _):
            p, prev, speed = state
            f = forces(p)
            swing = jnp.sqrt(jnp.sum((f - prev) ** 2, 1))
            traction = 0.5 * jnp.sqrt(jnp.sum((f + prev) ** 2, 1))
            g_swing = jnp.sum(mass * swing) + jnp.asarray(1e-9, dt)
            g_traction = jnp.sum(mass * traction)
            speed = jnp.minimum(tau * g_traction / g_swing,
                                1.5 * speed + jnp.asarray(1e-3, dt))
            fmag = jnp.sqrt(jnp.sum(f * f, 1))
            local = speed / (1 + speed * jnp.sqrt(swing))
            local = jnp.minimum(local, 10 / jnp.maximum(fmag, jnp.asarray(1e-9, dt)))
            return (p + local[:, None] * f, f, speed), None

        state = (pos, jnp.zeros_like(pos), jnp.asarray(1.0, dt))
        state, _ = jax.lax.scan(step, state, None, length=iterations)
        return state[0].astype(jnp.float32)


def layout(pairs, weights, sizes, n_super, cfg, dtype="float32", block=None):
    """Positions [s_layout, 2] (float32) from the reference supergraph."""
    s_layout, e_layout = layout_slots(n_super, len(weights), cfg["s_cap"],
                                      cfg["max_super_edges"])
    edges = np.full((e_layout, 2), s_layout, np.int32)
    w = np.zeros(e_layout, np.float32)
    k = min(len(weights), e_layout)
    edges[:k] = pairs[:k]
    w[:k] = weights[:k]
    live = np.arange(s_layout) < n_super
    mass = np.where(live, np.maximum(sizes[:s_layout], 0) + 1.0, 0.0)
    lay = cfg["layout"]
    block = block or min(s_layout, 2048)
    return np.asarray(_run(
        jnp.asarray(edges), jnp.asarray(w), jnp.asarray(mass, jnp.float32),
        n=s_layout, iterations=lay["iterations"], block=block, dtype=dtype,
        seed=lay["seed"], kr=float(lay["repulsion_k"]),
        kg=float(lay["gravity"]), tau=float(lay["jitter_tolerance"])))
