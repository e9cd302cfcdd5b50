"""Jit'd public wrapper for the n-body repulsion kernel.

Backend selection (``_resolve``):
  * TPU            → Pallas kernel (nbody.py)
  * CPU, small n   → dense jnp oracle (fast enough, exact)
  * CPU, large n   → j-chunked jnp scan (same math, bounded memory) —
                     interpret-mode Pallas is too slow for production CPU
                     use; the kernel itself is validated in interpret mode
                     by tests/test_kernels.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.compat import on_tpu
from repro.kernels.repulsion.nbody import repulsion_pallas
from repro.kernels.repulsion.ref import EPS, repulsion_ref


def _pad(x, n_pad, fill=0.0):
    pad = [(0, n_pad - x.shape[0])] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, pad, constant_values=fill)


@functools.partial(jax.jit, static_argnames=("kr", "chunk", "use_radii"))
def repulsion_chunked(pos, mass, kr: float, radii=None, chunk: int = 1024,
                      use_radii: bool = True):
    """Scan over j-chunks; identical math to ref, O(n·chunk) live memory."""
    n = pos.shape[0]
    n_pad = ((n + chunk - 1) // chunk) * chunk
    pos_p = _pad(pos, n_pad)
    mass_p = _pad(mass, n_pad)
    rad_p = _pad(radii, n_pad) if (radii is not None and use_radii) else jnp.zeros(n_pad, pos.dtype)
    idx = jnp.arange(n_pad)

    pj = pos_p.reshape(-1, chunk, 2)
    mj = mass_p.reshape(-1, chunk)
    rj = rad_p.reshape(-1, chunk)
    ij = idx.reshape(-1, chunk)

    def body(acc, blk):
        pjc, mjc, rjc, ijc = blk
        dx = pos_p[:, 0:1] - pjc[None, :, 0]
        dy = pos_p[:, 1:2] - pjc[None, :, 1]
        d2 = dx * dx + dy * dy
        d = jnp.sqrt(jnp.maximum(d2, EPS * EPS))
        eff = jnp.maximum(d - rad_p[:, None] - rjc[None, :], EPS) if use_radii else jnp.maximum(d, EPS)
        mag = kr * mass_p[:, None] * mjc[None, :] / (eff * d)
        mag = jnp.where(idx[:, None] == ijc[None, :], 0.0, mag)
        fx = jnp.sum(mag * dx, axis=1)
        fy = jnp.sum(mag * dy, axis=1)
        return acc + jnp.stack([fx, fy], axis=1), None

    acc, _ = jax.lax.scan(body, jnp.zeros((n_pad, 2), pos.dtype), (pj, mj, rj, ij))
    return acc[:n]


@functools.partial(
    jax.jit, static_argnames=("nl", "kr", "chunk", "use_radii")
)
def repulsion_chunked_rows(pos, mass, i0, nl: int, kr: float, radii=None,
                           chunk: int = 1024, use_radii: bool = True):
    """Rows [i0, i0+nl) of ``repulsion_chunked`` without materializing the
    rest: same padded j-chunk partition and in-chunk reduction order, so the
    owned rows are bitwise identical (rows are independent in that scan).
    The sharded FA2 layout (core/forceatlas2.layout_sharded) calls this with
    each device's node range; ``i0`` may be traced. Keep the body in
    lockstep with ``repulsion_chunked`` above — any drift breaks the
    bit-identity the device-count CI matrix asserts.
    """
    n = pos.shape[0]
    n_pad = ((n + chunk - 1) // chunk) * chunk
    pos_p = _pad(pos, n_pad)
    mass_p = _pad(mass, n_pad)
    rad_p = _pad(radii, n_pad) if (radii is not None and use_radii) else jnp.zeros(n_pad, pos.dtype)
    idx = jnp.arange(n_pad)

    pr = jax.lax.dynamic_slice_in_dim(pos_p, i0, nl)
    mr = jax.lax.dynamic_slice_in_dim(mass_p, i0, nl)
    rr = jax.lax.dynamic_slice_in_dim(rad_p, i0, nl)
    ir = jax.lax.dynamic_slice_in_dim(idx, i0, nl)

    pj = pos_p.reshape(-1, chunk, 2)
    mj = mass_p.reshape(-1, chunk)
    rj = rad_p.reshape(-1, chunk)
    ij = idx.reshape(-1, chunk)

    def body(acc, blk):
        pjc, mjc, rjc, ijc = blk
        dx = pr[:, 0:1] - pjc[None, :, 0]
        dy = pr[:, 1:2] - pjc[None, :, 1]
        d2 = dx * dx + dy * dy
        d = jnp.sqrt(jnp.maximum(d2, EPS * EPS))
        eff = jnp.maximum(d - rr[:, None] - rjc[None, :], EPS) if use_radii else jnp.maximum(d, EPS)
        mag = kr * mr[:, None] * mjc[None, :] / (eff * d)
        mag = jnp.where(ir[:, None] == ijc[None, :], 0.0, mag)
        fx = jnp.sum(mag * dx, axis=1)
        fy = jnp.sum(mag * dy, axis=1)
        return acc + jnp.stack([fx, fy], axis=1), None

    acc, _ = jax.lax.scan(body, jnp.zeros((nl, 2), pos.dtype), (pj, mj, rj, ij))
    return acc


def _resolve(backend: str, n: int) -> str:
    """The auto dispatch, shared by ``repulsion`` and ``repulsion_rows`` so
    a device of the sharded layout takes the path one device would."""
    if backend != "auto":
        return backend
    if on_tpu():
        return "pallas"
    return "ref" if n <= 2048 else "chunked"


def _pallas_inputs(pos, mass, radii, tile: int):
    """Tile size and the kernel's padded (pos, mass, radii)."""
    n = pos.shape[0]
    t = min(tile, max(128, n))
    n_pad = ((n + t - 1) // t) * t
    rad = _pad(radii, n_pad) if radii is not None else jnp.zeros(n_pad, pos.dtype)
    return t, _pad(pos, n_pad), _pad(mass, n_pad), rad


def repulsion(pos, mass, kr: float, radii=None, backend: str = "auto",
              tile: int = 512):
    """FA2 repulsion forces. pos [n,2], mass [n] → [n,2].

    Padded entries must carry mass 0 (they then exert/receive no force).
    """
    n = pos.shape[0]
    use_radii = radii is not None
    backend = _resolve(backend, n)
    if backend == "ref":
        return repulsion_ref(pos, mass, kr, radii=radii)
    if backend == "chunked":
        return repulsion_chunked(pos, mass, kr, radii=radii, use_radii=use_radii)
    # pallas (or explicit interpret validation)
    t, pos_p, mass_p, rad_p = _pallas_inputs(pos, mass, radii, tile)
    out = repulsion_pallas(pos_p, mass_p, rad_p, kr, ti=t, tj=t,
                           use_radii=use_radii,
                           interpret=backend == "interpret" or not on_tpu())
    return out[:n]


def repulsion_rows(pos, mass, i0, nl: int, kr: float, radii=None,
                   backend: str = "auto", tile: int = 512):
    """Rows [i0, i0+nl) of ``repulsion`` with the same backend and the same
    bits, computing only those rows where the backend allows (``i0`` may
    be traced) — a device's share of the sharded layout."""
    n = pos.shape[0]
    use_radii = radii is not None
    backend = _resolve(backend, n)
    if backend == "ref":
        full = repulsion_ref(pos, mass, kr, radii=radii)
        return jax.lax.dynamic_slice_in_dim(full, i0, nl)
    if backend == "chunked":
        return repulsion_chunked_rows(pos, mass, i0, nl, kr, radii=radii,
                                      use_radii=use_radii)
    t, pos_p, mass_p, rad_p = _pallas_inputs(pos, mass, radii, tile)
    ti = t if nl % t == 0 else nl
    return repulsion_pallas(pos_p, mass_p, rad_p, kr, ti=ti, tj=t,
                            use_radii=use_radii, i0=i0, rows=nl,
                            interpret=backend == "interpret" or not on_tpu())
