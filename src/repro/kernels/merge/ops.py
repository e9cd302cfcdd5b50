"""Public wrapper: sorted-merge-and-combine via Pallas on TPU, XLA
searchsorted + scatter elsewhere (dispatch mirrors kernels/segment/ops.py).
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.compat import resolve_backend
from repro.kernels.merge.ref import merge_combine_ref
from repro.kernels.merge.sorted_merge import merge_combine_pallas


def merge_combine(
    sa: jnp.ndarray,
    sb: jnp.ndarray,
    sw: jnp.ndarray,
    ca: jnp.ndarray,
    cb: jnp.ndarray,
    cw: jnp.ndarray,
    s_cap: int,
    backend: str = "auto",
):
    """Merge a sorted deduped chunk run [C] into the sorted state run [cap].

    Both runs are (a, b)-sorted with unique valid pairs and (s_cap, s_cap,
    0) padding last. Returns (oa, ob, ow, n): the union's smallest ``cap``
    pairs with combined weights, and the union's unique-pair count.
    """
    backend, interpret = resolve_backend(backend)
    if backend == "ref":
        return merge_combine_ref(sa, sb, sw, ca, cb, cw, s_cap)
    return merge_combine_pallas(sa, sb, sw, ca, cb, cw, s_cap, interpret=interpret)
