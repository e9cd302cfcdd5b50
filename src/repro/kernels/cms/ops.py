"""Jit'd public wrapper for the CMS update kernel: hashes keys (same
multiply-shift family as core/cms.py) and dispatches to the Pallas kernel
on TPU or the scatter-add oracle on CPU."""
from __future__ import annotations

import jax.numpy as jnp

from repro.core import cms as cms_lib
from repro.kernels.compat import resolve_backend
from repro.kernels.cms.cms_update import cms_update_pallas
from repro.kernels.cms.ref import cms_update_ref


def update(
    sketch: jnp.ndarray,
    keys: jnp.ndarray,
    weights: jnp.ndarray,
    cfg: cms_lib.CMSConfig,
    backend: str = "auto",
) -> jnp.ndarray:
    a, b = cms_lib.hash_params(cfg)
    h = cms_lib.hash_keys(keys, a, b, cfg.cols)
    h = jnp.where(keys[None, :] >= 0, h, -1)
    backend, interpret = resolve_backend(backend)
    if backend == "ref":
        return cms_update_ref(sketch, h, weights)
    return cms_update_pallas(
        sketch, h, weights.astype(jnp.float32), cfg.cols, interpret=interpret
    )
