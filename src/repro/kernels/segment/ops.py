"""Jit'd public wrapper: segment-sum via Pallas on TPU, XLA scatter on CPU."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.compat import resolve_backend
from repro.kernels.segment.ref import segment_sum_ref
from repro.kernels.segment.seg_matmul import segment_sum_pallas


def segment_sum(
    data: jnp.ndarray,
    seg_ids: jnp.ndarray,
    n_segments: int,
    backend: str = "auto",
    indices_are_sorted: bool = False,
) -> jnp.ndarray:
    """``indices_are_sorted`` promises sorted ``seg_ids`` (same result,
    faster scatter lowering on the ref path; the one-hot-matmul Pallas
    kernel is insensitive to input order and ignores the hint)."""
    backend, interpret = resolve_backend(backend)
    if backend == "ref":
        return segment_sum_ref(
            data, seg_ids, n_segments, indices_are_sorted=indices_are_sorted
        )
    return segment_sum_pallas(data, seg_ids, n_segments, interpret=interpret)
