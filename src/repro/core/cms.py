"""Count–min sketch (paper §3.3) — community sizing without atomic counters.

A CMS is a *linear* sketch: updates commute and shards merge by addition.
That is exactly what makes the paper's pipeline multi-pod scalable: each
device sketches its own edge shard and one all-reduce merges the sketches
(see core/pipeline.py and DESIGN.md §2).

Hashing: multiply-shift universal hashing in uint32 (wraps mod 2^32), then
mod ``cols``. The paper uses 4 hash rows and cols ≈ 1e-4 × |E|.

The pipeline's sketch updates (``core/supergraph.community_sizes`` and
``sharded_update`` below) go through ``kernels/cms``, a Pallas TPU kernel
that turns the scatter-add into a one-hot × matmul on the MXU; this
module's ``update`` is the plain scatter-add form and the public API.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class CMSConfig:
    rows: int = 4
    cols: int = 5000
    seed: int = 0x5EED


def hash_params(cfg: CMSConfig) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-row (a, b) multiply-shift constants (odd a)."""
    rng = np.random.default_rng(cfg.seed)
    a = rng.integers(1, 2**31, size=cfg.rows, dtype=np.uint32) * 2 + 1
    b = rng.integers(0, 2**31, size=cfg.rows, dtype=np.uint32)
    return jnp.asarray(a), jnp.asarray(b)


def hash_keys(keys: jnp.ndarray, a: jnp.ndarray, b: jnp.ndarray, cols: int) -> jnp.ndarray:
    """[rows, n] bucket indices for int32 keys."""
    k = keys.astype(jnp.uint32)[None, :]
    h = (a[:, None] * k + b[:, None]) >> jnp.uint32(5)
    return (h % jnp.uint32(cols)).astype(jnp.int32)


def init_sketch(cfg: CMSConfig, dtype=jnp.float32) -> jnp.ndarray:
    return jnp.zeros((cfg.rows, cfg.cols), dtype=dtype)


@functools.partial(jax.jit, static_argnames=("cfg",))
def update(sketch: jnp.ndarray, keys: jnp.ndarray, weights: jnp.ndarray, cfg: CMSConfig):
    """Add ``weights`` at ``keys``. Negative-key slots are masked (padding)."""
    a, b = hash_params(cfg)
    h = hash_keys(keys, a, b, cfg.cols)
    w = jnp.where(keys >= 0, weights, 0).astype(sketch.dtype)
    rows = jnp.arange(cfg.rows, dtype=jnp.int32)[:, None]
    return sketch.at[rows, h].add(w[None, :])


@functools.partial(jax.jit, static_argnames=("cfg",))
def query(sketch: jnp.ndarray, keys: jnp.ndarray, cfg: CMSConfig) -> jnp.ndarray:
    """Point-query: min over hash rows (classic CMS estimate)."""
    a, b = hash_params(cfg)
    h = hash_keys(keys, a, b, cfg.cols)
    rows = jnp.arange(cfg.rows, dtype=jnp.int32)[:, None]
    return jnp.min(sketch[rows, h], axis=0)


def merge(*sketches: jnp.ndarray) -> jnp.ndarray:
    """CMS is linear: shard-local sketches merge by addition."""
    return functools.reduce(jnp.add, sketches)


@functools.lru_cache(maxsize=None)
def sharded_update(mesh, cfg: CMSConfig):
    """Compiled sharded ``update`` over ``mesh``: keys/weights arrive
    row-sharded (all mesh axes on dim 0), the sketch replicated; each device
    sketches its own key slice and one ``psum`` merges — the linearity the
    module docstring promises. Exact vs. single-device while the counts stay
    integer-valued below 2^24 (community sizes are degree sums, i.e. ints).
    Requires ``len(keys) % mesh.size == 0`` — callers pad with key=-1 (the
    masked padding slot) to the next multiple.
    """
    from jax.sharding import PartitionSpec as P

    from repro.kernels.compat import shard_map_compat
    from repro.sharding.rules import row_chunk_spec

    axes = tuple(mesh.axis_names)
    row1d = P(row_chunk_spec(mesh)[0])  # 1-D operands: drop the trailing None

    from repro.kernels.cms import ops as cms_ops

    def body(sketch, keys, weights):
        local = cms_ops.update(jnp.zeros_like(sketch), keys, weights, cfg)
        return sketch + jax.lax.psum(local, axes)

    mapped = shard_map_compat(
        body, mesh, in_specs=(P(), row1d, row1d), out_specs=P()
    )
    return jax.jit(mapped, donate_argnums=(0,))


# --------------------------------------------------------------------------
# Chunk-incremental API (core/stream.py engine). The sketch is linear, so
# ``update`` already *is* the chunk step: init → update×chunks → finalize.
# ``finalize`` is the identity — it exists so every streamed stage exposes
# the same init/update/finalize contract.
# --------------------------------------------------------------------------

init = init_sketch


def finalize(sketch: jnp.ndarray) -> jnp.ndarray:
    return sketch
