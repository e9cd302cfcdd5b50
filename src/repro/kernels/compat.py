"""Small JAX helpers shared by the kernels, the streaming engine and the
launchers.

- ``CompilerParams`` is the Pallas TPU compiler-parameter class every
  kernel passes to ``pallas_call``; ``resolve_backend`` is the one
  dispatch rule of every ``kernels/*/ops.py`` wrapper.
- ``shard_map_compat`` is ``jax.shard_map`` with the replication check off.
- ``device_put_copied`` is the host→device transfer for reused host
  staging buffers: the returned array never reads the host buffer again.
- ``enable_compile_cache`` places JAX's persistent compilation cache.
"""
import os
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

CompilerParams = pltpu.CompilerParams

# The checkout root (src/repro/kernels/compat.py → three levels up).
_CHECKOUT = Path(__file__).resolve().parents[3]
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_backend(backend: str) -> tuple[str, bool]:
    """``(backend, interpret)`` for a kernel wrapper's ``backend`` argument.

    "auto" is the Pallas kernel on a TPU and the XLA reference path
    anywhere else; "pallas" off a TPU, and "interpret" anywhere, run the
    kernel in interpret mode (how the CPU tests check it against "ref").
    """
    if backend == "auto":
        backend = "pallas" if on_tpu() else "ref"
    return backend, backend == "interpret" or not on_tpu()


def shard_map_compat(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the static replication check disabled.

    The sharded stream/layout bodies return ``all_gather``-replicated
    values the checker cannot infer as replicated; disabling the check is
    the documented escape hatch and is bitwise-neutral.
    """
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )


def device_put_copied(x, sharding=None):
    """Transfer ``x`` so the result is independent of the host buffer.

    ``jax.device_put`` may keep reading a host NumPy buffer after it
    returns — on CPU the device array can alias it outright, whatever
    ``may_alias`` says. The device-side copy below is the array handed
    on: once it is ready it holds its own bytes, so a caller that blocks
    on it may refill the host buffer (``EdgeChunkStream.device_chunks``).
    """
    return jnp.copy(jax.device_put(x, sharding))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and no
    other directory is set here. Otherwise the cache lives in
    ``.bgv-compile-cache/`` at the checkout root, found from this file's
    path rather than the working directory, so every launcher of one
    checkout shares one cache. The size and compile-time floors are
    dropped so every executable is cached: a restarted process then loads
    its programs instead of compiling them again.
    """
    path = os.environ.get(CACHE_ENV)
    if not path:
        path = str(_CHECKOUT / ".bgv-compile-cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
