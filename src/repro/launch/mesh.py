"""The streaming engine's device mesh. A FUNCTION (not a module-level
constant) so importing this module never touches jax device state.

The mesh has ``Auto`` axes: the sharded bodies place their own
collectives with ``shard_map``, and an array on an ``Explicit`` mesh (what
``jax.make_mesh`` gives by default) cannot enter a jitted function that
has no mesh in context — the unsharded chunk updates are such functions.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_stream_mesh(devices: int | None = None):
    """1-D "data" mesh over the local devices — the streaming engine's
    sharded detect/layout placement (core/stream.py, StreamConfig.mesh).
    ``devices`` caps the mesh size (None = all available); on CPU, force
    a multi-device mesh with XLA_FLAGS=--xla_force_host_platform_device_count=N.
    """
    avail = jax.device_count()
    d = avail if devices is None else min(devices, avail)
    return _auto_mesh((d,), ("data",))
