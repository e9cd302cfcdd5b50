"""Stream-chunk placements, the stream mesh, and the compile cache."""
import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.sharding.rules import (
    block_chunk_spec,
    linear_axis_index,
    row_chunk_spec,
)


def _mesh_like(shape, names):
    # an abstract mesh for rule resolution only (no devices needed):
    # jax ≥ 0.5 takes (axis_sizes, axis_names), older takes ((name, size), ...)
    from jax.sharding import AbstractMesh

    try:
        return AbstractMesh(tuple(shape), tuple(names))
    except TypeError:
        return AbstractMesh(tuple(zip(names, shape)))


def test_chunk_specs_cover_all_mesh_axes():
    for shape, names in (((8,), ("data",)), ((2, 4), ("data", "model"))):
        mesh = _mesh_like(shape, names)
        assert row_chunk_spec(mesh) == P(tuple(names), None)
        assert block_chunk_spec(mesh) == P(None, tuple(names), None)


def test_stream_mesh_and_linear_axis_index():
    """make_stream_mesh over the local devices; linear_axis_index inside a
    shard_map body enumerates shards in the row order ``all_gather`` tiles
    them (the identity the sharded engine's row slicing rests on)."""
    from repro.kernels.compat import shard_map_compat
    from repro.launch.mesh import make_stream_mesh

    mesh = make_stream_mesh()
    assert mesh.axis_names == ("data",)
    assert mesh.size == jax.device_count()
    assert make_stream_mesh(devices=1).size == 1  # cap honored

    axes = tuple(mesh.axis_names)
    sizes = tuple(mesh.shape[a] for a in axes)

    def body():
        idx = linear_axis_index(axes, sizes)
        return jax.lax.all_gather(idx, axes, tiled=False)

    got = shard_map_compat(body, mesh, in_specs=(), out_specs=P())()
    np.testing.assert_array_equal(np.asarray(got), np.arange(mesh.size))


@pytest.mark.parametrize("env_dir", [None, "/some/cache/dir"])
def test_enable_compile_cache_placement(monkeypatch, env_dir):
    """``$JAX_COMPILATION_CACHE_DIR`` wins and no other directory is set;
    without it the cache sits at the checkout root, whatever the cwd."""
    from pathlib import Path

    from repro.kernels import compat

    updates = {}
    monkeypatch.setattr(compat.jax.config, "update",
                        lambda k, v: updates.__setitem__(k, v))
    if env_dir is None:
        monkeypatch.delenv(compat.CACHE_ENV, raising=False)
    else:
        monkeypatch.setenv(compat.CACHE_ENV, env_dir)
    monkeypatch.chdir("/")
    path = compat.enable_compile_cache()
    root = Path(__file__).resolve().parents[1]
    if env_dir is None:
        assert path == str(root / ".bgv-compile-cache")
        assert updates["jax_compilation_cache_dir"] == path
    else:
        assert path == env_dir
        assert "jax_compilation_cache_dir" not in updates
    assert updates["jax_persistent_cache_min_compile_time_secs"] == 0.0
