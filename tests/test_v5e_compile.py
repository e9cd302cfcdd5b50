"""Compile every Pallas kernel family for a TPU v5e at main-path shapes.

Interpret mode (what the rest of the suite runs on CPU) accepts block
shapes and in-kernel primitives that the TPU compiler refuses, so these
tests lower each family for a *described* ``v5e:2x2`` chip — no chip is
attached, nothing runs, and a passing compile is not a chip run. The
shapes are the ones the BerkStan-shaped pipeline hands each kernel
(685,230 nodes, ~6.7M edges; CMS widths from ``default_cms_cols`` for
BerkStan and LiveJournal; the default ``max_super_edges``).

The topology is described inside a module-scoped fixture, never at import
time: only one process may load the TPU library, and a worker that
described it while collecting would make the workers disagree on which
tests exist. The persistent compilation cache is off around the compiles
(a compile for a described chip cannot be read back without one).
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.coloring import PALETTE
from repro.kernels.cms.cms_update import cms_update_pallas
from repro.kernels.grid.tiled import far_field_pallas, near_field_pallas
from repro.kernels.merge.sorted_merge import merge_combine_pallas
from repro.kernels.raster.splat import count_scatter_pallas, disk_accum_pallas
from repro.kernels.repulsion.nbody import repulsion_pallas
from repro.kernels.segment.seg_matmul import segment_sum_pallas

BERKSTAN_NODES = 685_230
BERKSTAN_CMS_COLS = 6_649  # default_cms_cols(6.65M edges)
LIVEJOURNAL_CMS_COLS = 34_681  # default_cms_cols(34.68M edges)
MAX_SUPER_EDGES = 262_144  # default_config's max_super_edges cap
SMOKE_SUPER_EDGES = 1 << 20  # chip_smoke.py's capacity for this graph
WEBGOOGLE_SUPER_EDGES = 1 << 21  # the web-Google benchmark's capacity
CHUNK = 1 << 20  # edges per streamed chunk in the chip smoke run
N_GROUPS = len(PALETTE)  # render accumulation channels
SKITTER_SMALL_DISKS = 1 << 16  # as-Skitter's small-disk splat, padded to 2^k
DISK_SAMPLES = 18 * 18  # render/raster's _BBOX² samples per small disk


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # pragma: no cover - no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("cols", [BERKSTAN_CMS_COLS, LIVEJOURNAL_CMS_COLS])
def test_cms_update_compiles(one_chip, cols):
    """Node-keyed sizing: one key per node, 4 hash rows."""
    compiled = cms_update_pallas.lower(
        _sds((4, cols), "float32", one_chip),
        _sds((4, BERKSTAN_NODES), "int32", one_chip),
        _sds((BERKSTAN_NODES,), "float32", one_chip),
        cols=cols,
    ).compile()
    _assert_kernel(compiled)


def test_segment_sum_compiles(one_chip):
    """Grid cell stats: [Σm·x, Σm·y, Σm] into G² = 64² cells."""
    compiled = segment_sum_pallas.lower(
        _sds((CHUNK, 3), "float32", one_chip),
        _sds((CHUNK,), "int32", one_chip),
        n_segments=64 * 64,
    ).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("n", [8192, 65536])
def test_repulsion_compiles(one_chip, n):
    """Exact supergraph repulsion up to the default s_cap."""
    compiled = repulsion_pallas.lower(
        _sds((n, 2), "float32", one_chip),
        _sds((n,), "float32", one_chip),
        _sds((n,), "float32", one_chip),
        kr=80.0,
    ).compile()
    _assert_kernel(compiled)


def test_grid_far_field_compiles(one_chip):
    n, cells = BERKSTAN_NODES, 64 * 64
    compiled = far_field_pallas.lower(
        _sds((n, 2), "float32", one_chip),
        _sds((n,), "float32", one_chip),
        _sds((n,), "int32", one_chip),
        _sds((cells, 2), "float32", one_chip),
        _sds((cells,), "float32", one_chip),
        kr=80.0,
    ).compile()
    _assert_kernel(compiled)


def test_grid_near_field_compiles(one_chip):
    n = BERKSTAN_NODES
    compiled = near_field_pallas.lower(
        _sds((n, 2), "float32", one_chip),
        _sds((n,), "float32", one_chip),
        _sds((n,), "int32", one_chip),
        kr=80.0,
        window=32,
    ).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("cap,c", [
    (MAX_SUPER_EDGES, CHUNK),
    (MAX_SUPER_EDGES, 1 << 14),
    (SMOKE_SUPER_EDGES, CHUNK),
    (WEBGOOGLE_SUPER_EDGES, CHUNK),
])
def test_merge_combine_compiles(one_chip, cap, c):
    """Superedge state (default, BerkStan's and web-Google's capacity) +
    one deduped chunk run."""
    compiled = merge_combine_pallas.lower(
        _sds((cap,), "int32", one_chip),
        _sds((cap,), "int32", one_chip),
        _sds((cap,), "float32", one_chip),
        _sds((c,), "int32", one_chip),
        _sds((c,), "int32", one_chip),
        _sds((c,), "float32", one_chip),
        s_cap=1 << 16,
    ).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("n,side", [
    ((1 << 16) * 8, 1024),
    ((1 << 16) * 8, 256),
    (SKITTER_SMALL_DISKS * DISK_SAMPLES, 1024),
], ids=["1024", "256", "skitter_small_disks"])
def test_count_scatter_compiles(one_chip, n, side):
    """Splats into the [len(PALETTE), side, side] accumulator: one 64k-edge
    render chunk × 8 samples (full image and one served tile), and the
    largest call the benchmark makes, as-Skitter's small-disk splat, whose
    scalar-prefetched step tables are the longest."""
    size = N_GROUPS * side * side
    compiled = count_scatter_pallas.lower(
        _sds((n,), "int32", one_chip),
        _sds((n,), "int32", one_chip),
        size=size,
        acc=_sds((size,), "int32", one_chip),
    ).compile()
    _assert_kernel(compiled)


def test_disk_accum_compiles(one_chip):
    """Large node disks (dense per-pixel pass) over a 1024² image."""
    n = 1024
    compiled = disk_accum_pallas.lower(
        _sds((n,), "float32", one_chip),
        _sds((n,), "float32", one_chip),
        _sds((n,), "float32", one_chip),
        _sds((n,), "int32", one_chip),
        n_groups=N_GROUPS,
        h=1024,
        w=1024,
    ).compile()
    _assert_kernel(compiled)
