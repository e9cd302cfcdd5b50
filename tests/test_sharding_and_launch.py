"""Sharding rules + step builders + HLO analysis + data pipeline."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config, all_cells
from repro.configs.base import input_specs
from repro.launch.hlo_analysis import analyze_hlo
from repro.sharding.rules import (
    PROFILES,
    block_chunk_spec,
    filter_spec,
    linear_axis_index,
    row_chunk_spec,
    spec_for,
)


@pytest.fixture(scope="module")
def mesh22():
    return jax.make_mesh((1, 1), ("data", "model"))


def _mesh_like(shape, names):
    # an abstract mesh for rule resolution only (no devices needed):
    # jax ≥ 0.5 takes (axis_sizes, axis_names), older takes ((name, size), ...)
    from jax.sharding import AbstractMesh

    try:
        return AbstractMesh(tuple(shape), tuple(names))
    except TypeError:
        return AbstractMesh(tuple(zip(names, shape)))


def test_spec_for_divisibility_fallback():
    mesh = _mesh_like((16, 16), ("data", "model"))
    prof = PROFILES["tp"]
    # heads=8 on a 16-way model axis must degrade to None (gemma3 case)
    s = spec_for((34, 2560, 8, 256), ("layer", "embed", "heads", "head_dim"), prof, mesh)
    assert s == P(None, None, None, None) or s[2] is None
    # heads=32 shards fine (yi case); embed falls to data
    s = spec_for((32, 4096, 32, 128), ("layer", "embed", "heads", "head_dim"), prof, mesh)
    assert s[2] == ("model",) or s[2] == "model"
    assert s[1] in (("data",), "data")


def test_spec_for_no_axis_reuse():
    mesh = _mesh_like((16, 16), ("data", "model"))
    prof = PROFILES["tp"]
    # expert takes model first; mlp must NOT reuse it
    s = spec_for((61, 384, 7168, 2048), ("layer", "expert", "embed", "mlp"), prof, mesh)
    flat = [a for entry in s if entry for a in (entry if isinstance(entry, tuple) else (entry,))]
    assert len(flat) == len(set(flat))
    assert s[1] in ("model", ("model",))


def test_filter_spec_drops_missing_axes():
    mesh = _mesh_like((16, 16), ("data", "model"))
    s = filter_spec(P(("pod", "data"), None, "model"), mesh)
    assert s == P(("data",), None, "model")


def test_filter_spec_multi_axis_entries():
    mesh = _mesh_like((2, 8, 16), ("pod", "data", "model"))
    # every axis present: spec passes through untouched
    s = filter_spec(P(("pod", "data"), None, "model"), mesh)
    assert s == P(("pod", "data"), None, "model")
    # none of an entry's axes present → that entry degrades to None
    s = filter_spec(P(("expert",), "replica", None), mesh)
    assert s == P(None, None, None)


def test_spec_for_non_divisible_on_multi_axis_extent():
    # embed maps to ("pod", "data") = 32-way; 4096 % 32 == 0 shards,
    # 4100 % 32 != 0 degrades that dim (and only that dim) to None.
    mesh = _mesh_like((2, 16, 16), ("pod", "data", "model"))
    prof = PROFILES["tp"]
    s = spec_for((4096, 64), ("embed", "heads"), prof, mesh)
    assert s[0] == ("pod", "data")
    s = spec_for((4100, 64), ("embed", "heads"), prof, mesh)
    assert s[0] is None
    assert s[1] in ("model", ("model",))


def test_spec_for_axis_reuse_across_mapped_tuples():
    # "embed" already consumed "data"; a later dim whose mapping is only
    # "data" must not reuse it even though its size divides the extent.
    mesh = _mesh_like((4, 4), ("data", "model"))
    prof = {"embed": ("data",), "mlp": ("data",)}
    s = spec_for((64, 64), ("embed", "mlp"), prof, mesh)
    assert s == P("data", None)


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


def test_host_mesh_compatible_with_stream_chunk_specs():
    """The production-named host mesh must accept the chunk placements and
    the tp profile (the same code paths the real meshes run)."""
    from jax.sharding import NamedSharding
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh()
    assert set(mesh.axis_names) == {"data", "model"}
    arr = jax.device_put(
        jnp.zeros((4, 2), jnp.int32), NamedSharding(mesh, row_chunk_spec(mesh))
    )
    assert arr.shape == (4, 2)
    s = spec_for((4096, 64, 16), ("embed", "heads", "head_dim"),
                 PROFILES["tp"], mesh)
    NamedSharding(mesh, filter_spec(s, mesh))  # constructible, no raise


def test_all_runnable_cells_have_specs_and_builders():
    """Every non-skipped cell must produce abstract inputs (cheap check —
    the full lower+compile proof is launch/dryrun.py). The biggraphvis
    cells build their abstract args inside launch/steps.py instead."""
    n_run = n_skip = n_bgv = 0
    for arch, shape in all_cells():
        if shape.skip:
            n_skip += 1
            continue
        if arch.family == "bgv":
            n_bgv += 1
            continue
        specs = input_specs(arch, shape)
        assert all(hasattr(v, "shape") for v in specs.values())
        n_run += 1
    assert n_run == 36  # the assigned 40 minus 4 documented skips
    assert n_skip == 4  # long_500k on the pure full-attention archs
    assert n_bgv == 4  # the paper's own workload cells


def test_host_mesh_step_builder_runs_real_data():
    """build_step on a 1×1 mesh with REAL (tiny-shape) data: the same
    sharded step functions that the dry-run lowers actually execute."""
    from dataclasses import replace
    from repro.launch.steps import build_step
    from repro.configs.base import ShapeSpec

    mesh = jax.make_mesh((1, 1), ("data", "model"))
    arch = get_config("granite-moe-1b-a400m")
    small_model = replace(arch.model, n_layers=2, d_model=64, n_heads=4,
                          n_kv_heads=2, head_dim=16, d_ff=64, vocab=97,
                          vocab_padded=112, q_chunk=0,
                          moe=replace(arch.model.moe, n_experts=4, top_k=2,
                                      d_ff_expert=32))
    arch = replace(arch, model=small_model,
                   shapes={"train_4k": ShapeSpec("train_4k", "train",
                                                 seq_len=16, global_batch=2)})
    shape = arch.shapes["train_4k"]
    built = build_step(arch, shape, mesh)
    from repro.models.param import init_params
    from repro.models import transformer as tfm
    from repro.train.optimizer import AdamWConfig, init_opt_state

    params = init_params(jax.random.PRNGKey(0), tfm.param_specs(small_model))
    state = init_opt_state(params, AdamWConfig(state_bits=arch.opt_state_bits))
    rng = np.random.default_rng(0)
    batch = {
        "tokens": jnp.asarray(rng.integers(1, 97, (2, 16)), jnp.int32),
        "loss_mask": jnp.ones((2, 16), jnp.float32),
    }
    with mesh:
        step = jax.jit(built.fn, in_shardings=built.in_shardings,
                       out_shardings=built.out_shardings,
                       donate_argnums=built.donate)
        params, state, metrics = step(params, state, batch)
    assert np.isfinite(float(metrics["loss"]))


def test_hlo_analysis_loop_adjustment():
    """The analyzer must multiply scan-body dots by the trip count."""
    def scanned(w, x):
        def body(h, wi):
            return jnp.tanh(h @ wi), None
        h, _ = jax.lax.scan(body, x, w)
        return h

    w = jax.ShapeDtypeStruct((8, 128, 128), jnp.float32)
    x = jax.ShapeDtypeStruct((32, 128), jnp.float32)
    compiled = jax.jit(scanned).lower(w, x).compile()
    stats = analyze_hlo(compiled.as_text())
    per_dot = 2 * 32 * 128 * 128
    assert stats.n_whiles >= 1
    assert abs(stats.dot_flops - 8 * per_dot) / (8 * per_dot) < 0.05, stats.dot_flops


def test_hlo_analysis_collectives():
    mesh = jax.make_mesh((1,), ("d",))
    from jax.sharding import NamedSharding

    def f(x):
        return jax.lax.with_sharding_constraint(x * 2, NamedSharding(mesh, P()))

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    with mesh:
        compiled = jax.jit(
            f, in_shardings=NamedSharding(mesh, P("d", None))
        ).lower(x).compile()
    stats = analyze_hlo(compiled.as_text())
    assert stats.collective_bytes >= 0  # single-device: no collectives required


def test_lm_stream_deterministic_and_sharded():
    from repro.data.pipeline import LMStream

    s = LMStream(vocab=100, batch=8, seq_len=16, seed=3)
    a = s.batch_at(5)
    b = s.batch_at(5)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    c = s.batch_at(6)
    assert (a["tokens"] != c["tokens"]).any()
    # host shards tile the global batch exactly
    left = s.batch_at(5, shard=(0, 4))["tokens"]
    right = s.batch_at(5, shard=(4, 4))["tokens"]
    np.testing.assert_array_equal(np.concatenate([left, right]), a["tokens"])


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
