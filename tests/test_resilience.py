"""Fault tolerance (ISSUE 10): kill-and-resume bit-identity of the
streaming pipeline (including across device counts), retry/quarantine at
the EdgeStore boundary under injected faults, corrupt-store diagnostics,
the FA2 divergence sentinel, tile-engine degradation, and the errors.*
observability surface."""
import hashlib
import os
import subprocess
import sys
import tempfile
import textwrap
import time
from pathlib import Path

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import biggraphvis, default_config
from repro.core.cms import CMSConfig
from repro.core import forceatlas2 as fa2
from repro.core.scoda import ScodaConfig
from repro.core.stream import StreamConfig, stream_pipeline
from repro.data.edge_store import (
    CorruptStoreError,
    open_edge_store,
    write_bin,
    write_npy,
    write_shards,
)
from repro.obs.metrics import (
    ERROR_COUNTERS,
    MetricsRegistry,
    REGISTRY,
    ensure_error_counters,
)
from repro.resilience import (
    ChaosConfig,
    ChaosEdgeStore,
    CheckpointMismatchError,
    KillSwitch,
    SimulatedPreemption,
    StreamCheckpointer,
    ValidationError,
    ValidationPolicy,
    latest_step,
    load_arrays,
    restore_latest_valid,
    save,
)

# Small enough to stream in seconds, large enough for multiple chunks per
# pass: 32 chunks/pass × (ROUNDS detect passes + 1 supergraph pass) = 96
# chunk boundaries to kill at.
N, E, CHUNK, ROUNDS, BLOCK = 240, 2000, 64, 2, 32
N_CHUNKS = -(-E // CHUNK)
N_BOUNDARIES = (ROUNDS + 1) * N_CHUNKS
SCODA = ScodaConfig(degree_threshold=8, rounds=ROUNDS, block_size=BLOCK)
CMS = CMSConfig(rows=4, cols=256)
S_CAP, MAX_SE = 512, 2048


def _edges():
    rng = np.random.default_rng(7)
    return rng.integers(0, N, (E, 2), dtype=np.int32)


def _run(source, checkpoint=None, resume=False, stream_cfg=None):
    return stream_pipeline(
        source, N, SCODA, CMS, S_CAP, MAX_SE,
        stream_cfg or StreamConfig(chunk_size=CHUNK),
        checkpoint=checkpoint, resume=resume,
    )


def _digest(labels, gdeg, sg, q) -> str:
    h = hashlib.sha256()
    for a in (labels, gdeg, sg.edges, sg.weights, sg.sizes, sg.labels):
        h.update(np.asarray(a).tobytes())
    h.update(np.float64(q).tobytes())
    return h.hexdigest()


_BASELINE: dict = {}


def _baseline_digest() -> str:
    """Uninterrupted-run digest, computed once per process (module-level
    cache rather than a fixture so the hypothesis property test — whose
    stub wrapper takes no fixture arguments — can use it too)."""
    if "digest" not in _BASELINE:
        labels, gdeg, sg, q, _ = _run(_edges())
        _BASELINE["digest"] = _digest(labels, gdeg, sg, q)
    return _BASELINE["digest"]


# ------------------------------------------------------ checkpoint mechanics


def test_checkpoint_atomic_roundtrip_and_prune(tmp_path):
    d = str(tmp_path)
    tree = {"com": np.arange(10, dtype=np.int32),
            "deg": np.ones(10, dtype=np.int32)}
    for step in range(1, 6):
        save(d, step, tree, extra={"phase": "detect", "chunk": step}, keep=2)
    assert latest_step(d) == 5
    kept = sorted(f for f in os.listdir(d) if f.endswith(".npz"))
    assert kept == ["step_00000004.npz", "step_00000005.npz"]
    assert not any(f.endswith(".tmp") for f in os.listdir(d))
    arrays, meta = load_arrays(d, 5)
    np.testing.assert_array_equal(arrays["com"], tree["com"])
    np.testing.assert_array_equal(arrays["deg"], tree["deg"])
    assert meta["chunk"] == 5 and meta["phase"] == "detect"


def test_restore_latest_valid_walks_back_past_corruption(tmp_path):
    d = str(tmp_path)
    save(d, 1, {"x": np.arange(3)}, extra={"chunk": 1})
    save(d, 2, {"x": np.arange(4)}, extra={"chunk": 2})
    bad = tmp_path / "step_00000002.npz"
    bad.write_bytes(b"not an npz at all")  # post-rename bit-rot
    arrays, meta = restore_latest_valid(d)
    assert meta["step"] == 1 and len(arrays["x"]) == 3
    assert not bad.exists()  # the corrupt newest was unlinked...
    assert not (tmp_path / "step_00000002.npz.meta.json").exists()  # +meta
    assert restore_latest_valid(str(tmp_path / "empty")) is None


def test_restore_latest_valid_predicate_walks_back(tmp_path):
    """A loadable npz whose meta lacks the resume cursor (lost to a crash)
    must be walked back past, not handed to the caller to KeyError on."""
    d = str(tmp_path)
    save(d, 1, {"x": np.arange(3)}, extra={"chunk": 1})
    save(d, 2, {"x": np.arange(4)}, extra={"chunk": 2})
    (tmp_path / "step_00000002.npz.meta.json").unlink()
    arrays, meta = restore_latest_valid(d, valid=lambda a, m: "chunk" in m)
    assert meta["step"] == 1 and meta["chunk"] == 1
    assert not (tmp_path / "step_00000002.npz").exists()


def test_prune_removes_orphaned_meta(tmp_path):
    """A kill between the meta rename and the npz rename leaves a meta
    with no npz; the next save's prune sweeps it."""
    d = str(tmp_path)
    save(d, 1, {"x": np.arange(3)}, extra={"chunk": 1}, keep=2)
    orphan = tmp_path / "step_00000099.npz.meta.json"
    orphan.write_text("{}")
    save(d, 2, {"x": np.arange(3)}, extra={"chunk": 2}, keep=2)
    assert not orphan.exists()
    assert latest_step(d) == 2  # discovery keys off .npz, never the meta


def test_checkpointer_seq_seeded_on_restore(tmp_path):
    """``restore_latest`` must continue the save sequence past the restored
    step (regression: a resumed process restarted _seq at 0, so its saves
    sorted below the on-disk window and were pruned on arrival)."""
    d = str(tmp_path)
    ck = StreamCheckpointer(d, every_chunks=1, keep=3)
    for i in range(5):
        ck.boundary("detect", 0, i, False, lambda: {"x": np.arange(3)})
    assert latest_step(d) == 5
    ck2 = StreamCheckpointer(d, every_chunks=1, keep=3)
    found = ck2.restore_latest()
    assert found is not None and found[1]["step"] == 5
    ck2.boundary("detect", 0, 5, False, lambda: {"x": np.arange(3)})
    assert latest_step(d) == 6  # not pruned-on-arrival under steps 3..5


# ----------------------------------------------------- kill/resume identity


@pytest.mark.parametrize(
    "kill_at", [0, N_CHUNKS - 1, N_CHUNKS + 15, ROUNDS * N_CHUNKS + 5,
                N_BOUNDARIES - 1],
)
def test_kill_and_resume_bit_identical(tmp_path, kill_at):
    """Kill at chunk boundary ``kill_at`` (first chunk, round boundary,
    mid-round, supergraph phase, last boundary), resume, and require the
    final digest to match the uninterrupted run exactly."""
    want = _baseline_digest()
    ks = KillSwitch(kill_at)
    ck = StreamCheckpointer(str(tmp_path), every_chunks=1, on_boundary=ks)
    with pytest.raises(SimulatedPreemption):
        _run(_edges(), checkpoint=ck)
    assert ks.fired and ck.saves > 0
    ck2 = StreamCheckpointer(str(tmp_path), every_chunks=1)
    labels, gdeg, sg, q, stats = _run(_edges(), checkpoint=ck2, resume=True)
    assert stats.resumed_at, "resume should report the restored cursor"
    assert _digest(labels, gdeg, sg, q) == want


@settings(max_examples=6, deadline=None)
@given(st.integers(0, N_BOUNDARIES - 1))
def test_kill_and_resume_property(kill_at):
    """Property over *random* kill points: every chunk boundary is a safe
    preemption point (resume is bit-identical, labels/supergraph/Q)."""
    want = _baseline_digest()
    with tempfile.TemporaryDirectory() as d:
        ks = KillSwitch(kill_at)
        ck = StreamCheckpointer(d, every_chunks=1, on_boundary=ks)
        try:
            _run(_edges(), checkpoint=ck)
            raised = False
        except SimulatedPreemption:
            raised = True
        assert raised and ks.fired
        labels, gdeg, sg, q, stats = _run(
            _edges(), checkpoint=StreamCheckpointer(d, every_chunks=1),
            resume=True,
        )
        assert stats.resumed_at
        assert _digest(labels, gdeg, sg, q) == want


def test_resume_layout_bit_identical(tmp_path):
    """End-to-end through ``biggraphvis``: the resumed run's *layout* (not
    just labels/supergraph) matches the uninterrupted run byte for byte."""
    edges = _edges()
    cfg = default_config(N, E, 8, rounds=ROUNDS, iterations=5)
    from dataclasses import replace

    cfg = replace(cfg, scoda=replace(cfg.scoda, block_size=BLOCK))
    res = biggraphvis(edges, N, cfg, stream=StreamConfig(chunk_size=CHUNK))
    ck = StreamCheckpointer(str(tmp_path), every_chunks=1,
                            on_boundary=KillSwitch(40))
    with pytest.raises(SimulatedPreemption):
        biggraphvis(edges, N, cfg, stream=StreamConfig(chunk_size=CHUNK),
                    checkpoint=ck)
    res2 = biggraphvis(
        edges, N, cfg, stream=StreamConfig(chunk_size=CHUNK),
        checkpoint=StreamCheckpointer(str(tmp_path), every_chunks=1),
        resume=True,
    )
    assert res2.stream.resumed_at
    assert np.asarray(res2.labels).tolist() == np.asarray(res.labels).tolist()
    assert (np.asarray(res2.positions).tobytes()
            == np.asarray(res.positions).tobytes())
    assert res2.modularity == res.modularity


def test_post_resume_checkpoints_advance_past_kill_point(tmp_path):
    """The resumed run's own checkpoints must land *after* the pre-kill
    steps: a second preemption then resumes from post-resume progress,
    not from the first kill point."""
    ck = StreamCheckpointer(str(tmp_path), every_chunks=1,
                            on_boundary=KillSwitch(10))
    with pytest.raises(SimulatedPreemption):
        _run(_edges(), checkpoint=ck)
    stale = latest_step(str(tmp_path))
    ck2 = StreamCheckpointer(str(tmp_path), every_chunks=1)
    labels, gdeg, sg, q, stats = _run(_edges(), checkpoint=ck2, resume=True)
    assert stats.resumed_at and ck2.saves > 0
    assert latest_step(str(tmp_path)) > stale
    _, meta = restore_latest_valid(str(tmp_path))
    assert meta["step"] > stale
    assert _digest(labels, gdeg, sg, q) == _baseline_digest()


def test_resume_walks_back_past_metaless_checkpoint(tmp_path):
    """A checkpoint npz whose meta.json is gone (bit-rot / legacy crash)
    has no resume cursor: ``stream_pipeline`` must fall back to the
    previous checkpoint, not KeyError or skip the fingerprint check."""
    want = _baseline_digest()
    ck = StreamCheckpointer(str(tmp_path), every_chunks=1,
                            on_boundary=KillSwitch(10))
    with pytest.raises(SimulatedPreemption):
        _run(_edges(), checkpoint=ck)
    step = latest_step(str(tmp_path))
    (tmp_path / f"step_{step:08d}.npz.meta.json").unlink()
    labels, gdeg, sg, q, stats = _run(
        _edges(), checkpoint=StreamCheckpointer(str(tmp_path), every_chunks=1),
        resume=True,
    )
    assert stats.resumed_at
    assert _digest(labels, gdeg, sg, q) == want


def test_resume_fingerprint_mismatch_raises(tmp_path):
    ck = StreamCheckpointer(str(tmp_path), every_chunks=1,
                            on_boundary=KillSwitch(2))
    with pytest.raises(SimulatedPreemption):
        _run(_edges(), checkpoint=ck)
    with pytest.raises(CheckpointMismatchError):
        _run(_edges(),
             checkpoint=StreamCheckpointer(str(tmp_path), every_chunks=1),
             resume=True,
             stream_cfg=StreamConfig(chunk_size=2 * CHUNK))


def test_resume_with_no_checkpoint_starts_fresh(tmp_path):
    labels, gdeg, sg, q, stats = _run(
        _edges(), checkpoint=StreamCheckpointer(str(tmp_path)), resume=True,
    )
    assert stats.resumed_at == ""
    assert _digest(labels, gdeg, sg, q) == _baseline_digest()


def test_resume_across_device_counts(tmp_path):
    """A checkpoint written on one device resumes bit-identically on a
    forced-4-device mesh (arrays are stored unsharded; the sharded detect
    path is the engine's bit-identity contract)."""
    want = _baseline_digest()
    ck = StreamCheckpointer(str(tmp_path), every_chunks=1,
                            on_boundary=KillSwitch(N_CHUNKS + 7))
    with pytest.raises(SimulatedPreemption):
        _run(_edges(), checkpoint=ck)
    script = textwrap.dedent("""
        import hashlib, sys
        import numpy as np
        import jax
        from repro.core.cms import CMSConfig
        from repro.core.scoda import ScodaConfig
        from repro.core.stream import StreamConfig, stream_pipeline
        from repro.launch.mesh import make_stream_mesh
        from repro.resilience import StreamCheckpointer

        assert jax.device_count() == 4, jax.device_count()
        rng = np.random.default_rng(7)
        edges = rng.integers(0, {n}, ({e}, 2), dtype=np.int32)
        labels, gdeg, sg, q, stats = stream_pipeline(
            edges, {n}, ScodaConfig(degree_threshold=8, rounds={rounds},
                                    block_size={block}),
            CMSConfig(rows=4, cols=256), {s_cap}, {max_se},
            StreamConfig(chunk_size={chunk}, shard_detect=True,
                         mesh=make_stream_mesh()),
            checkpoint=StreamCheckpointer({d!r}, every_chunks=1),
            resume=True,
        )
        assert stats.resumed_at, "subprocess did not resume"
        h = hashlib.sha256()
        for a in (labels, gdeg, sg.edges, sg.weights, sg.sizes, sg.labels):
            h.update(np.asarray(a).tobytes())
        h.update(np.float64(q).tobytes())
        sys.stdout.write(h.hexdigest())
    """).format(n=N, e=E, rounds=ROUNDS, block=BLOCK, s_cap=S_CAP,
                max_se=MAX_SE, chunk=CHUNK, d=str(tmp_path))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = str(Path(__file__).resolve().parents[1] / "src")
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == want


# ------------------------------------------------ validation & quarantine


def test_transient_io_error_retried_to_identical_result():
    store = ChaosEdgeStore(_edges(), ChaosConfig(
        io_error_offsets=(5 * CHUNK,), transient_attempts=1))
    pol = ValidationPolicy(max_retries=2, retry_backoff_s=0.001)
    labels, gdeg, sg, q, stats = _run(
        store, stream_cfg=StreamConfig(chunk_size=CHUNK, validation=pol))
    assert stats.retries >= 1
    assert stats.quarantined_chunks == 0
    assert store.injected[("io", 5 * CHUNK)] >= 1
    assert _digest(labels, gdeg, sg, q) == _baseline_digest()


def test_permanent_io_error_quarantines_and_completes():
    store = ChaosEdgeStore(_edges(), ChaosConfig(io_error_offsets=(3 * CHUNK,)))
    pol = ValidationPolicy(max_retries=1, retry_backoff_s=0.001)
    reg_before = REGISTRY.counter("errors.quarantined_chunks").value
    labels, gdeg, sg, q, stats = _run(
        store, stream_cfg=StreamConfig(chunk_size=CHUNK, validation=pol))
    # chunk 3 is unreadable on every pass (ROUNDS detect + 1 supergraph):
    # the obs counter tallies per-occurrence, StreamStats reports the
    # distinct chunks (regression: the stats mirror double-counted).
    assert stats.quarantined_chunks == 1
    assert stats.quarantined_chunk_ids == [3]
    assert REGISTRY.counter("errors.quarantined_chunks").value - reg_before \
        == ROUNDS + 1
    labels = np.asarray(labels)
    assert labels.shape == (N,) and (labels >= 0).all()
    assert np.isfinite(q)


def test_quarantine_disabled_propagates_io_error():
    store = ChaosEdgeStore(_edges(), ChaosConfig(io_error_offsets=(0,)))
    pol = ValidationPolicy(max_retries=1, retry_backoff_s=0.001,
                           quarantine=False)
    with pytest.raises(OSError, match="injected I/O error"):
        _run(store, stream_cfg=StreamConfig(chunk_size=CHUNK, validation=pol))


def test_truncated_read_is_io_error_with_byte_offset():
    store = ChaosEdgeStore(_edges(), ChaosConfig(
        truncate_offsets=(2 * CHUNK,), truncate_rows=10))
    pol = ValidationPolicy(max_retries=0, quarantine=False)
    with pytest.raises(OSError, match="short read") as ei:
        _run(store, stream_cfg=StreamConfig(chunk_size=CHUNK, validation=pol))
    assert f"byte offset {(2 * CHUNK + 10) * 8}" in str(ei.value)


def test_bitflip_out_of_range_id_dropped_or_raised():
    cfg = ChaosConfig(bitflip_offsets=(0,))
    pol = ValidationPolicy(retry_backoff_s=0.001)
    store = ChaosEdgeStore(_edges(), cfg)
    labels, gdeg, sg, q, stats = _run(
        store, stream_cfg=StreamConfig(chunk_size=CHUNK, validation=pol))
    # the flip recurs on every pass over chunk 0
    assert stats.dropped_edges >= ROUNDS + 1
    assert np.asarray(labels).shape == (N,)
    with pytest.raises(ValidationError, match="invalid rows"):
        _run(ChaosEdgeStore(_edges(), cfg),
             stream_cfg=StreamConfig(
                 chunk_size=CHUNK,
                 validation=ValidationPolicy(on_invalid="error")))


def test_self_loop_policy():
    edges = _edges()
    edges[::100, 1] = edges[::100, 0]  # plant 20 self-loops
    n_loops = int((edges[:, 0] == edges[:, 1]).sum())
    pol = ValidationPolicy(self_loops="drop")
    _, _, _, _, stats = _run(
        edges.copy(), stream_cfg=StreamConfig(chunk_size=CHUNK, validation=pol))
    assert stats.dropped_edges >= n_loops  # dropped on every pass
    with pytest.raises(ValidationError, match="self-loop"):
        _run(edges.copy(), stream_cfg=StreamConfig(
            chunk_size=CHUNK,
            validation=ValidationPolicy(self_loops="error")))


# ------------------------------------------------- corrupt-store diagnostics


def test_corrupt_npy_store_names_file_and_offset(tmp_path):
    edges = _edges()
    path = write_npy(str(tmp_path / "edges.npy"), edges)
    size = os.path.getsize(path) - 100
    with open(path, "r+b") as f:
        f.truncate(size)
    with pytest.raises(CorruptStoreError) as ei:
        open_edge_store(path)
    msg = str(ei.value)
    assert "edges.npy" in msg and str(size) in msg


def test_corrupt_bin_store_names_trailing_record(tmp_path):
    path = write_bin(str(tmp_path / "edges.bin"), _edges())
    good = os.path.getsize(path)
    with open(path, "ab") as f:
        f.write(b"\x01\x02\x03")  # partial 8-byte record
    with pytest.raises(CorruptStoreError) as ei:
        open_edge_store(path)
    msg = str(ei.value)
    assert "trailing partial record" in msg and str(good) in msg


def test_sharded_store_manifest_validation(tmp_path):
    import json

    d = str(tmp_path / "shards")
    write_shards(d, _edges(), shard_edges=E // 4)
    assert open_edge_store(d).n_edges == E  # clean manifest opens fine

    man = Path(d) / "manifest.json"
    doc = json.loads(man.read_text())
    doc["shards"][1]["edges"] += 7
    man.write_text(json.dumps(doc))
    with pytest.raises(CorruptStoreError, match="shard"):
        open_edge_store(d)

    doc["shards"][1]["edges"] -= 7
    missing = Path(d) / doc["shards"][0]["file"]
    man.write_text(json.dumps(doc))
    missing.unlink()
    with pytest.raises(CorruptStoreError, match="missing"):
        open_edge_store(d)


# -------------------------------------------------- FA2 divergence sentinel


def _layout_inputs(poison: bool):
    from repro.resilience import poison_weights

    rng = np.random.default_rng(3)
    n = 40
    e = rng.integers(0, n, (120, 2), dtype=np.int32)
    w = np.abs(rng.normal(1.0, 0.2, 120)).astype(np.float32)
    if poison:
        w = poison_weights(w, k=4, seed=1)
    mass = np.ones(n, np.float32)
    return e, w, mass, n


def test_nan_guard_off_is_bit_identical_when_clean():
    e, w, mass, n = _layout_inputs(poison=False)
    cfg_off = fa2.FA2Config(iterations=20)
    cfg_on = fa2.FA2Config(iterations=20, nan_guard=True)
    p_off, tr_off, _ = fa2.layout(e, w, mass, n, cfg_off)
    p_on, tr_on, _ = fa2.layout(e, w, mass, n, cfg_on)
    assert np.asarray(p_off).tobytes() == np.asarray(p_on).tobytes()
    assert fa2.recovery_count(tr_on) == 0


def test_nan_guard_recovers_from_poisoned_forces():
    e, w, mass, n = _layout_inputs(poison=True)
    p_off, _, _ = fa2.layout(e, w, mass, n, fa2.FA2Config(iterations=20))
    assert not np.isfinite(np.asarray(p_off)).all()  # unguarded diverges
    p_on, tr_on, _ = fa2.layout(
        e, w, mass, n, fa2.FA2Config(iterations=20, nan_guard=True))
    assert np.isfinite(np.asarray(p_on)).all()  # guarded stays finite
    assert fa2.recovery_count(tr_on) > 0
    # recovery rows never satisfy the adaptive stop (regression: a -1
    # sentinel row must not read as "converged")
    cfg = fa2.FA2Config(iterations=20, nan_guard=True, stop_tolerance=1e9,
                        min_iterations=1)
    _, tr, iters = fa2.layout(e, w, mass, n, cfg)
    assert fa2.recovery_count(tr[:int(iters)]) == int(iters)
    # tol ≤ 1 is the sharp case: a recovery row [-1,-1,s] satisfies
    # row[0] <= tol*row[1] (-1 <= -tol), so without the row[0] >= 0 guard
    # the layout froze right after the first rollback
    cfg = fa2.FA2Config(iterations=20, nan_guard=True, stop_tolerance=0.5,
                        min_iterations=1)
    _, tr, iters = fa2.layout(e, w, mass, n, cfg)
    assert int(iters) == 20, "layout froze on a nan_guard recovery row"
    assert fa2.recovery_count(tr) == 20


# ------------------------------------------------- tile-engine degradation


@pytest.fixture(scope="module")
def pyramid():
    from repro.graph import mode_degree, planted_partition
    from repro.serve.tiles import TileConfig, TilePyramid

    edges, _ = planted_partition(150, 4, 0.3, 0.01, seed=1)
    cfg = default_config(150, len(edges), mode_degree(edges, 150),
                         iterations=5, s_cap=32)
    result = biggraphvis(edges, 150, cfg)
    return TilePyramid(result, TileConfig(tile_size=64, depth=2))


def test_tile_render_failure_isolated_and_not_cached(pyramid):
    from repro.serve.tiles import TileEngine, TileRequest, error_tile

    eng = TileEngine(pyramid, slots=4)
    specs = list(pyramid.specs())
    bad, good = specs[1], specs[2]
    orig = pyramid.render_tile
    before = REGISTRY.counter("errors.failed_tiles").value
    try:
        pyramid.render_tile = lambda s: (_ for _ in ()).throw(
            RuntimeError("render boom")) if s == bad else orig(s)
        rb, rg = TileRequest(bad), TileRequest(good)
        eng.submit(rb)
        eng.submit(rg)
        eng.tick()
        # the failing spec is isolated: its waiter gets the error tile,
        # the healthy spec in the same batch still renders
        assert rb.done and rb.failed
        np.testing.assert_array_equal(rb.tile, error_tile(64))
        assert rg.done and not rg.failed
        assert eng.failed == 1
        assert REGISTRY.counter("errors.failed_tiles").value == before + 1
        assert bad not in eng.cache and good in eng.cache
    finally:
        pyramid.render_tile = orig
    # transient failure: the next request re-renders successfully
    req = TileRequest(bad)
    eng.submit(req)
    eng.tick()
    assert req.done and not req.failed


def test_tile_engine_sheds_overdue_requests(pyramid):
    from repro.serve.tiles import TileEngine, TileRequest

    eng = TileEngine(pyramid, slots=4, deadline_s=0.005)
    req = TileRequest(list(pyramid.specs())[3])
    eng.submit(req)
    time.sleep(0.02)
    done = eng.tick()
    assert req in done and req.failed and req.tile is not None
    assert eng.shed == 1
    with pytest.raises(ValueError, match="deadline_s"):
        TileEngine(pyramid, deadline_s=0.0)


# --------------------------------------------------------- errors.* surface


def test_error_counters_registered_and_dumped():
    reg = MetricsRegistry()
    ensure_error_counters(reg)
    txt = reg.dump_text(prefix="errors.")
    for name in ERROR_COUNTERS:
        assert f"{name} 0" in txt
    # idempotent and non-destructive
    reg.counter("errors.io_retries").inc(3)
    ensure_error_counters(reg)
    assert reg.counter("errors.io_retries").value == 3
