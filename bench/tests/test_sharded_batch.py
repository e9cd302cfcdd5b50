"""The four-chip ``skitter.sharded_batch`` cell: its configuration keeps one
layout shape on every seed, its driver reads ``correct`` on four forced
host devices and not correct when the layout falls back to one, and its
collective readers read a synthetic trace and job list."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from bench import graphgen, harness, tracereduce
from bench.reference import fa2, scoda, supergraph
from bench.tests.test_generator import SEEDS

CELL = "skitter.sharded_batch"


def test_skitter_fits_capacity_in_one_layout_shape():
    cfg = harness.config("skitter")
    g = cfg["generator"]
    shapes = set()
    for seed in SEEDS:
        edges = graphgen.planted_partition(cfg["nodes"], g["blocks"], g["p_in"],
                                           g["p_out"], seed)
        assert abs(len(edges) - cfg["edges"]) / cfg["edges"] < 0.02
        labels = scoda.detect(edges, cfg["nodes"],
                              cfg["scoda"]["degree_threshold"],
                              cfg["scoda"]["rounds"], cfg["scoda"]["block_size"])
        dense, n_super = supergraph.dense(labels)
        n_superedges = len(supergraph.superedges(edges, dense)[1])
        assert n_super <= cfg["s_cap"], (seed, n_super)
        assert n_superedges <= cfg["max_super_edges"], (seed, n_superedges)
        shapes.add(fa2.layout_slots(n_super, n_superedges, cfg["s_cap"],
                                    cfg["max_super_edges"]))
    assert shapes == {(cfg["s_cap"], cfg["max_super_edges"])}, shapes


# One process on 4 forced host devices: the tiny stand-in of the cell (the
# ``tiny`` config with the cell's mesh), run as ``bench/run.py`` runs it,
# sound and then with the layout's node partition refused.
_SCRIPT = """
import json, sys
sys.path[:0] = [{checkout!r}, {src!r}]
import jax
assert jax.device_count() == 4, jax.device_count()
import bench.run as run
import repro.core.forceatlas2 as fa2
import repro.kernels.compat as compat
from bench import harness
from bench.tests import tiny

cfg = tiny.config()
cfg["mesh"] = harness.config("skitter")["mesh"]
harness.config = lambda name: cfg
harness.cache_dir = lambda: __import__("pathlib").Path({tmp!r})
run.accelerator_ok = lambda wl: True
compat.enable_compile_cache = lambda: ""
for planted in (False, True):
    if planted:
        fa2._sharded_fallback_reason = lambda n, cfg, mesh: "planted"
    assert run.main(["--workload", {cell!r}, "--seed", "5", "--seconds", "2",
                     "--trace", "0"]) == 0
"""


@pytest.fixture(scope="module")
def four_device_runs(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("sharded")
    checkout = str(harness.CHECKOUT)
    script = textwrap.dedent(_SCRIPT).format(
        checkout=checkout, src=os.path.join(checkout, "src"), tmp=str(tmp),
        cell=CELL)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    lines = [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
    assert len(lines) == 2, proc.stdout[-4000:]
    return [json.loads(ln) for ln in lines]


def test_sharded_driver_on_four_devices_is_correct(four_device_runs):
    sound = four_device_runs[0]
    assert sound["correct"] is True, sound["checks"]
    assert sound["checks"]["devices_short"] == {"value": 0, "limit": 0}
    assert sound["failed"] == 0 and sound["attempted"] >= 1
    assert set(sound["metrics"]) == {"job_s", "peak_hbm_gb", "setup_s"}


def test_single_device_layout_reads_devices_short(four_device_runs):
    planted = four_device_runs[1]
    assert planted["checks"]["devices_short"]["value"] == 3
    assert planted["correct"] is False
    # Everything else still equals the reference: only the devices differ.
    assert all(c["value"] <= c["limit"] for name, c in planted["checks"].items()
               if name != "devices_short")


def _ev(name, start, dur, text=""):
    return tracereduce.Event(name, float(start), float(dur), text)


def test_collective_seconds_of_a_synthetic_trace():
    drv = harness.load_module("drivers", "sharded_batch")
    gather = "%fusion.9 = f32[64]{0} fusion(f32[16]{0} %p), kind=kCustom, calls=%all-gather-fusion.2"
    trace = tracereduce.Trace(device_ops={
        "/device:TPU:0": [
            _ev("jit_a/all-reduce.1", 100, 50),
            _ev("jit_a/fusion.3", 120, 200),  # compute overlapping it
            _ev("jit_a/all-gather-start.2", 400, 10),
            _ev("jit_a/all-gather-done.2", 405, 20),  # overlaps its start
            _ev("jit_a/fusion.9", 900, 100, gather),
            _ev("jit_a/all-reduce.7", 1950, 100),  # half past the window
        ],
        "/device:TPU:1": [
            _ev("jit_a/reduce-scatter.4", 0, 300),  # starts before it
            _ev("jit_a/copy.5", 500, 100, "%copy.5 = s32[8]{0} copy(%x)"),
        ],
    })
    # Plane 0: 50 + (400..425) 25 + 100 + 50 = 225 ns; plane 1: 200 ns.
    got = drv.collective_seconds(trace, 100, 2000)
    assert got == pytest.approx((225 + 200) / 2 / 1e9)
    assert drv.collective_seconds(tracereduce.Trace(), 0, 1) is None


def test_collective_s_and_gb_readers():
    s = harness.load_module("metrics", "collective_s")
    gb = harness.load_module("metrics", "collective_gb")
    jobs = [{"collective_bytes": {"detect": 3e9, "supergraph": 1e9, "layout": 0}},
            {"collective_bytes": {"detect": 3e9, "supergraph": 2e9, "layout": 1e9}}]
    assert gb.read({"jobs": jobs}) == pytest.approx(5.0)
    assert s.read({"collective_s": 1.5}) == 1.5
    # A program without the counters, or a run without a device trace.
    assert gb.read({"jobs": [{"detect_s": 1.0}]}) is None
    assert s.read({"jobs": jobs}) is None
