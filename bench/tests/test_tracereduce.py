"""The reduction from a profiler trace to busy time, idle gaps, kernel time
and the breakdown: on hand-made events, and on a small trace recorded on
a TPU v5e by ``record_trace.py`` and kept in ``data/``."""
from pathlib import Path

import pytest

from bench import harness, tracereduce
from bench.tracereduce import Event, Trace

DATA = Path(__file__).parent / "data" / "probe.xplane.pb"


def ev(name, start, dur, text=None):
    return Event(name, float(start), float(dur), text or name)


def test_busy_is_the_union_of_overlapping_ops():
    evs = [ev("a", 0, 10), ev("b", 5, 10), ev("c", 30, 5), ev("d", 90, 20)]
    assert tracereduce.busy_ns(evs, 0, 100) == 15 + 5 + 10
    assert tracereduce.idle_gaps(evs, 0, 100) == [(15, 30), (35, 90)]


def test_gaps_take_the_innermost_annotation():
    spans = [ev("bench.window", 0, 100), ev("job.pipeline", 0, 50),
             ev("job.png", 60, 30)]
    assert tracereduce.label_at(spans, 20) == "job.pipeline"
    assert tracereduce.label_at(spans, 70) == "job.png"
    assert tracereduce.label_at(spans, 95) == "bench.window"
    assert tracereduce.label_at(spans, 150) == "none"


def test_op_names_come_from_the_hlo_instruction():
    text = ("%merge_scatter_combine.2 = (s32[1,8]{1,0}) custom-call(s32[1,8] "
            "%bitcast.26), custom_call_target=\"tpu_custom_call\"")
    assert tracereduce.op_name(text) == "merge_scatter_combine.2"
    user = ev("bitcast.3", 0, 1, "%bitcast.3 = s32[8] bitcast(%merge_scatter_combine.2)")
    assert not tracereduce.is_kernel(user, "merge_scatter_combine")
    assert tracereduce.is_kernel(ev("merge_scatter_combine.2", 0, 1),
                                 "merge_scatter_combine")


def test_self_time_subtracts_nested_ops():
    evs = [ev("while.1", 0, 50), ev("fusion.2", 10, 10), ev("fusion.3", 30, 5),
           ev("cond.4", 60, 20), ev("kernel.5", 65, 10)]
    got = tracereduce.self_ns(evs, 0, 100)
    assert got == {"while.1": 35, "fusion.2": 10, "fusion.3": 5,
                   "cond.4": 10, "kernel.5": 10}


def test_reduce_averages_planes_and_names_kernels():
    ops = [ev("fusion.1", 0, 20), ev("merge_scatter_combine.2", 40, 10)]
    trace = Trace(device_ops={"/device:TPU:0": ops, "/device:TPU:1": ops[:1]},
                  host_spans=[ev("job.png", 20, 20)])
    out = tracereduce.reduce(trace, 0, 100)
    assert out["window_s"] == pytest.approx(100e-9)
    assert out["busy_s"] == pytest.approx(25e-9)  # (30 + 20) / 2 planes
    assert out["breakdown"]["device_ops"][0] == ["fusion.1", pytest.approx(20e-9)]
    assert out["breakdown"]["idle_gaps"][0] == ["none", pytest.approx(50e-9)]
    assert ["job.png", pytest.approx(20e-9)] in out["breakdown"]["idle_gaps"]
    assert tracereduce.kernel_seconds(trace, "merge_scatter_combine", 0, 100) \
        == pytest.approx(5e-9)
    assert tracereduce.reduce(Trace(), 0, 100) == {}


def test_recorded_tpu_trace():
    trace = tracereduce.load(str(DATA), harness.ANNOTATIONS + ("bench.window",))
    assert trace.device_ops, "no device plane in the recorded trace"
    window = [s for s in trace.host_spans if s.name == "bench.window"][-1]
    t0, t1 = window.start_ns, window.end_ns
    out = tracereduce.reduce(trace, t0, t1)
    assert 0 < out["busy_s"] < out["window_s"]
    assert len(out["breakdown"]["device_ops"]) <= 10
    merge = tracereduce.kernel_seconds(trace, "merge_scatter_combine", t0, t1)
    rep = tracereduce.kernel_seconds(trace, "nbody_repulsion", t0, t1)
    assert 0 < merge < out["busy_s"] and 0 < rep < out["busy_s"]
    labels = {label for label, _s in out["breakdown"]["idle_gaps"]}
    assert "job.png" in labels  # the host-only pauses between kernels
    longest = max(s for label, s in out["breakdown"]["idle_gaps"] if label == "job.png")
    assert longest >= 0.015
