"""Device idle time by innermost host span (``bench/spans.py``): the split
on hand-made events, and the tool end to end on the tiny batch cell."""
import json

import pytest

from bench import spans
from bench.tests import tiny
from bench.tracereduce import Event, Trace


def ev(name, start, dur):
    return Event(name, float(start), float(dur), name)


def _trace(ops, host, planes=1):
    return Trace(device_ops={f"/device:TPU:{i}": ops for i in range(planes)},
                 host_spans=host)


def test_idle_goes_to_the_innermost_span():
    host = [ev("job.pipeline", 0, 100), ev("detect", 10, 60),
            ev("stream.stall", 20, 20), ev("render.png", 80, 10)]
    ops = [ev("fusion.1", 0, 25), ev("fusion.2", 30, 40)]
    got = spans.idle_by_span(_trace(ops, host), 0, 100)
    # idle: [25, 30) under the stall, [70, 80) and [90, 100) under
    # job.pipeline, [80, 90) under render.png
    assert got == {"stream.stall": pytest.approx(5e-9),
                   "job.pipeline": pytest.approx(20e-9),
                   "render.png": pytest.approx(10e-9)}


def test_idle_under_no_span_is_reported_apart():
    host = [ev("render.png", 40, 20)]
    ops = [ev("fusion.1", 0, 10)]
    got = spans.idle_by_span(_trace(ops, host), 0, 100)
    assert got == {"none": pytest.approx(70e-9),
                   "render.png": pytest.approx(20e-9)}


@pytest.mark.parametrize("planes", [1, 2])
def test_idle_parts_sum_to_window_minus_busy(planes):
    host = [ev("bench.window", 5, 190), ev("stream.fill", 12, 50),
            ev("stream.put", 61, 3), ev("biggraphvis.fetch", 150, 40),
            ev("render.png", 160, 5)]
    ops = [ev("a", 0, 15), ev("b", 10, 20), ev("c", 60, 2), ev("d", 100, 70),
           ev("e", 190, 30)]
    trace = _trace(ops, host, planes)
    t0, t1 = 0, 200
    got = spans.idle_by_span(trace, t0, t1)
    out = spans.breakdown(trace, t0, t1, {"stream.fill", "stream.put",
                                          "biggraphvis.fetch", "render.png"}, 1)
    assert sum(got.values()) == pytest.approx(out["window_s"] - out["busy_s"])
    assert out["staging_bound_s"] + out["host_bound_s"] + out["bench_idle_s"] \
        == pytest.approx(out["window_s"] - out["busy_s"])
    assert spans.idle_by_span(Trace(), t0, t1) == {}


def test_tiny_cell_counts_compiles_per_job(monkeypatch, tmp_path, capsys):
    tiny.use(monkeypatch, tmp_path)
    assert spans.main(["--workload", "berkstan.batch", "--seed", "5",
                       "--jobs", "2"]) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert len(out["jobs"]) == 2
    assert all("compiles" in j for j in out["jobs"])
    assert out["jobs"][1]["compiles"] == 0
    assert out["compiles_per_job"] is not None
    # The CPU backend writes no device plane: no device numbers.
    assert out["staging_bound_s"] is None and out["host_bound_s"] is None
    for name in ("stream.stall", "stream.fill", "stream.put", "render.png",
                 "biggraphvis.fetch"):
        assert name in out["program_spans"]
    from repro.obs import get_tracer

    assert get_tracer().enabled is False  # reset after the window
