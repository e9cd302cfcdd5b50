"""repro.obs: tracer nesting/threading/export, metrics registry semantics,
the compile-meter's idempotent registration, and the stats invariants the
instrumented subsystems promise (StreamStats stage accounting, RenderStats
timing keys, BGVResult layout-iteration agreement)."""
import json
import threading
import time

import numpy as np
import pytest

from repro.core.pipeline import biggraphvis, default_config, layout_supergraph
from repro.graph import mode_degree, planted_partition
from repro.obs.metrics import Histogram, MetricsRegistry
from repro.obs.trace import NULL_SPAN, Tracer, get_tracer, set_tracer
from repro.render import RenderConfig, render_arrays


# ---------------------------------------------------------------------------
# Tracer


def test_span_nesting_and_parenting():
    tr = Tracer()
    with tr.span("a"):
        with tr.span("b"):
            with tr.span("c"):
                pass
        with tr.span("b2"):
            pass
    spans = {s.name: s for s in tr.spans()}
    assert spans["a"].parent is None
    assert spans["b"].parent == spans["a"].span_id
    assert spans["c"].parent == spans["b"].span_id
    assert spans["b2"].parent == spans["a"].span_id
    assert spans["a"].t0 <= spans["b"].t0
    assert spans["b"].t1 <= spans["a"].t1
    assert all(s.duration >= 0 for s in spans.values())


def test_span_attrs_and_set():
    tr = Tracer()
    with tr.span("x", chunk=3) as sp:
        sp.set(extra="y")
    (s,) = tr.spans()
    assert s.attrs == {"chunk": 3, "extra": "y"}


def test_disabled_tracer_is_noop():
    tr = Tracer(enabled=False)
    h = tr.span("anything", a=1)
    assert h is NULL_SPAN
    with h:
        pass
    assert tr.spans() == []


def _profiled_host_events(tmp_path, body) -> dict:
    """Run ``body`` under a ``jax.profiler`` trace; return the host events
    of the trace read back with ``ProfileData``, as name → [(start, end)]."""
    import glob

    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    events.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns))
    return events


def test_enabled_spans_land_in_the_profiler_trace(tmp_path):
    tr = Tracer()

    def body():
        with tr.span("obs.outer", chunk=3):
            with tr.span("obs.inner"):
                time.sleep(0.002)

    events = _profiled_host_events(tmp_path, body)
    # Named by the span alone: attributes stay in the tracer's record.
    ((o0, o1),) = events["obs.outer"]
    ((i0, i1),) = events["obs.inner"]
    assert o0 <= i0 < i1 <= o1
    assert i1 - i0 >= 2e6  # the sleep, on the profiler's ns clock
    assert {s.name for s in tr.spans()} == {"obs.outer", "obs.inner"}


def test_disabled_tracer_writes_no_profiler_event(tmp_path):
    tr = Tracer(enabled=False)

    def body():
        h = tr.span("obs.disabled", chunk=1)
        assert h is NULL_SPAN
        with h:
            time.sleep(0.001)

    events = _profiled_host_events(tmp_path, body)
    assert "obs.disabled" not in events
    assert tr.spans() == []


def test_thread_local_span_stacks():
    tr = Tracer()
    err = []

    def worker(name):
        try:
            with tr.span(name):
                time.sleep(0.01)
                with tr.span(name + ".child"):
                    pass
        except Exception as e:  # pragma: no cover
            err.append(e)

    threads = [
        threading.Thread(target=worker, args=(f"t{i}",)) for i in range(4)
    ]
    with tr.span("main"):
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    assert not err
    spans = {s.name: s for s in tr.spans()}
    # Each thread's child parents to its own root — never to another
    # thread's open span (including main's).
    for i in range(4):
        root, child = spans[f"t{i}"], spans[f"t{i}.child"]
        assert root.parent is None
        assert child.parent == root.span_id
        assert child.tid == root.tid


def test_chrome_export_valid(tmp_path):
    tr = Tracer()
    with tr.span("outer", n=np.int64(7)):
        with tr.span("inner"):
            pass
    path = tr.to_chrome(str(tmp_path / "t.trace.json"))
    doc = json.load(open(path))
    assert "traceEvents" in doc
    events = doc["traceEvents"]
    assert len(events) == 2
    for e in events:
        assert e["ph"] == "X"
        assert e["dur"] >= 0 and e["ts"] >= 0
        assert {"name", "pid", "tid", "args"} <= set(e)
    outer = next(e for e in events if e["name"] == "outer")
    assert outer["args"]["n"] == 7  # numpy scalar coerced to JSON int


def test_jsonl_export(tmp_path):
    tr = Tracer()
    with tr.span("a"):
        pass
    path = tr.to_jsonl(str(tmp_path / "t.jsonl"))
    rows = [json.loads(line) for line in open(path)]
    assert rows[0]["name"] == "a"
    assert rows[0]["parent"] is None


def test_global_tracer_default_disabled():
    assert get_tracer().enabled is False or get_tracer().span("x") is not None
    # set/reset round-trips
    tr = Tracer()
    assert set_tracer(tr) is tr
    assert get_tracer() is tr
    set_tracer(None)
    assert get_tracer().enabled is False


# ---------------------------------------------------------------------------
# Metrics


def test_counter_gauge_basics():
    reg = MetricsRegistry()
    reg.counter("c").inc()
    reg.counter("c").inc(4)
    assert reg.value("c") == 5
    reg.gauge("g").set(2.5)
    reg.gauge("g").set_max(1.0)  # lower: no change
    assert reg.value("g") == 2.5
    reg.gauge("g").set_max(9.0)
    assert reg.value("g") == 9.0
    assert reg.value("missing", default=-1) == -1


def test_registry_kind_mismatch_raises():
    reg = MetricsRegistry()
    reg.counter("x")
    with pytest.raises(TypeError):
        reg.gauge("x")
    with pytest.raises(TypeError):
        reg.histogram("x")


def test_histogram_quantiles():
    h = Histogram("h")
    values = [0.001 * (i + 1) for i in range(1000)]  # 1ms .. 1s uniform
    for v in values:
        h.record(v)
    assert h.count == 1000
    assert h.vmin == pytest.approx(0.001)
    assert h.vmax == pytest.approx(1.0)
    # log2 buckets: worst-case relative error is the bucket width (2x)
    assert h.p50 == pytest.approx(0.5, rel=1.0)
    assert h.p99 == pytest.approx(0.99, rel=1.0)
    assert h.quantile(0.0) <= h.quantile(0.5) <= h.quantile(1.0) <= h.vmax
    assert h.mean == pytest.approx(np.mean(values), rel=1e-6)


def test_histogram_underflow_and_nan():
    h = Histogram("h")
    h.record(0.0)
    h.record(-3.0)
    h.record(float("nan"))
    assert h.count == 0 and h.underflow == 3
    assert h.p50 == 0.0  # no positive samples


def test_registry_dump_and_snapshot():
    reg = MetricsRegistry()
    reg.counter("a.n").inc(2)
    reg.gauge("a.g").set(1.5)
    reg.histogram("b.h").record(0.25)
    text = reg.dump_text()
    assert "a.n 2" in text
    assert "a.g 1.5" in text
    assert "b.h count=1" in text
    snap = reg.snapshot(prefix="a.")
    assert set(snap) == {"a.n", "a.g"}
    assert reg.names(prefix="b.") == ["b.h"]


# ---------------------------------------------------------------------------
# Compile meter (moved from repro.serve.tiles — satellite invariants)


def test_jit_compile_count_reexported_from_serve():
    from repro.obs.meters import jit_compile_count as obs_fn
    from repro.serve.tiles import jit_compile_count as tiles_fn
    import repro.serve as serve

    assert tiles_fn is obs_fn  # the deprecation re-export is the same object
    assert serve.jit_compile_count is obs_fn


def test_compile_listener_idempotent():
    from repro.obs import meters

    first = meters.register_compile_listener()
    # Whatever happened before this test, a second registration in the
    # same process must be refused.
    assert meters.register_compile_listener() is False
    assert first in (True, False)
    # and the counter is readable + monotone
    c0 = meters.jit_compile_count()
    assert meters.jit_compile_count() >= c0


# ---------------------------------------------------------------------------
# Stats invariants (the documented contracts CI relies on)


@pytest.fixture(scope="module")
def small_result():
    n = 400
    edges, _ = planted_partition(n, 8, 0.2, 1e-3, seed=3)
    cfg = default_config(n, len(edges), mode_degree(edges, n),
                         rounds=2, iterations=8)
    t0 = time.perf_counter()
    res = biggraphvis(edges, n, cfg)
    wall = time.perf_counter() - t0
    return res, cfg, wall


def test_stream_stats_stage_seconds_invariants(small_result):
    res, _cfg, wall = small_result
    s = res.stream
    assert s is not None
    for stage, secs in s.stage_seconds.items():
        assert secs >= 0.0, stage
    assert sum(s.stage_seconds.values()) == pytest.approx(s.seconds)
    # stage time is measured inside the pipeline call: never more than the
    # whole call's wall clock
    assert s.seconds <= wall


def test_bgv_layout_iterations_matches_layout(small_result):
    res, cfg, _wall = small_result
    _pos, iters = layout_supergraph(res.supergraph, cfg)
    assert res.timings["layout_iterations"] == iters


def test_render_stats_timings_keys():
    pos = np.array([[0.0, 0.0], [1.0, 1.0], [0.0, 1.0]], np.float32)
    radii = np.array([1.0, 2.0, 1.0], np.float32)
    groups = np.array([0, 1, 2], np.int32)
    edges = np.array([[0, 1], [1, 2]], np.int32)
    _img, stats = render_arrays(
        pos, radii, groups, edges,
        cfg=RenderConfig(width=64, height=64),
    )
    # The documented timing set — launch/render_runner and the CI summary
    # read exactly these keys.
    assert set(stats.timings) == {
        "node_raster_s", "edge_raster_s", "compose_s"
    }
    assert all(v >= 0.0 for v in stats.timings.values())
    assert stats.seconds >= sum(stats.timings.values()) * 0.0  # non-negative


# ---------------------------------------------------------------------------
# End-to-end traced pipeline


def test_traced_pipeline_phase_coverage(tmp_path):
    n = 300
    edges, _ = planted_partition(n, 6, 0.25, 1e-3, seed=4)
    cfg = default_config(n, len(edges), mode_degree(edges, n),
                         rounds=2, iterations=5)
    tr = Tracer(enabled=True)
    from dataclasses import replace

    res = biggraphvis(edges, n, replace(cfg, obs=tr))
    res.render(str(tmp_path / "out.png"))
    names = tr.span_names()
    for phase in ("biggraphvis", "detect", "detect.chunk", "supergraph",
                  "supergraph.chunk", "layout", "biggraphvis.fetch", "render",
                  "render.fetch", "render.edge_setup", "render.compose",
                  "render.png"):
        assert phase in names, (phase, sorted(names))
    # span tree: biggraphvis is an ancestor of the detect chunks
    spans = tr.spans()
    by_id = {s.span_id: s for s in spans}
    chunk = next(s for s in spans if s.name == "detect.chunk")
    seen = set()
    node = chunk
    while node.parent is not None and node.parent not in seen:
        seen.add(node.parent)
        node = by_id[node.parent]
    assert node.name == "biggraphvis"
    # and the publishing side-effects landed in the global registry
    from repro.obs.metrics import REGISTRY

    assert REGISTRY.value("layout.iterations_run") >= 1
    assert REGISTRY.value("stream.chunks") >= 1
    assert REGISTRY.value("render.renders") >= 1
