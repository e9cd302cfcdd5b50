"""Unified tracing/metrics subsystem — the one instrumentation layer every
subsystem reports through (the paper's claims are performance claims; this
is where "where did this request's 40 ms go?" gets answered across
stream → layout → render → serve boundaries).

Three zero-dependency pieces:

* ``Tracer`` (``repro.obs.trace``) — nested wall-clock spans via context
  managers with thread-local span stacks, exported as Chrome
  trace-event/Perfetto JSON, JSONL, or an indented text tree. An enabled
  tracer also writes each span into a running ``jax.profiler`` trace.
* ``MetricsRegistry`` (``repro.obs.metrics``) — process-global named
  counters / gauges / log-bucket histograms (p50/p99 without numpy);
  ``REGISTRY`` is the global instance the stats dataclasses publish into.
* meters (``repro.obs.meters``) — ``jit_compile_count`` (idempotent
  ``jax.monitoring`` compile-event listener; moved here from
  ``repro/serve/tiles.py``), live-array/device-memory gauges, and the
  ``jax.profiler.trace`` wrapper behind every launcher's ``--profile``.

Wiring: ``StreamConfig.obs`` / ``BGVConfig.obs`` / ``RenderConfig.obs``
carry an explicit ``Tracer``; subsystems fall back to the process-global
tracer (``enable_tracing()`` / ``get_tracer()``), which is what the
``--trace-out`` / ``--metrics-out`` / ``--profile`` flags on every
``repro.launch`` CLI toggle (``repro.obs.cli``). Tracing off costs one
attribute check per span site; tracing on is gated ≤ 3 % overhead on the
stream bench by ``benchmarks/obs_bench.py`` (CI ``obs-smoke``).

Importing ``repro.obs`` pulls only the stdlib pieces; the jax-facing
meters load lazily (PEP 562).
"""
import importlib

from repro.obs.metrics import (  # noqa: F401  (stdlib-only, eager)
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    REGISTRY,
    counter,
    gauge,
    histogram,
)
from repro.obs.trace import (  # noqa: F401  (stdlib-only, eager)
    NULL_TRACER,
    Span,
    Tracer,
    enable_tracing,
    get_tracer,
    set_tracer,
)

_LAZY = {
    "add_obs_args": "repro.obs.cli",
    "jit_compile_count": "repro.obs.meters",
    "live_array_bytes": "repro.obs.meters",
    "obs_session": "repro.obs.cli",
    "profile_trace": "repro.obs.meters",
    "register_compile_listener": "repro.obs.meters",
    "update_memory_gauges": "repro.obs.meters",
}

__all__ = sorted(
    [
        "Counter", "Gauge", "Histogram", "MetricsRegistry", "REGISTRY",
        "NULL_TRACER", "Span", "Tracer", "counter", "gauge", "histogram",
        "enable_tracing", "get_tracer", "set_tracer",
    ]
    + list(_LAZY)
)


def __getattr__(name):
    if name in _LAZY:
        return getattr(importlib.import_module(_LAZY[name]), name)
    raise AttributeError(f"module 'repro.obs' has no attribute '{name}'")


def __dir__():
    return sorted(set(globals()) | set(__all__))
