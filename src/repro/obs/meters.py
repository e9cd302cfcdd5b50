"""JAX-facing meters: compile events, device/host memory, XLA profiles.

* ``jit_compile_count`` — monotone count of programs compiled (or loaded
  from the persistent cache) via ``jax.monitoring`` (moved here from
  ``repro/serve/tiles.py``; the old import path re-exports). Listener
  registration is **idempotent**: one process-wide listener whatever the
  import path or how many engines are constructed — the pre-move code
  could double-register (and so double-count) if a second registration
  path ever ran. Compile durations also land in the
  ``jax.compile_seconds`` histogram.
* ``update_memory_gauges`` — snapshot ``jax.live_arrays()`` bytes and
  per-device allocator peaks (``device.memory_stats()`` where the backend
  reports them; CPU typically doesn't) into ``jax.*`` gauges.
* ``profile_trace`` — opt-in ``jax.profiler.trace`` wrapper so a CLI flag
  (``--profile DIR``) captures an XLA/TensorBoard profile around any
  phase, degrading to a no-op where the profiler is unavailable.

Importing this module does NOT import jax (lazy inside functions), so
``repro.obs`` stays importable in jax-free tooling contexts.
"""
from __future__ import annotations

import contextlib
import threading

from repro.obs.metrics import REGISTRY

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_register_lock = threading.Lock()
_listener_registered = False


def _on_compile_event(name, *args, **kwargs):
    if name == _COMPILE_EVENT:
        REGISTRY.counter("jax.compiles").inc()
        if args:
            REGISTRY.histogram("jax.compile_seconds").record(args[0])


def register_compile_listener() -> bool:
    """Idempotently attach the compile-event listener. Returns True the
    one time it actually registers, False every call after — however many
    modules, engines, or re-imports ask."""
    global _listener_registered
    with _register_lock:
        if _listener_registered:
            return False
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(_on_compile_event)
        _listener_registered = True
        return True


def jit_compile_count() -> int:
    """Monotone count of programs this process handed to XLA: each one
    compiled, or loaded from the persistent compilation cache (on jax 0.9
    the event wraps both; a load records only its short load time).
    In-memory jit cache hits do not fire it. Counting starts at the first
    call; callers take deltas. The serve benchmark's "steady-state ticks
    trigger zero recompilation" check is a flat delta across the measured
    phase."""
    register_compile_listener()
    return int(REGISTRY.counter("jax.compiles").value)


def live_array_bytes() -> int:
    """Total bytes of every live jax array in the process — the
    host-visible view of device residency (covers backends whose
    ``memory_stats`` is unavailable, e.g. CPU)."""
    import jax

    total = 0
    for a in jax.live_arrays():
        try:
            total += a.size * a.dtype.itemsize
        except Exception:  # deleted/donated buffers race the walk
            pass
    return total


def update_memory_gauges(registry=None) -> dict:
    """Refresh the memory gauges and return their snapshot:

    * ``jax.live_bytes`` — current ``live_arrays`` total (gauge) and its
      process high-watermark ``jax.live_bytes_peak``.
    * ``jax.dev<i>.peak_bytes`` — per-device allocator peak from
      ``device.memory_stats()["peak_bytes_in_use"]`` where the backend
      reports it (GPU/TPU; absent on CPU).
    """
    import jax

    reg = registry if registry is not None else REGISTRY
    live = live_array_bytes()
    reg.gauge("jax.live_bytes").set(live)
    reg.gauge("jax.live_bytes_peak").set_max(live)
    out = {"jax.live_bytes": live}
    for i, dev in enumerate(jax.devices()):
        stats = None
        try:
            stats = dev.memory_stats()
        except Exception:
            pass
        if stats and "peak_bytes_in_use" in stats:
            name = f"jax.dev{i}.peak_bytes"
            reg.gauge(name).set_max(stats["peak_bytes_in_use"])
            out[name] = stats["peak_bytes_in_use"]
    return out


@contextlib.contextmanager
def profile_trace(out_dir: str | None):
    """Capture a ``jax.profiler`` trace of the enclosed block into
    ``out_dir`` (viewable in Perfetto/TensorBoard). ``None`` or an
    unavailable profiler degrade to a plain no-op block — callers treat a
    missing profile as a missing artifact, never an error."""
    if not out_dir:
        yield
        return
    try:
        import jax

        ctx = jax.profiler.trace(str(out_dir))
    except Exception:
        yield
        return
    with ctx:
        yield
