"""Reduce a JAX profiler trace to the benchmark's device numbers.

* busy time: the union of the intervals in which an operation ran on a
  device (events of the device planes' op line), inside the window;
* idle share: 1 − busy / window;
* kernel time: the summed device duration of the ops named after the
  kernel;
* breakdown: the device operations that took most self time (nested ops
  subtracted from the loop or conditional holding them), and the longest
  idle gaps, each labelled by the innermost benchmark annotation
  (``jax.profiler.TraceAnnotation``) that was open on the host at the
  gap's midpoint.

Device and host events of one ``ProfileData`` share one clock.
"""
from __future__ import annotations

import bisect
import glob
import os
from dataclasses import dataclass, field

# Lines of a device plane that hold whole programs or steps rather than the
# operations inside them; counting them too would count time twice.
_OUTER_LINES = ("XLA Modules", "Steps", "Framework Name Scope",
                "Framework Ops", "Source code", "XLA TraceMe",
                "Launch Stats", "SparseCore")


@dataclass
class Event:
    name: str
    start_ns: float
    dur_ns: float
    text: str = ""  # the op's whole HLO instruction

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclass
class Trace:
    device_ops: dict = field(default_factory=dict)  # plane → [Event]
    host_spans: list = field(default_factory=list)  # [Event] annotations


def find_xplane(trace_dir: str) -> str | None:
    hits = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))
    return hits[-1] if hits else None


def op_name(text: str) -> str:
    """An op's own name: ``%fusion.3 = f32[...] fusion(...)`` → ``fusion.3``
    (a TPU trace names device ops by their whole HLO instruction)."""
    return text.split(" = ", 1)[0].lstrip("%")


def _event(ev) -> Event:
    return Event(op_name(ev.name), float(ev.start_ns), float(ev.duration_ns),
                 ev.name)


def _op_line(plane):
    lines = [ln for ln in plane.lines if ln.name not in _OUTER_LINES]
    named = [ln for ln in lines if ln.name == "XLA Ops"]
    return named or lines


def _with_modules(plane, ops: list) -> list:
    """Prefix each op's name with the program it ran in (the enclosing
    event of the plane's ``XLA Modules`` line, its hash dropped), since op
    names repeat across programs: ``jit_layout/fusion.38``."""
    mods = sorted((ev.start_ns, ev.start_ns + ev.duration_ns,
                   ev.name.split("(", 1)[0])
                  for ln in plane.lines if ln.name == "XLA Modules"
                  for ev in ln.events)
    if not mods:
        return ops
    starts = [m[0] for m in mods]
    for op in ops:
        i = bisect.bisect_right(starts, op.start_ns) - 1
        if i >= 0 and op.start_ns < mods[i][1]:
            op.name = f"{mods[i][2]}/{op.name}"
    return ops


def load(path: str, annotations: tuple[str, ...]) -> Trace:
    """Device op events per device plane, and the host annotation spans
    whose names are in ``annotations``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = Trace()
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            evs = _with_modules(plane, [
                _event(ev) for ln in _op_line(plane) for ev in ln.events
                if ev.duration_ns > 0])
            if evs:
                out.device_ops[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name in annotations:
                        out.host_spans.append(_event(ev))
    return out


def merge_intervals(intervals):
    """Sorted, disjoint union of (start, end) intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _clip(events, t0, t1):
    return [(max(ev.start_ns, t0), min(ev.end_ns, t1)) for ev in events
            if ev.end_ns > t0 and ev.start_ns < t1]


def busy_ns(events, t0, t1) -> float:
    return sum(e - s for s, e in merge_intervals(_clip(events, t0, t1)))


def idle_gaps(events, t0, t1):
    """[(start, end)] of the stretches inside the window with no device op."""
    gaps, cur = [], t0
    for s, e in merge_intervals(_clip(events, t0, t1)):
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if cur < t1:
        gaps.append((cur, t1))
    return gaps


def label_at(spans, t: float) -> str:
    """Innermost host annotation open at time ``t``, or ``"none"``."""
    inside = [sp for sp in spans if sp.start_ns <= t < sp.end_ns]
    if not inside:
        return "none"
    return min(inside, key=lambda sp: sp.dur_ns).name


def is_kernel(ev: Event, kernel: str) -> bool:
    """A Pallas kernel's op is named after the kernel: ``kernel`` or
    ``kernel.<n>``, after its program's prefix."""
    op = ev.name.rsplit("/", 1)[-1]
    return op == kernel or op.startswith(kernel + ".")


def kernel_ns(events, kernel: str, t0, t1) -> float:
    return sum(e - s for s, e in
               _clip([ev for ev in events if is_kernel(ev, kernel)], t0, t1))


def self_ns(events, t0, t1) -> dict:
    """Per op name, device time inside the window not covered by the ops
    nested in it (a while loop or conditional holds its body's ops)."""
    spans = sorted(((s, e, ev.name) for ev in events for s, e in _clip([ev], t0, t1)),
                   key=lambda x: (x[0], -x[1]))
    totals: dict = {}
    stack: list = []  # [end, name, start, covered] of the open ops

    def close(item):
        end, name, start, covered = item
        totals[name] = totals.get(name, 0.0) + (end - start) - covered

    for s, e, name in spans:
        while stack and stack[-1][0] <= s:
            close(stack.pop())
        if stack:
            stack[-1][3] += min(e, stack[-1][0]) - s
        stack.append([e, name, s, 0.0])
    while stack:
        close(stack.pop())
    return totals


def reduce(trace: Trace, t0: float, t1: float, top: int = 10) -> dict:
    """Busy and window seconds averaged over the device planes, per-kernel
    lookup, and the breakdown of the newest run."""
    planes = list(trace.device_ops.values())
    if not planes:
        return {}
    window_s = (t1 - t0) / 1e9
    busy_s = sum(busy_ns(evs, t0, t1) for evs in planes) / len(planes) / 1e9
    totals: dict = {}
    for evs in planes:
        for name, ns in self_ns(evs, t0, t1).items():
            totals[name] = totals.get(name, 0.0) + ns
    ops = sorted(totals.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(idle_gaps(planes[0], t0, t1), key=lambda g: g[0] - g[1])[:top]
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "breakdown": {
            "device_ops": [[name, ns / 1e9 / len(planes)] for name, ns in ops],
            "idle_gaps": [[label_at(trace.host_spans, (s + e) / 2),
                           (e - s) / 1e9] for s, e in gaps],
        },
    }


def kernel_seconds(trace: Trace, kernel: str, t0: float, t1: float) -> float:
    """Device seconds of ``kernel``, averaged over the device planes."""
    planes = list(trace.device_ops.values())
    if not planes:
        return 0.0
    return sum(kernel_ns(evs, kernel, t0, t1) for evs in planes) / len(planes) / 1e9
