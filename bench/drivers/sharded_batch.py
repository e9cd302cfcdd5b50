"""Batch jobs through ``StreamRunner`` on a mesh: the driver of
``"driver": "sharded_batch"`` mixes.

A job is the ``batch`` driver's job with the pipeline run through the
program's multi-chip entry point: ``StreamRunner`` over
``make_stream_mesh(devices)`` with the configuration's ``mesh`` flags
(chunks row-sharded, every detect and aggregation pass data-parallel, the
layout node-partitioned), then ``BGVResult.render`` and the PNG write, on
the first device, as in ``batch``. After each job the driver reads the
``shard.devices.<stage>`` and ``shard.collective_bytes.<stage>`` gauges the
program published. The check adds ``devices_short``: the mesh size less
the fewest devices any stage ran on in the newest job, so a stage that
fell back to one device makes the run not correct.

Set-up stops at once where the program keeps no per-stage device record
(``StreamStats.stage_devices``): ``devices_short`` could not be read.

``layer_context`` adds ``collective_s``: the device seconds per job of the
collective operations in the window's trace, averaged over the device
planes.
"""
from __future__ import annotations

import re

import jax
import numpy as np

from bench import harness, tracereduce
from bench.inputs import graph_file, program_config

batch = harness.load_module("drivers", "batch")

STAGES = ("detect", "supergraph", "layout")

# XLA's collective opcodes; async ones run as ``<op>-start`` / ``<op>-done``.
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")
_COLLECTIVE_RE = re.compile(
    r"(?:\s|calls=%)(?:" + "|".join(COLLECTIVES) + r")(?:-start|-done)?[.(\s-]")


def is_collective(ev) -> bool:
    """A device op that is a collective: named after one (after its
    program's prefix), or whose HLO instruction runs or calls one."""
    return (ev.name.rsplit("/", 1)[-1].startswith(COLLECTIVES)
            or bool(_COLLECTIVE_RE.search(ev.text)))


def collective_seconds(trace, t0: float, t1: float) -> float | None:
    """Device seconds inside [t0, t1) in which a collective op ran (the
    union of their intervals), averaged over the device planes."""
    planes = list(trace.device_ops.values())
    if not planes:
        return None
    return sum(tracereduce.busy_ns([ev for ev in evs if is_collective(ev)],
                                   t0, t1)
               for evs in planes) / len(planes) / 1e9


def window_collective_seconds(trace_dir) -> float | None:
    """``collective_seconds`` of the last ``bench.window`` of the profiler
    trace under ``trace_dir``, or None where there is none."""
    path = tracereduce.find_xplane(str(trace_dir))
    if path is None:
        return None
    trace = tracereduce.load(path, ("bench.window",))
    if not trace.host_spans:
        return None
    window = trace.host_spans[-1]
    return collective_seconds(trace, window.start_ns, window.end_ns)


class Driver(batch.Driver):
    # -- set-up ----------------------------------------------------------

    def setup(self, warm: bool = True) -> None:
        from repro import StreamConfig
        from repro.core.stream import StreamStats
        from repro.launch.mesh import make_stream_mesh
        from repro.launch.stream_runner import StreamRunner, StreamRunnerConfig

        if "stage_devices" not in StreamStats.__dataclass_fields__:
            raise RuntimeError(
                "this program keeps no per-stage device record "
                "(StreamStats.stage_devices), so devices_short cannot be read")
        mesh = self.cfg["mesh"]
        self.path, self.edges = graph_file(self.cfg, self.seed)
        self.bgv = program_config(self.cfg)
        self.runner = StreamRunner(self.bgv, StreamRunnerConfig(
            stream=StreamConfig(chunk_size=self.cfg["chunk_size"],
                                shard_detect=mesh["shard_detect"],
                                shard_layout=mesh["shard_layout"]),
            shard_chunks=mesh["shard_chunks"],
        ), mesh=make_stream_mesh(mesh["devices"]))
        self.png = harness.cache_dir() / f"{self.wl['name']}.png"
        self.warm = self._job() if warm else None

    def _job(self) -> dict:
        from repro.obs.metrics import REGISTRY
        from repro.render.png import write_png

        with jax.profiler.TraceAnnotation("job.pipeline"):
            res = self.runner.run(str(self.path), self.cfg["nodes"])
        with jax.profiler.TraceAnnotation("job.render"):
            img, _ = res.render(None)
        with jax.profiler.TraceAnnotation("job.png"):
            write_png(str(self.png), img)
        out = {
            "labels": np.asarray(res.labels),
            "pairs": np.asarray(res.supergraph.edges),
            "weights": np.asarray(res.supergraph.weights),
            "sizes": np.asarray(res.sizes),
            "groups": np.asarray(res.groups),
            "n_supernodes": res.n_supernodes,
            "n_superedges": res.n_superedges,
            "modularity": res.modularity,
            "positions": np.asarray(res.positions),
            "image": img,
        }
        out["digest"] = batch._digest(
            out["labels"], out["pairs"], out["weights"], out["sizes"],
            out["positions"], img, np.float64(out["modularity"]))
        out["stages"] = {
            "detect_s": res.stream.stage_seconds["detect_s"],
            "supergraph_s": res.stream.stage_seconds["supergraph_s"],
            "layout_s": res.timings["layout_s"],
            "render_s": res.timings["render_s"],
            "copy_stall_s": res.stream.copy_stall_s,
            "devices": {s: int(REGISTRY.value(f"shard.devices.{s}"))
                        for s in STAGES},
            "collective_bytes": {
                s: int(REGISTRY.value(f"shard.collective_bytes.{s}"))
                for s in STAGES},
        }
        return out

    # -- check -----------------------------------------------------------

    def numbers(self, out: dict, ref: dict) -> dict:
        got = super().numbers(out, ref)
        devices = self.out["stages"]["devices"].values()
        got["devices_short"] = self.cfg["mesh"]["devices"] - min(devices)
        return got

    # -- per-layer context -------------------------------------------------

    def layer_context(self) -> dict:
        ctx = super().layer_context()
        secs = window_collective_seconds(
            harness.cache_dir() / "trace" / self.wl["name"])
        if secs is not None and self.jobs:
            ctx["collective_s"] = secs / len(self.jobs)
        return ctx
