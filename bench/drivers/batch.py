"""Batch jobs, back to back: the general driver of ``"driver": "batch"`` mixes.

One job takes the configuration's graph from its ``.npy`` edge file on
local disk through ``repro.biggraphvis`` (streamed in ``chunk_size``
chunks: SCoDA detection, CMS sizing, superedge aggregation, modularity,
ForceAtlas2 on the supergraph) and renders the drawing to a PNG. Set-up
generates the graph from the seed and runs one job, which warms every
program the window uses. The window runs jobs back to back and closes at
the end of the first job that finishes after ``seconds``.

The check runs the plain reference (``bench/reference``) over the same
edge list once the window has closed and compares the window's last job
with it; every other job of the window has to equal that job.
"""
from __future__ import annotations

import hashlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import harness
from bench.inputs import graph_file, program_config, reference_config
from bench.reference import fa2 as ref_fa2
from bench.reference import render as ref_render
from bench.reference import scoda as ref_scoda
from bench.reference import supergraph as ref_sg


# Parts of the reference that a control computes one precision lower.
LOWERED = ("layout", "modularity", "groups")


def _digest(*arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


class Driver:
    def __init__(self, wl: dict, cfg: dict, mix: dict, seed: int):
        self.wl, self.cfg, self.mix, self.seed = wl, cfg, mix, seed
        self.jobs: list[dict] = []
        self.attempted = self.failed = 0
        self.errors: list[str] = []
        self.out = None  # host copy of the newest job's result

    # -- set-up ----------------------------------------------------------

    def setup(self, warm: bool = True) -> None:
        from repro import StreamConfig

        self.path, self.edges = graph_file(self.cfg, self.seed)
        self.bgv = program_config(self.cfg)
        self.stream = StreamConfig(chunk_size=self.cfg["chunk_size"])
        self.png = harness.cache_dir() / f"{self.wl['name']}.png"
        self.warm = self._job() if warm else None

    def _job(self) -> dict:
        from repro import biggraphvis
        from repro.render.png import write_png

        with jax.profiler.TraceAnnotation("job.pipeline"):
            res = biggraphvis(str(self.path), self.cfg["nodes"], self.bgv,
                              stream=self.stream)
        with jax.profiler.TraceAnnotation("job.render"):
            img, _ = res.render(None)
        with jax.profiler.TraceAnnotation("job.png"):
            write_png(str(self.png), img)
        k = res.n_superedges
        out = {
            "labels": np.asarray(res.labels),
            "pairs": np.asarray(res.supergraph.edges),
            "weights": np.asarray(res.supergraph.weights),
            "sizes": np.asarray(res.sizes),
            "groups": np.asarray(res.groups),
            "n_supernodes": res.n_supernodes,
            "n_superedges": k,
            "modularity": res.modularity,
            "positions": np.asarray(res.positions),
            "image": img,
        }
        out["digest"] = _digest(out["labels"], out["pairs"], out["weights"],
                                out["sizes"], out["positions"], img,
                                np.float64(out["modularity"]))
        out["stages"] = {
            "detect_s": res.stream.stage_seconds["detect_s"],
            "supergraph_s": res.stream.stage_seconds["supergraph_s"],
            "layout_s": res.timings["layout_s"],
            "render_s": res.timings["render_s"],
            "copy_stall_s": res.stream.copy_stall_s,
        }
        return out

    # -- window ----------------------------------------------------------

    def window(self, seconds: float) -> dict:
        t0 = time.perf_counter()
        while True:
            self.attempted += 1
            try:
                out = self._job()
            except Exception as e:  # a failed job ends the window
                self.failed += 1
                self.errors.append(f"{type(e).__name__}: {e}")
                break
            self.jobs.append({"digest": out["digest"], **out["stages"]})
            self.out = out
            if time.perf_counter() - t0 >= seconds:
                break
        elapsed = time.perf_counter() - t0
        done = len(self.jobs)
        return {"job_s": elapsed / done if done else None}

    def release(self) -> None:
        """Free what the program holds on the device before the reference."""
        self.warm = None
        jax.clear_caches()

    # -- check -----------------------------------------------------------

    def reference(self, lower=()) -> dict:
        """The reference result of this run's graph, shaped as a job's output.
        The parts named in ``lower`` (of ``LOWERED``) are computed in
        bfloat16, one precision below what the configuration states:
        float32 layout, float64 modularity, exact colour groups' running
        sum."""
        cfg = self.cfg
        rc = reference_config(cfg)
        if getattr(self, "_ref_labels", None) is None:
            self._ref_labels = ref_scoda.detect(
                self.edges, cfg["nodes"], cfg["scoda"]["degree_threshold"],
                cfg["scoda"]["rounds"], cfg["scoda"]["block_size"])
        if "modularity" in lower or "groups" in lower:
            sg = ref_sg.build(
                self.edges, self._ref_labels, cfg["nodes"], rc,
                modularity_dtype=jnp.bfloat16 if "modularity" in lower else np.float64,
                sum_dtype=jnp.bfloat16 if "groups" in lower else np.int64)
        else:
            if getattr(self, "_ref_sg", None) is None:
                self._ref_sg = ref_sg.build(self.edges, self._ref_labels,
                                            cfg["nodes"], rc)
            sg = self._ref_sg
        lay = ref_fa2.layout(sg["pairs"], sg["weights"], sg["sizes"],
                             sg["n_supernodes"], rc,
                             dtype="bfloat16" if "layout" in lower else "float32")
        pos = np.zeros((cfg["s_cap"], 2), np.float32)
        pos[: len(lay)] = lay
        r = cfg["render"]
        image = ref_render.render(pos, sg["sizes"], sg["groups"], sg["pairs"],
                                  sg["weights"], width=r["width"],
                                  height=r["height"])
        k, cap = sg["n_superedges"], cfg["max_super_edges"]
        pairs = np.full((max(cap, k), 2), cfg["s_cap"], np.int64)
        pairs[:k] = sg["pairs"]
        weights = np.zeros(max(cap, k))
        weights[:k] = sg["weights"]
        return {"labels": sg["labels"], "pairs": pairs, "weights": weights,
                "sizes": sg["sizes"], "groups": sg["groups"],
                "n_supernodes": sg["n_supernodes"], "n_superedges": k,
                "modularity": sg["modularity"], "positions": pos,
                "image": image, "s_layout": len(lay)}

    def numbers(self, out: dict, ref: dict) -> dict:
        """Every number the check compares: ``out`` (a job's output, or the
        control's) against the reference ``ref``."""
        cfg = self.cfg
        k, kr = out["n_superedges"], ref["n_superedges"]
        pairs_differ = (abs(k - kr) + abs(out["n_supernodes"] - ref["n_supernodes"])
                        + int(np.sum(np.any(out["pairs"][: cfg["max_super_edges"]]
                                            != ref["pairs"][: cfg["max_super_edges"]], 1)))
                        + int(np.sum(out["weights"][: cfg["max_super_edges"]]
                                     != ref["weights"][: cfg["max_super_edges"]])))
        live = np.arange(ref["s_layout"]) < ref["n_supernodes"]
        pr = ref["positions"][: ref["s_layout"]][live].astype(np.float64)
        pp = out["positions"][: ref["s_layout"]][live].astype(np.float64)
        # The layout's width (the wider side of its bounding box): FA2's
        # divergence between two float32 summation orders moves the nodes
        # but leaves the width in place; a bfloat16 layout comes out ~9 %
        # narrower.
        width = float(np.max(np.ptp(pr, axis=0))) or 1.0
        gap = np.max(np.abs(pp - pr), axis=1)
        img = np.abs(ref_render.block_means(out["image"], 64)
                     - ref_render.block_means(ref["image"], 64))
        return {
            "labels_differ": int(np.sum(out["labels"] != ref["labels"])),
            "superedges_differ": int(pairs_differ),
            "sizes_differ": int(np.sum(out["sizes"].astype(np.float64)
                                       != ref["sizes"])),
            "groups_differ": int(np.sum(out["groups"] != ref["groups"])),
            "capacity_over": max(0, ref["n_supernodes"] - cfg["s_cap"])
            + max(0, kr - cfg["max_super_edges"]),
            "jobs_differ": sum(j["digest"] != self.out["digest"] for j in self.jobs),
            "modularity_gap": abs(out["modularity"] - ref["modularity"])
            / max(abs(ref["modularity"]), 1e-12),
            "layout_gap": float(np.median(gap)) / width,
            "extent_gap": abs(float(np.max(np.ptp(pp, axis=0))) - width) / width,
            "image_gap": float(np.mean(img)),
        }

    def readings(self) -> dict:
        """The numbers of the newest job (sound) and of two controls, each
        the reference put in the program's place with parts lowered to
        bfloat16: all of ``LOWERED`` (``control``) and the layout alone
        (``control_layout``). All against the reference as the
        configuration states it."""
        if self.out is None:
            return {}
        ref = self.reference()
        return {"sound": self.numbers(self.out, ref),
                "control": self.numbers(self.reference(LOWERED), ref),
                "control_layout": self.numbers(self.reference(("layout",)), ref)}

    def check(self, limits: dict) -> dict:
        self.ref = self.reference()
        got = self.numbers(self.out, self.ref) if self.out is not None else {}
        return {name: {"value": got.get(name), "limit": lim}
                for name, lim in limits.items()}

    # -- per-layer context -------------------------------------------------

    def layer_context(self) -> dict:
        ctx = {"jobs": self.jobs, "cfg": self.cfg}
        ref = getattr(self, "ref", None)
        if ref is not None and self.jobs:
            ctx["work"] = {
                "jobs": len(self.jobs),
                "n_supernodes": ref["n_supernodes"],
                "iterations": self.cfg["layout"]["iterations"],
                "merge_runs": merge_runs(self.edges, ref["labels"],
                                         self.cfg["chunk_size"]),
            }
        return ctx


def merge_runs(edges: np.ndarray, dense_labels: np.ndarray, chunk: int):
    """Per streamed chunk, (state pairs before, distinct pairs in the chunk,
    state pairs after): the live sizes the superedge merge works on."""
    a = dense_labels[edges[:, 0]]
    b = dense_labels[edges[:, 1]]
    lo, hi = np.minimum(a, b), np.maximum(a, b)
    key = np.where(lo != hi, lo * (1 << 32) + hi, -1)
    chunk_of = np.arange(len(key)) // chunk
    valid = key >= 0
    uniq, first = np.unique(key[valid], return_index=True)
    first_chunk = chunk_of[valid][first]
    n_chunks = int(chunk_of[-1]) + 1
    new_per_chunk = np.bincount(first_chunk, minlength=n_chunks)
    runs, state = [], 0
    for c in range(n_chunks):
        sel = valid & (chunk_of == c)
        distinct = len(np.unique(key[sel]))
        after = state + int(new_per_chunk[c])
        runs.append((state, distinct, after))
        state = after
    return runs
