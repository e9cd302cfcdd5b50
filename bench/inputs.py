"""Inputs shared by the drivers: the seed's graph file and the program's
and the reference's view of one configuration file."""
from __future__ import annotations

from dataclasses import replace

import numpy as np

from bench import graphgen, harness


def graph_file(cfg: dict, seed: int):
    """The seed's edge list as ``.npy`` under ``bench/.cache``: generated
    once, read back after. Files of other seeds of the config go first."""
    graphs = harness.cache_dir() / "graphs"
    graphs.mkdir(parents=True, exist_ok=True)
    path = graphs / f"{cfg['name']}-{seed}.npy"
    if path.exists():
        return path, np.load(path)
    for old in graphs.glob(f"{cfg['name']}-*.npy"):
        old.unlink()
    g = cfg["generator"]
    edges = graphgen.planted_partition(cfg["nodes"], g["blocks"], g["p_in"],
                                       g["p_out"], seed)
    tmp = path.with_suffix(".tmp.npy")
    np.save(tmp, edges)
    tmp.rename(path)
    return path, edges


def program_config(cfg: dict):
    """``default_config`` at the published sizes, with the file's overrides;
    every setting the file states must then hold."""
    from repro import default_config

    bgv = default_config(cfg["nodes"], cfg["edges"],
                         cfg["scoda"]["degree_threshold"],
                         rounds=cfg["scoda"]["rounds"],
                         iterations=cfg["layout"]["iterations"],
                         repulsion=cfg["layout"]["repulsion"])
    bgv = replace(bgv, **cfg["overrides"])
    stated = {
        "scoda.block_size": (bgv.scoda.block_size, cfg["scoda"]["block_size"]),
        "cms.rows": (bgv.cms.rows, cfg["cms"]["rows"]),
        "cms.cols": (bgv.cms.cols, cfg["cms"]["cols"]),
        "cms.seed": (bgv.cms.seed, cfg["cms"]["seed"]),
        "s_cap": (bgv.s_cap, cfg["s_cap"]),
        "max_super_edges": (bgv.max_super_edges, cfg["max_super_edges"]),
        "layout.seed": (bgv.layout.seed, cfg["layout"]["seed"]),
        "layout.dtype": (bgv.layout.dtype, cfg["layout"]["dtype"]),
        "layout.repulsion_k": (bgv.layout.repulsion_k, cfg["layout"]["repulsion_k"]),
        "layout.gravity": (bgv.layout.gravity, cfg["layout"]["gravity"]),
        "layout.jitter_tolerance": (bgv.layout.jitter_tolerance,
                                    cfg["layout"]["jitter_tolerance"]),
    }
    wrong = {k: v for k, v in stated.items() if v[0] != v[1]}
    if wrong:
        raise ValueError(f"program settings differ from the config: {wrong}")
    return bgv


def reference_config(cfg: dict) -> dict:
    return {
        "s_cap": cfg["s_cap"],
        "max_super_edges": cfg["max_super_edges"],
        "cms_rows": cfg["cms"]["rows"],
        "cms_cols": cfg["cms"]["cols"],
        "cms_seed": cfg["cms"]["seed"],
        "layout": cfg["layout"],
    }
