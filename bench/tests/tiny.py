"""A tiny stand-in of a cell's configuration, for CPU tests."""
import copy

from bench import graphgen, harness

NODES, BLOCKS, P_IN, P_OUT = 20_000, 20, 0.01, 1e-5


def config(seed: int = 5) -> dict:
    cfg = copy.deepcopy(harness.config("berkstan"))
    edges = graphgen.planted_partition(NODES, BLOCKS, P_IN, P_OUT, seed)
    cfg.update(name="tiny", nodes=NODES, edges=len(edges), s_cap=NODES,
               max_super_edges=1 << 16, overrides={"max_super_edges": 1 << 16},
               chunk_size=1 << 14)
    cfg["generator"].update(blocks=BLOCKS, p_in=P_IN, p_out=P_OUT)
    cfg["scoda"]["degree_threshold"] = graphgen.mode_degree(edges, NODES)
    cfg["cms"]["cols"] = max(256, len(edges) // 1000)
    return cfg


def use(monkeypatch, tmp_path, seed: int = 5):
    """Point the registry at the tiny config, the cache at
    ``tmp_path``, and skip the look for a chip."""
    import bench.run as run
    import repro.kernels.compat as compat

    cfg = config(seed)
    monkeypatch.setattr(harness, "config", lambda name: cfg)
    monkeypatch.setattr(harness, "cache_dir", lambda: tmp_path)
    monkeypatch.setattr(harness, "peaks", lambda kind: {
        "flops_per_s": 197e12, "bytes_per_s": 819e9})
    monkeypatch.setattr(run, "accelerator_ok", lambda wl: True)
    # Programs compiled for the CPU stay out of the checkout's cache.
    monkeypatch.setattr(compat, "enable_compile_cache", lambda: "")
    return run
