"""Data layer: out-of-core edge stores for the streaming engine
(``edge_store``).

Re-exports from ``edge_store`` are lazy (PEP 562) so running the converter
CLI as ``python -m repro.data.edge_store`` does not import the module twice.
"""
import importlib

__all__ = [
    "EDGE_DTYPE",
    "BinEdgeStore",
    "EdgeStore",
    "EdgeStoreError",
    "InMemoryEdgeStore",
    "NpyEdgeStore",
    "ShardedEdgeStore",
    "as_edge_store",
    "open_edge_store",
    "write_bin",
    "write_npy",
    "write_shards",
]


def __getattr__(name):
    if name in __all__:
        return getattr(importlib.import_module("repro.data.edge_store"), name)
    raise AttributeError(f"module 'repro.data' has no attribute '{name}'")
