"""Serving subsystem: the interactive tile-pyramid layout service
(``tiles`` — pan/zoom over a computed ``BGVResult`` with an LRU tile
cache and slot-batched re-renders).

Imports are lazy (PEP 562): importing ``repro.serve`` loads nothing until
an export is asked for.
"""
import importlib

_EXPORTS = {
    "DrillSpec": "repro.serve.tiles",
    "TileCache": "repro.serve.tiles",
    "TileConfig": "repro.serve.tiles",
    "TileEngine": "repro.serve.tiles",
    "TilePyramid": "repro.serve.tiles",
    "TileRequest": "repro.serve.tiles",
    "TileSpec": "repro.serve.tiles",
    "community_subgraph": "repro.serve.tiles",
    "jit_compile_count": "repro.serve.tiles",
    "synthetic_trace": "repro.serve.tiles",
}
__all__ = sorted(_EXPORTS)


def __getattr__(name):
    if name in _EXPORTS:
        return getattr(importlib.import_module(_EXPORTS[name]), name)
    raise AttributeError(f"module 'repro.serve' has no attribute '{name}'")


def __dir__():
    return sorted(set(globals()) | set(__all__))
