"""Graph substrate: generators, padding, degrees."""
from repro.graph.generators import (
    planted_partition,
    powerlaw_graph,
)
from repro.graph.utils import (
    degrees,
    mode_degree,
    pad_edges,
    pad_to_multiple,
    EDGE_SENTINEL,
)

__all__ = [
    "EDGE_SENTINEL",
    "degrees",
    "mode_degree",
    "pad_edges",
    "pad_to_multiple",
    "planted_partition",
    "powerlaw_graph",
]
