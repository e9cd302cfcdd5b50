from repro.sharding.rules import (
    row_chunk_spec,
    block_chunk_spec,
    linear_axis_index,
)

__all__ = ["block_chunk_spec", "linear_axis_index", "row_chunk_spec"]
