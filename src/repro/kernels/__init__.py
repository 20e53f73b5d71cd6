"""Pallas TPU kernels for the join's compute hot-spots.

distance_tile.py -- brute-force / refine tile (MXU formulation), count+hits
fused_join.py    -- fused gather-refine sweep (scalar-prefetch windows,
                    in-kernel HBM->VMEM gather, count + fill slot scan)
ops.py           -- jit'd wrappers (interpret on CPU, Mosaic on TPU)
ref.py           -- pure-jnp oracles (tests assert allclose against these)
"""
from repro.kernels.ops import (
    distance_tile_counts,
    distance_tile_hits,
    fused_join_hits,
    fused_window_hits,
)

__all__ = [
    "distance_tile_counts",
    "distance_tile_hits",
    "fused_join_hits",
    "fused_window_hits",
]
