"""Least HBM traffic of multi-source BFS launches.

Whatever the algorithm (level-synchronous, direction-optimizing, one
lane or many), one launch must read the graph's index arrays once (the
int32 neighbor array, E * 4 bytes, and the int32 row offsets, V * 4),
read its sources (4 bytes each) and write each source's (V,) int32 depth
row once. Levels and lanes that a real implementation re-reads are not
counted, so the share of this bound that a launch reaches cannot pass
100% and says how far memory bounds it.
"""
from __future__ import annotations


def least_bytes(num_vertices: int, num_edges: int, launches: int,
                sources: int) -> int:
    """Bytes over ``launches`` launches that served ``sources`` sources."""
    per_launch = 4 * num_edges + 4 * num_vertices
    return launches * per_launch + sources * (4 + 4 * num_vertices)
