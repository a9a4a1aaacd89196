"""Least HBM traffic of multi-source SSSP launches.

One launch must read the graph's index arrays once (int32 neighbors,
E * 4 bytes, and int32 row offsets, V * 4), the int32 edge weights once
(E * 4), its sources (4 bytes each), and write each source's (V,) int32
distance row once. Relaxation rounds are not counted, so Bellman-Ford,
delta-stepping or Dijkstra are held to the same bound.
"""
from __future__ import annotations


def least_bytes(num_vertices: int, num_edges: int, launches: int,
                sources: int) -> int:
    """Bytes over ``launches`` launches that served ``sources`` sources."""
    per_launch = 8 * num_edges + 4 * num_vertices
    return launches * per_launch + sources * (4 + 4 * num_vertices)
