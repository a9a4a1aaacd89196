"""GAP-style SSSP edge weights: an integer in [1, 255] per (src, dst).

A splitmix64-style hash of the edge's endpoints in the graph's original
vertex ids, so a weight does not depend on how a server lays the graph
out. The served SSSP uses this weight function; the reference keeps its
own copy so that it takes nothing from the program under test.
"""
from __future__ import annotations

import numpy as np


def edge_weights(src: np.ndarray, dst: np.ndarray) -> np.ndarray:
    key = ((np.asarray(src, np.int64).astype(np.uint64) << np.uint64(32))
           | np.asarray(dst, np.int64).astype(np.uint64))
    key = (key ^ (key >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    key = (key ^ (key >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    key ^= key >> np.uint64(31)
    return (key % np.uint64(255)).astype(np.int64) + 1
