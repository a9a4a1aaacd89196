"""PBBS grid graph: a ``dims``-dimensional torus with about ``n`` vertices.

As PBBS's ``gridGraph`` generator builds its ``3Dgrid`` inputs: the side
is ``round(n ** (1 / dims))``, each vertex is linked to its successor
along every dimension with wraparound, and with ``jumble`` the vertex
labels are permuted at random (the ``_J_`` of the input's name). Every
link is served as two arcs, so the graph is undirected, each vertex has
degree ``2 * dims``, and every vertex lies ``dims * (side // 2)`` hops
from the vertex farthest from it: the high-diameter regime, where the
number of BFS levels, not the degree skew, sets the work.

The lattice is the same for every seed; the seed draws the labels.
"""
from __future__ import annotations

import numpy as np


def _side(config: dict) -> int:
    dims = int(config["dims"])
    return int(round(int(config["n"]) ** (1.0 / dims)))


def sizes(config: dict) -> tuple[int, int]:
    """(vertices, arcs) of the generated edge list, both directions."""
    dims = int(config["dims"])
    v = _side(config) ** dims
    return v, 2 * dims * v


def generate(config: dict, seed: int) -> tuple[int, np.ndarray, np.ndarray]:
    """``(num_vertices, src, dst)`` with int64 host edge arrays."""
    dims, side = int(config["dims"]), _side(config)
    if side < 3:
        raise ValueError(f"a torus of side {side} has duplicate links")
    idx = np.arange(side ** dims, dtype=np.int64).reshape((side,) * dims)
    src = np.tile(idx.ravel(), dims)
    dst = np.concatenate([np.roll(idx, -1, axis=a).ravel()
                          for a in range(dims)])
    if config["jumble"]:
        label = np.random.default_rng([int(seed), 2]).permutation(idx.size)
        src, dst = label[src], label[dst]
    return idx.size, np.concatenate([src, dst]), np.concatenate([dst, src])
