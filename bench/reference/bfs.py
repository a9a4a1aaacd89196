"""Plain BFS reference: hop depth from each source along out-edges.

``solve`` gives, for each source, the (V,) int64 depth row the served
BFS must return: 0 at the source, -1 where the source cannot reach.
scipy's breadth-first shortest paths do the work; duplicate edges and
self-loops change no depth.

``depth_of`` measures a request's size for the traffic generator: the
depth of the root's deepest vertex, the levels a level-synchronous BFS
takes to finish less one.

``control`` is the same reference with one guarantee broken, the one a
faster traversal would be tempted to drop: the level loop stops one
level early, so each row's deepest vertices stay unreached.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp
from scipy.sparse.csgraph import dijkstra

UNREACHED = -1
# roots whose depths one pass of ``depth_of`` finds: the bits of a uint64
DEPTH_BATCH = 64


def _adjacency(num_vertices: int, src: np.ndarray, dst: np.ndarray):
    n = int(num_vertices)
    key = np.unique(np.asarray(src, np.int64) * n + np.asarray(dst, np.int64))
    s, d = key // n, key % n
    return sp.csr_matrix((np.ones(len(s), np.float64), (s, d)), shape=(n, n))


def solve(num_vertices: int, src: np.ndarray, dst: np.ndarray,
          sources) -> np.ndarray:
    hops = dijkstra(_adjacency(num_vertices, src, dst), unweighted=True,
                    indices=np.asarray(sources, np.int64))
    hops = np.atleast_2d(hops)
    out = np.full(hops.shape, UNREACHED, np.int64)
    reached = np.isfinite(hops)
    out[reached] = hops[reached].astype(np.int64)
    return out


def depth_of(num_vertices: int, src: np.ndarray, dst: np.ndarray):
    """A function of an array of roots: for each, the hop depth of the
    deepest vertex it reaches. The roots are searched ``DEPTH_BATCH`` at
    a time, one bit of a uint64 each, a level at a time over every arc."""
    n = int(num_vertices)
    key = np.unique(np.asarray(dst, np.int64) * n + np.asarray(src, np.int64))
    head, tail = key // n, key % n
    starts = np.flatnonzero(np.r_[True, head[1:] != head[:-1]])
    heads = head[starts]

    def depths(roots) -> np.ndarray:
        roots = np.asarray(roots, np.int64)
        out = np.zeros(len(roots), np.int64)
        for lo in range(0, len(roots), DEPTH_BATCH):
            chunk = roots[lo:lo + DEPTH_BATCH]
            lanes = np.arange(len(chunk), dtype=np.uint64)
            seen = np.zeros(n, np.uint64)
            np.bitwise_or.at(seen, chunk, np.uint64(1) << lanes)
            front, level = seen.copy(), 0
            while True:
                reach = np.zeros(n, np.uint64)
                reach[heads] = np.bitwise_or.reduceat(front[tail], starts)
                front = reach & ~seen
                advanced = np.bitwise_or.reduce(front)
                if not advanced:
                    break
                level += 1
                seen |= front
                moved = (advanced >> lanes) & np.uint64(1)
                out[lo + np.flatnonzero(moved)] = level
        return out
    return depths


def control(num_vertices: int, src: np.ndarray, dst: np.ndarray,
            sources) -> np.ndarray:
    rows = solve(num_vertices, src, dst, sources)
    for row in rows:
        row[row == row.max()] = UNREACHED if row.max() > 0 else row.max()
    return rows
