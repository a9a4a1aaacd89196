"""Plain SSSP reference: shortest-path distances under the GAP weights.

``solve`` gives, for each source, the (V,) int64 distance row the served
SSSP must return: 0 at the source, 2**31 - 1 where the source cannot
reach. Each edge (u, v) weighs ``weights.edge_weights(u, v)`` in original
ids; duplicate edges weigh the same, so one copy of each stands for all.
scipy's Dijkstra does the work.

``depth_of`` measures a request's size for the traffic generator: how
many arcs the deepest of a root's shortest paths needs, counting the
fewest-arc path where several are shortest. A level-synchronous
relaxation settles a root in that many rounds and one more.

``control`` breaks the guarantee a faster relaxation would be tempted to
drop: Bellman-Ford rounds (each relaxes every edge from the previous
round's distances, as a level-synchronous relaxation does) stop one round
before the distances settle.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp
from scipy.sparse.csgraph import dijkstra

from bench.reference.weights import edge_weights

UNREACHED = 2**31 - 1
# roots whose depths the traffic generator asks ``depth_of`` for at once:
# one Dijkstra each, so one at a time, to stop as soon as enough are found
DEPTH_BATCH = 1


def _edges(num_vertices: int, src: np.ndarray, dst: np.ndarray):
    n = int(num_vertices)
    key = np.unique(np.asarray(src, np.int64) * n + np.asarray(dst, np.int64))
    s, d = key // n, key % n
    return s, d, edge_weights(s, d)


def solve(num_vertices: int, src: np.ndarray, dst: np.ndarray,
          sources) -> np.ndarray:
    n = int(num_vertices)
    s, d, w = _edges(n, src, dst)
    adj = sp.csr_matrix((w.astype(np.float64), (s, d)), shape=(n, n))
    dist = np.atleast_2d(dijkstra(adj, indices=np.asarray(sources, np.int64)))
    out = np.full(dist.shape, UNREACHED, np.int64)
    reached = np.isfinite(dist)
    out[reached] = dist[reached].astype(np.int64)
    return out


def depth_of(num_vertices: int, src: np.ndarray, dst: np.ndarray):
    """A function of an array of roots: for each, the fewest arcs on any
    shortest path to the vertex that needs most, over every vertex the
    root reaches. One Dijkstra per root on the weights ``w * 2**k + 1``,
    with ``2**k`` above any arc count, orders paths by distance and then
    by arcs."""
    n = int(num_vertices)
    s, d, w = _edges(n, src, dst)
    scale = float(2 ** int(n).bit_length())
    if 255.0 * n * scale >= 2.0**53:
        raise ValueError(f"{n} vertices: distance and arcs do not fit "
                         "one float64")
    adj = sp.csr_matrix((w * scale + 1.0, (s, d)), shape=(n, n))

    def depths(roots) -> np.ndarray:
        out = []
        for root in np.asarray(roots, np.int64):
            dist = dijkstra(adj, indices=int(root))
            out.append(int(np.fmod(dist[np.isfinite(dist)], scale).max()))
        return np.asarray(out, np.int64)
    return depths


def control(num_vertices: int, src: np.ndarray, dst: np.ndarray,
            sources) -> np.ndarray:
    n = int(num_vertices)
    s, d, w = _edges(n, src, dst)
    order = np.argsort(d, kind="stable")
    s, d, w = s[order], d[order], w[order]
    starts = np.flatnonzero(np.r_[True, d[1:] != d[:-1]])
    heads = d[starts]
    rows = []
    for source in np.atleast_1d(np.asarray(sources, np.int64)):
        dist = np.full(n, UNREACHED, np.int64)
        dist[source] = 0
        prev = dist
        while True:
            du = dist[s]
            cand = np.where(du == UNREACHED, UNREACHED, du + w)
            new = dist.copy()
            new[heads] = np.minimum(dist[heads],
                                    np.minimum.reduceat(cand, starts))
            if np.array_equal(new, dist):
                break
            prev, dist = dist, new
        rows.append(prev)
    return np.stack(rows)
