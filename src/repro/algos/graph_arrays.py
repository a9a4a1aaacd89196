"""Device-side graph representation for the JAX graph kernels.

A `GraphArrays` pytree mirrors the GAP benchmark's working set: out-CSR,
in-CSR (transpose), COO views and degrees, all as jnp arrays. The six
kernels (BFS, PR, BC, SSSP, CC, CC-SV) consume this structure; vertex
relabeling (reordering) changes only the *content* of these arrays, never
the kernel code — exactly the paper's contract.

Shape bucketing (engine/backends.py) uploads graphs *padded* to a shared
(V_bucket, E_bucket) shape so XLA compiles once per bucket instead of
once per exact CSR shape. Padded uploads carry ``vertex_valid`` /
``edge_valid`` masks; the kernels consult them so results on the real
vertices are exact. Sentinel edges are self-loops on the last *padded*
vertex (padding edges forces at least one padded vertex), which keeps
them out of every real vertex's adjacency even before masking.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..core.csr import Graph


class GraphArrays(NamedTuple):
    indptr: jnp.ndarray     # (V+1,) int32 out-CSR
    indices: jnp.ndarray    # (E,)  int32 out-CSR neighbor (dst) ids
    src: jnp.ndarray        # (E,)  int32 COO source per out-edge
    t_indptr: jnp.ndarray   # (V+1,) int32 in-CSR
    t_indices: jnp.ndarray  # (E,)  int32 in-CSR neighbor (src) ids
    t_dst: jnp.ndarray      # (E,)  int32 COO dst per in-edge
    out_degree: jnp.ndarray  # (V,) int32
    in_degree: jnp.ndarray   # (V,) int32
    weights: jnp.ndarray     # (E,) int32 edge weights aligned with out-CSR
    # Bucket-padding masks. None (the default) means "all real": the
    # kernels then skip masking entirely, so unpadded uploads lower to the
    # exact same XLA programs as before bucketing existed.
    vertex_valid: jnp.ndarray | None = None  # (V,) bool, False = padding
    edge_valid: jnp.ndarray | None = None    # (E,) bool, False = sentinel
    # (E,) int32 edge weights aligned with the in-CSR, for the pull
    # relaxation. None (a positional build from the CSR fields alone)
    # makes the SSSP program derive them from ``weights`` itself.
    t_weights: jnp.ndarray | None = None
    # (E,) int32 each out-arc's position in the in-CSR, for the BFS push
    # step. None (a positional build) makes the program derive it.
    out_to_in: jnp.ndarray | None = None

    @property
    def num_vertices(self) -> int:
        return self.indptr.shape[0] - 1

    @property
    def num_edges(self) -> int:
        return self.indices.shape[0]


@jax.jit
def in_csr_positions(indices: jnp.ndarray) -> jnp.ndarray:
    """(E,) out-CSR destinations -> (E,) int32 each out-arc's position in
    the in-CSR. The in-CSR is the out-CSR's arcs stably sorted by
    destination (`Graph.transpose` sorts the (dst, src) keys), so the map
    is that sort's inverse permutation; a bucket's sentinel tail, whose
    destination is the last vertex, maps to itself. Two sorts on the
    device, no scatter."""
    pos = jnp.arange(indices.shape[0], dtype=jnp.int32)
    _, order = lax.sort((indices, pos), num_keys=1, is_stable=True)
    return lax.sort((order, pos), num_keys=1)[1]


def edge_weights(src: np.ndarray, dst: np.ndarray,
                 canonical_ids: np.ndarray | None = None) -> np.ndarray:
    """Deterministic int weights in [1, 255] per canonical edge identity.

    Weights are a pure function of the edge's (src, dst) in *canonical*
    ids — the graph's own ids, or ``canonical_ids[v]`` mapping back to the
    original layout for a relabeled graph — so they are relabel-invariant
    and identical across execution backends (single-device `to_device`
    and the sharded partitioner both call this).
    """
    h_src = np.asarray(src, dtype=np.int64)
    h_dst = np.asarray(dst, dtype=np.int64)
    if canonical_ids is not None:
        canon = np.asarray(canonical_ids, dtype=np.int64)
        h_src, h_dst = canon[h_src], canon[h_dst]
    # splitmix-style hash of canonical (src, dst) -> stable per-edge weight
    key = (h_src.astype(np.uint64) << np.uint64(32)) | h_dst.astype(np.uint64)
    key = (key ^ (key >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    key = (key ^ (key >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    key ^= key >> np.uint64(31)
    return (key % np.uint64(255)).astype(np.int32) + 1


def to_device(g: Graph, weight_seed: int = 17,
              canonical_ids: np.ndarray | None = None,
              pad_to: tuple[int, int] | None = None) -> GraphArrays:
    """Upload a host Graph; deterministic int weights in [1, 255] for SSSP.

    ``pad_to=(num_v, num_e)`` uploads the graph padded to that bucket
    shape: extra vertices are isolated (degree 0, ``vertex_valid`` False),
    extra edges are self-loops on the last padded vertex (``edge_valid``
    False, weight 1 in both CSR views). Kernels mask them out, so results
    restricted to the real ``[:V]`` prefix equal the unpadded run. When
    edges are padded there must be at least one padded vertex to host the
    sentinels — `engine.backends.bucket_dims` guarantees that.
    The in-CSR position of each out-arc (``out_to_in``) is derived on the
    device from the uploaded destinations.
    """
    t = g.transpose
    src = g.edge_src.astype(np.int64)
    dst = g.indices.astype(np.int64)
    w = edge_weights(src, dst, canonical_ids)
    # the same hash on the in-CSR's (src, dst) pairs: every arc keeps its
    # weight in both views, duplicates included
    t_w = edge_weights(t.indices, t.edge_src, canonical_ids)
    _ = weight_seed  # reserved; hash keeps weights relabel-invariant

    n, e = g.num_vertices, g.num_edges
    if pad_to is None:
        num_v, num_e = n, e
    else:
        num_v, num_e = pad_to
        if num_v < n or num_e < e:
            raise ValueError(f"pad_to {pad_to} smaller than graph ({n}, {e})")
        if num_e > e and num_v == n:
            raise ValueError("edge padding needs at least one padded vertex "
                             "to host sentinel self-loops")
    if (num_v, num_e) == (n, e):
        indices = jnp.asarray(g.indices, jnp.int32)
        return GraphArrays(
            indptr=jnp.asarray(g.indptr, jnp.int32),
            indices=indices,
            src=jnp.asarray(src, jnp.int32),
            t_indptr=jnp.asarray(t.indptr, jnp.int32),
            t_indices=jnp.asarray(t.indices, jnp.int32),
            t_dst=jnp.asarray(t.edge_src, jnp.int32),
            out_degree=jnp.asarray(g.out_degree, jnp.int32),
            in_degree=jnp.asarray(g.in_degree, jnp.int32),
            weights=jnp.asarray(w, jnp.int32),
            t_weights=jnp.asarray(t_w, jnp.int32),
            out_to_in=in_csr_positions(indices),
        )

    sentinel = num_v - 1  # always a padded vertex when sentinel edges exist

    def pad_v(arr, fill=0):
        out = np.full(num_v, fill, np.int32)
        out[:n] = arr
        return out

    def pad_e(arr, fill):
        out = np.full(num_e, fill, np.int32)
        out[:e] = arr
        return out

    def pad_ptr(ptr):
        # padded vertices own no real edges; the whole sentinel tail is
        # booked to the last padded vertex so the CSR stays monotone.
        out = np.full(num_v + 1, e, np.int64)
        out[:n + 1] = ptr
        out[num_v] = num_e
        return out.astype(np.int32)

    vertex_valid = np.zeros(num_v, bool)
    vertex_valid[:n] = True
    edge_valid = np.zeros(num_e, bool)
    edge_valid[:e] = True
    indices = jnp.asarray(pad_e(g.indices, sentinel))
    return GraphArrays(
        indptr=jnp.asarray(pad_ptr(g.indptr)),
        indices=indices,
        src=jnp.asarray(pad_e(src, sentinel)),
        t_indptr=jnp.asarray(pad_ptr(t.indptr)),
        t_indices=jnp.asarray(pad_e(t.indices, sentinel)),
        t_dst=jnp.asarray(pad_e(t.edge_src, sentinel)),
        out_degree=jnp.asarray(pad_v(g.out_degree)),
        in_degree=jnp.asarray(pad_v(g.in_degree)),
        weights=jnp.asarray(pad_e(w, 1)),
        vertex_valid=jnp.asarray(vertex_valid),
        edge_valid=jnp.asarray(edge_valid),
        t_weights=jnp.asarray(pad_e(t_w, 1)),
        out_to_in=in_csr_positions(indices),
    )
