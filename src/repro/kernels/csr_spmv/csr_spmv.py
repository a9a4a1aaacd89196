"""Pallas TPU kernel: pull-mode CSR SpMV / PageRank gather-reduce.

The paper's hot loop is ``y[v] = Σ_{u→v} x[u]`` over the in-CSR edge array —
random reads of the vertex-property array ``x``. TPU adaptation (DESIGN.md
§3): after LOrder, hot vertices occupy a low-id prefix, so the property
array's hot working set is a *contiguous slab*. The kernel keeps the whole
property vector VMEM-resident (graph property arrays are O(MB)) and tiles
the *edge* stream: edges are pre-sorted by destination (in-CSR order) and
padded so each edge block lands in exactly one destination tile, letting
each grid step accumulate into a single output tile.

Grid: ``(num_dst_tiles, blocks_per_tile)`` — the second dimension walks the
edge blocks of one destination tile and accumulates in-place (output
revisiting), initializing at block 0.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

DST_TILE = 512      # output rows per tile (8-sublane aligned x f32)
EDGE_BLOCK = 2048   # edge-stream block (lane aligned)


def _kernel(src_ref, dstloc_ref, val_ref, x_ref, y_ref):
    """One edge block -> accumulate into one destination tile."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    src = src_ref[...]        # (EDGE_BLOCK,) int32 global src ids
    dst = dstloc_ref[...]     # (EDGE_BLOCK,) int32 dst ids local to tile
    val = val_ref[...]        # (EDGE_BLOCK,) f32 edge weight (0 for padding)
    gathered = jnp.take(x_ref[...], src, axis=0) * val
    y_ref[...] += jax.ops.segment_sum(gathered, dst, num_segments=DST_TILE)


def pack_edges(t_indptr: np.ndarray, t_indices: np.ndarray,
               weights: np.ndarray | None = None,
               dst_tile: int = DST_TILE, edge_block: int = EDGE_BLOCK):
    """Host-side packing of the in-CSR edge stream into tile-aligned blocks.

    Returns (src, dst_local, val, blocks_per_tile, num_tiles, n_pad) with
    src/dst/val shaped (num_tiles * blocks_per_tile * edge_block,).
    """
    n = len(t_indptr) - 1
    num_tiles = -(-n // dst_tile)
    n_pad = num_tiles * dst_tile
    dst = np.repeat(np.arange(n, dtype=np.int64), np.diff(t_indptr))
    src = np.asarray(t_indices, dtype=np.int32)
    val = (np.ones(len(src), np.float32) if weights is None
           else np.asarray(weights, np.float32))
    tile_of = dst // dst_tile
    counts = np.bincount(tile_of, minlength=num_tiles)
    bpt = max(1, int(-(-counts.max() // edge_block)))
    cap = bpt * edge_block
    S = np.zeros((num_tiles, cap), np.int32)
    D = np.zeros((num_tiles, cap), np.int32)
    V = np.zeros((num_tiles, cap), np.float32)
    off = 0
    for t in range(num_tiles):
        c = int(counts[t])
        S[t, :c] = src[off:off + c]
        D[t, :c] = (dst[off:off + c] - t * dst_tile).astype(np.int32)
        V[t, :c] = val[off:off + c]
        off += c
    return (S.reshape(-1), D.reshape(-1), V.reshape(-1), bpt, num_tiles, n_pad)


@functools.partial(jax.jit, static_argnames=("blocks_per_tile", "num_tiles",
                                             "n_pad", "interpret"))
def csr_spmv_pallas(src, dst_local, val, x, *, blocks_per_tile: int,
                    num_tiles: int, n_pad: int, interpret: bool = False):
    """y = A^T-gather-reduce(x) with A in packed edge-block form."""
    x_pad = jnp.zeros((n_pad,), x.dtype).at[: x.shape[0]].set(x)
    eb = EDGE_BLOCK
    grid = (num_tiles, blocks_per_tile)

    def edge_map(i, j):
        return (i * blocks_per_tile + j,)

    y = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((eb,), edge_map),            # src ids
            pl.BlockSpec((eb,), edge_map),            # dst local
            pl.BlockSpec((eb,), edge_map),            # edge values
            pl.BlockSpec((n_pad,), lambda i, j: (0,)),  # x resident
        ],
        out_specs=pl.BlockSpec((DST_TILE,), lambda i, j: (i,)),
        out_shape=jax.ShapeDtypeStruct((n_pad,), x.dtype),
        interpret=interpret,
    )(src.reshape(num_tiles * blocks_per_tile, eb).reshape(-1),
      dst_local.reshape(-1), val.reshape(-1), x_pad)
    return y[: x.shape[0]] if x.shape[0] != n_pad else y
