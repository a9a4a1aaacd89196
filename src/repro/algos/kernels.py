"""The six GAP-style graph kernels in pure JAX (paper §5.1).

Each kernel is edge-parallel (COO segment ops; BFS and SSSP pull over the
in-CSR with a segmented scan) with `lax.while_loop` outer iteration — the
JAX-native rendering of the level-synchronous / iterative structure the
paper's C++ GAPS kernels use. All are `jit`-able; vertex property arrays
are the reuse-heavy state the paper reorders for.

Bucket padding: when a `GraphArrays` carries ``vertex_valid`` /
``edge_valid`` masks (shape-bucketed uploads, see engine/backends.py),
every kernel excludes sentinel edges and padded vertices, so results on
the real ``[:V]`` prefix are exactly the unpadded results. The masks are
``None`` for unpadded uploads and the branches below are resolved at
trace time, so unbucketed serving lowers to the identical XLA program as
before.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from .graph_arrays import GraphArrays

INF_I32 = jnp.int32(2**31 - 1)


def _seg_sum(vals, segs, n):
    return jax.ops.segment_sum(vals, segs, num_segments=n)


def _seg_min(vals, segs, n):
    return jax.ops.segment_min(vals, segs, num_segments=n)


# ------------------------------------------- in-CSR segmented reduction
#
# BFS and SSSP pull over the in-CSR: its arcs are sorted by destination,
# so each vertex's in-arcs form one contiguous run of the edge axis, and
# the per-step reduction onto destinations is a segmented scan along that
# axis instead of a scatter onto unsorted ids. The scan doubles: pass k
# combines x[i] with x[i - 2**k] where both lie in one run, so after
# ⌈log2 d⌉ passes the last arc of every run of length <= d holds the
# reduction of the whole run. Every pass is a dense shift and select.

class InRuns(NamedTuple):
    """The in-CSR's destination runs, as the pull steps read them."""
    offset: jnp.ndarray  # (E,) int32 position of each in-arc in its run
    ends: jnp.ndarray    # (V,) int32 each vertex's last in-arc (0 if none)
    has_in: jnp.ndarray  # (V,) bool, in-degree > 0
    passes: jnp.ndarray  # () int32 ⌈log2⌉ of the largest real in-degree


def _in_runs(g: GraphArrays) -> InRuns:
    """Loop-invariant run layout of ``g``'s in-CSR. The pass count is
    taken over real vertices only, so a bucket's sentinel tail (booked to
    a padded vertex, all masked) does not lengthen the scan; it is a
    traced value, so one compiled program serves every graph of a
    bucket."""
    e = g.num_edges
    pos = jnp.arange(e, dtype=jnp.int32)
    first = (pos == 0) | (g.t_dst != jnp.roll(g.t_dst, 1))
    offset = pos - lax.cummax(jnp.where(first, pos, 0))
    deg = g.t_indptr[1:] - g.t_indptr[:-1]
    if g.vertex_valid is not None:
        deg = jnp.where(g.vertex_valid, deg, 0)
    longest = jnp.maximum(deg.max(), 1)
    passes = (32 - lax.clz(longest - 1)).astype(jnp.int32)
    return InRuns(offset, jnp.maximum(g.t_indptr[1:] - 1, 0),
                  g.t_indptr[1:] > g.t_indptr[:-1], passes)


def _segment_reduce(x: jnp.ndarray, runs: InRuns, op, empty) -> jnp.ndarray:
    """(..., E) per-in-arc values -> (..., V) ``op``-reduction over each
    vertex's in-arc run (``empty`` where it has none). ``op`` is
    idempotent (OR, min), so overlapping windows are harmless."""
    if x.shape[-1] == 0:                 # no arcs, so every run is empty
        return jnp.full(x.shape[:-1] + runs.has_in.shape, empty, x.dtype)

    def one_pass(k, x):
        shift = jnp.left_shift(jnp.int32(1), k)
        return jnp.where(runs.offset >= shift,
                         op(x, jnp.roll(x, shift, axis=-1)), x)

    x = lax.fori_loop(0, runs.passes, one_pass, x)
    return jnp.where(runs.has_in, x[..., runs.ends], empty)


# ---------------------------------------------------------------------- BFS
#
# The lanes of a multi-source BFS travel as bits: lane l is bit l % 32 of
# word l // 32, so one level gathers and scans one uint32 per arc and
# word, whatever the number of lanes up to 32, and the segmented OR is a
# bitwise OR.

def _lane_bits(num_lanes: int) -> jnp.ndarray:
    """(S, 1) uint32: each lane's bit within its word."""
    lane = jnp.arange(num_lanes, dtype=jnp.uint32) % 32
    return jnp.left_shift(jnp.uint32(1), lane)[:, None]


def _pack_lanes(flags: jnp.ndarray) -> jnp.ndarray:
    """(S, V) bool -> (⌈S/32⌉, V) uint32 lane bits."""
    s = flags.shape[0]
    bits = jnp.where(flags, _lane_bits(s), jnp.uint32(0))
    bits = jnp.pad(bits, ((0, -s % 32), (0, 0)))
    # the bits of one word are distinct, so their sum is their OR
    return bits.reshape(-1, 32, bits.shape[-1]).sum(axis=1,
                                                    dtype=jnp.uint32)


def _unpack_lanes(words: jnp.ndarray, num_lanes: int) -> jnp.ndarray:
    """(⌈S/32⌉, V) uint32 lane bits -> (S, V) bool."""
    per_lane = jnp.repeat(words, 32, axis=0)[:num_lanes]
    return (per_lane & _lane_bits(num_lanes)) != 0


def _bfs_levels(g: GraphArrays, runs: InRuns, sources: jnp.ndarray
                ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Level-synchronous BFS from (S,) sources at once -> (S, V) depths
    and each lane's level-loop trip count (its eccentricity + 1)."""
    n, s = g.num_vertices, sources.shape[0]
    start = jnp.arange(n, dtype=jnp.int32)[None, :] == sources[:, None]
    depth0 = jnp.where(start, 0, -1).astype(jnp.int32)
    front0 = _pack_lanes(start)

    def cond(state):
        _, front, _, _ = state
        return (front != 0).any()

    def body(state):
        depth, front, seen, level = state
        # gather(prop, src) over the in-CSR: the hot access the paper
        # optimizes — property reads follow the g.t_indices layout.
        with jax.named_scope("bfs_frontier_gather"):
            active = front[:, g.t_indices]
            if g.edge_valid is not None:
                active = jnp.where(g.edge_valid, active, jnp.uint32(0))
        with jax.named_scope("bfs_segment_reduce"):
            touched = _segment_reduce(active, runs, jnp.bitwise_or,
                                      jnp.uint32(0))
        new = touched & ~seen
        depth = jnp.where(_unpack_lanes(new, s), level + 1, depth)
        return depth, new, seen | new, level + 1

    depth, _, _, _ = lax.while_loop(
        cond, body, (depth0, front0, front0, jnp.int32(0)))
    # a lane's loop ran while its frontier was not empty: one level past
    # its deepest vertex
    return depth, depth.max(axis=1) + 1


@jax.jit
def bfs(g: GraphArrays, source: jnp.ndarray) -> jnp.ndarray:
    """Level-synchronous BFS (pull). Returns depth (V,), -1 unreached."""
    return _bfs_levels(g, _in_runs(g), jnp.reshape(source, (1,)))[0][0]


# ----------------------------------------------------------------- PageRank
def pagerank(g: GraphArrays, num_iters: int = 20, damping: float = 0.85,
             tol: float = 1e-6) -> jnp.ndarray:
    return _pagerank(g, num_iters, damping, tol)


@jax.jit
def _pagerank(g: GraphArrays, num_iters, damping, tol):
    """Pull-mode PR: r[v] = (1-d)/N + d * Σ_{u→v} r[u]/outdeg[u].

    With bucket masks, N is the count of *real* vertices and all rank mass
    (base, dangling redistribution, the rank vector itself) stays on real
    vertices; padded vertices hold rank 0 throughout, so the real prefix
    matches the unpadded run.
    """
    n = g.num_vertices
    valid = g.vertex_valid
    if valid is None:
        n_real = jnp.float32(n)
        dangling_mask = g.out_degree == 0
    else:
        n_real = valid.sum().astype(jnp.float32)
        dangling_mask = (g.out_degree == 0) & valid
    base = (1.0 - damping) / n_real
    outdeg = jnp.maximum(g.out_degree, 1).astype(jnp.float32)

    def body(state):
        r, _, it = state
        contrib = r / outdeg
        # pull over in-CSR: gather(contrib, t_indices) is the reuse-heavy read
        summed = _seg_sum(contrib[g.t_indices], g.t_dst, n)
        # dangling mass redistributed uniformly (GAP semantics)
        dangling = jnp.where(dangling_mask, r, 0.0).sum()
        r_new = base + damping * (summed + dangling / n_real)
        if valid is not None:
            r_new = jnp.where(valid, r_new, 0.0)
        err = jnp.abs(r_new - r).sum()
        return r_new, err, it + 1

    def cond(state):
        _, err, it = state
        return (it < num_iters) & (err > tol)

    r0 = jnp.ones((n,), jnp.float32) / n_real
    if valid is not None:
        r0 = jnp.where(valid, r0, 0.0)
    r, _, _ = lax.while_loop(cond, body, (r0, jnp.float32(jnp.inf), jnp.int32(0)))
    return r


# --------------------------------------------------- PageRank via Pallas SpMV
def pagerank_spmv(g: GraphArrays, spmv_src: jnp.ndarray,
                  spmv_dst: jnp.ndarray, spmv_val: jnp.ndarray,
                  num_iters: int = 20, damping: float = 0.85,
                  tol: float = 1e-6, *, blocks_per_tile: int,
                  num_tiles: int, n_pad: int,
                  interpret: bool = False) -> jnp.ndarray:
    """`_pagerank` with the pull relaxation routed through the Pallas
    CSR-SpMV kernel (kernels/csr_spmv) inside the same ``while_loop``.

    ``spmv_src``/``spmv_dst``/``spmv_val`` are the graph's in-CSR edge
    stream pre-packed by `kernels.csr_spmv.pack_edges` into dst-tiled
    blocks — after LOrder the hot-prefix rows land in the first tiles and
    the VMEM-resident property vector's hot slab stays resident across
    the edge stream. Sentinel edges of bucketed uploads carry
    ``spmv_val == 0`` so they contribute nothing; the remaining mask
    handling is identical to `_pagerank`, and results agree with it to
    float tolerance (the tile-blocked summation order differs).

    Not jitted here: the engine wraps it per pack shape
    (``blocks_per_tile``/``num_tiles``/``n_pad`` are static arguments of
    the pallas_call), so its compile-cache keys stay pack-aware.
    """
    from ..kernels.csr_spmv.csr_spmv import csr_spmv_pallas

    n = g.num_vertices
    valid = g.vertex_valid
    if valid is None:
        n_real = jnp.float32(n)
        dangling_mask = g.out_degree == 0
    else:
        n_real = valid.sum().astype(jnp.float32)
        dangling_mask = (g.out_degree == 0) & valid
    base = (1.0 - damping) / n_real
    outdeg = jnp.maximum(g.out_degree, 1).astype(jnp.float32)

    def body(state):
        r, _, it = state
        contrib = r / outdeg
        summed = csr_spmv_pallas(
            spmv_src, spmv_dst, spmv_val, contrib,
            blocks_per_tile=blocks_per_tile, num_tiles=num_tiles,
            n_pad=n_pad, interpret=interpret)
        dangling = jnp.where(dangling_mask, r, 0.0).sum()
        r_new = base + damping * (summed + dangling / n_real)
        if valid is not None:
            r_new = jnp.where(valid, r_new, 0.0)
        err = jnp.abs(r_new - r).sum()
        return r_new, err, it + 1

    def cond(state):
        _, err, it = state
        return (it < num_iters) & (err > tol)

    r0 = jnp.ones((n,), jnp.float32) / n_real
    if valid is not None:
        r0 = jnp.where(valid, r0, 0.0)
    r, _, _ = lax.while_loop(cond, body,
                             (r0, jnp.float32(jnp.inf), jnp.int32(0)))
    return r


# ------------------------------------------------- Connected Components (LP)
@jax.jit
def cc_labelprop(g: GraphArrays) -> jnp.ndarray:
    """CC by iterative min-label propagation over the symmetrized edges."""
    n = g.num_vertices

    def body(state):
        lab, _ = state
        lab_src, lab_dst = lab[g.src], lab[g.indices]
        if g.edge_valid is not None:
            lab_src = jnp.where(g.edge_valid, lab_src, INF_I32)
            lab_dst = jnp.where(g.edge_valid, lab_dst, INF_I32)
        m1 = _seg_min(lab_src, g.indices, n)
        m2 = _seg_min(lab_dst, g.src, n)
        new = jnp.minimum(lab, jnp.minimum(m1, m2))
        return new, (new != lab).any()

    def cond(state):
        return state[1]

    lab0 = jnp.arange(n, dtype=jnp.int32)
    lab, _ = lax.while_loop(cond, body, (lab0, jnp.bool_(True)))
    return lab


# ------------------------------------------- Connected Components (CC-SV)
@jax.jit
def cc_shiloach_vishkin(g: GraphArrays) -> jnp.ndarray:
    """Shiloach-Vishkin: alternating hook + pointer-jumping (paper's CC_SV)."""
    n = g.num_vertices

    def body(state):
        parent, _ = state
        pu = parent[g.src]
        pv = parent[g.indices]
        # hook: root(pu) adopts smaller pv (and symmetrically)
        lo = jnp.minimum(pu, pv)
        hi = jnp.maximum(pu, pv)
        if g.edge_valid is not None:
            # sentinel edges hook nothing: min with INF is a no-op
            lo = jnp.where(g.edge_valid, lo, INF_I32)
            hi = jnp.where(g.edge_valid, hi, 0)
        parent1 = parent.at[hi].min(lo)
        # pointer jumping to full compression
        def jump(st):
            p, _ = st
            p2 = p[p]
            return p2, (p2 != p).any()
        parent2, _ = lax.while_loop(lambda st: st[1], jump,
                                    (parent1, jnp.bool_(True)))
        return parent2, (parent2 != parent).any()

    p0 = jnp.arange(n, dtype=jnp.int32)
    parent, _ = lax.while_loop(lambda st: st[1], body, (p0, jnp.bool_(True)))
    return parent


# -------------------------------------------------------- SSSP (Bellman-Ford)
def _in_weights(g: GraphArrays) -> jnp.ndarray:
    """Edge weights in in-CSR order. An upload without them derives them:
    the in-CSR is the out-CSR's arcs stably sorted by destination (sources
    ascend within each run in both), and sentinel arcs are the tail of
    both views."""
    if g.t_weights is not None:
        return g.t_weights
    return g.weights[jnp.argsort(g.indices, stable=True)]


def _sssp_rounds(g: GraphArrays, runs: InRuns, t_weights: jnp.ndarray,
                 source: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """`sssp` plus the relaxation loop's trip count."""
    n = g.num_vertices
    dist0 = jnp.full((n,), INF_I32).at[source].set(0)

    def body(state):
        dist, _, it = state
        with jax.named_scope("sssp_candidate_gather"):
            du = dist[g.t_indices]
            cand = jnp.where(du == INF_I32, INF_I32, du + t_weights)
            if g.edge_valid is not None:
                cand = jnp.where(g.edge_valid, cand, INF_I32)
        with jax.named_scope("sssp_segment_reduce"):
            relaxed = _segment_reduce(cand, runs, jnp.minimum, INF_I32)
        new = jnp.minimum(dist, relaxed)
        return new, (new != dist).any(), it + 1

    def cond(state):
        _, changed, it = state
        return changed & (it < n)

    dist, _, rounds = lax.while_loop(cond, body,
                                     (dist0, jnp.bool_(True), jnp.int32(0)))
    return dist, rounds


@jax.jit
def sssp(g: GraphArrays, source: jnp.ndarray) -> jnp.ndarray:
    """Bellman-Ford with edge-parallel relaxation (paper's SSSP), pulled
    over the in-CSR."""
    return _sssp_rounds(g, _in_runs(g), _in_weights(g), source)[0]


# -------------------------------------------- Betweenness Centrality (Brandes)
@jax.jit
def bc_single_source(g: GraphArrays, source: jnp.ndarray) -> jnp.ndarray:
    """Brandes dependency accumulation for one source (unweighted)."""
    n = g.num_vertices
    depth = bfs(g, source)
    max_level = depth.max()

    # forward: path counts sigma, level-synchronous over out-edges
    sigma0 = jnp.zeros((n,), jnp.float32).at[source].set(1.0)
    du = depth[g.src]
    dv = depth[g.indices]
    tree_edge = (dv == du + 1) & (du >= 0)
    if g.edge_valid is not None:
        tree_edge &= g.edge_valid

    def fwd(level, sigma):
        mask = tree_edge & (du == level)
        add = _seg_sum(jnp.where(mask, sigma[g.src], 0.0), g.indices, n)
        return sigma + add

    sigma = lax.fori_loop(0, max_level + 1, fwd, sigma0)

    # backward: delta[u] += sigma[u]/sigma[v] * (1 + delta[v]) along tree edges
    def bwd(i, delta):
        level = max_level - 1 - i
        mask = tree_edge & (du == level)
        sig_v = jnp.maximum(sigma[g.indices], 1e-30)
        contrib = jnp.where(mask, sigma[g.src] / sig_v * (1.0 + delta[g.indices]), 0.0)
        return delta + _seg_sum(contrib, g.src, n)

    delta = lax.fori_loop(0, jnp.maximum(max_level, 0), bwd,
                          jnp.zeros((n,), jnp.float32))
    return delta.at[source].set(0.0)


# ------------------------------------------------------- k-NN beam search
#
# The search-serving workload (ROADMAP item 4, Coleman et al.): greedy
# best-first traversal of a fixed out-degree k-NN graph with a bounded
# beam, one `lax.while_loop` per query in the PR 7 fused-loop style.
# Candidates are ranked by the lexicographic pair
#
#     (float32_dist_bits, canonical_id)
#
# squared-L2 distances are non-negative, so their float32 bit patterns
# are order-preserving as int32 — and the canonical (original) vertex id
# breaks every distance tie layout-invariantly. That single invariant is
# what buys bit-identical results across {exact, bucketed, sharded}
# backends and any reorder. (A packed ``bits << 31 | id`` int64 key would
# be one array instead of two, but x64 stays off repo-wide; `lexsort`
# over the pair is the same total order.) KNN_SENTINEL exceeds the bit
# pattern of any real distance (+inf is 0x7F800000), so empty beam slots
# and already-visited candidates sort strictly last.

KNN_SENTINEL = 2**31 - 1  # int32 max


def _dist_bits(dist: jnp.ndarray) -> jnp.ndarray:
    return lax.bitcast_convert_type(dist.astype(jnp.float32), jnp.int32)


def knn_search(g: GraphArrays, vectors: jnp.ndarray, canon: jnp.ndarray,
               entry: jnp.ndarray, query: jnp.ndarray, *, k_out: int,
               beam_width: int, k_return: int, max_steps: int
               ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """One query -> ``(ids, visited)``: the ``k_return`` nearest served
    vertex ids found (-1 in empty slots) and the (V,) visited mask whose
    per-query sum is the visit-frequency telemetry the reorder policy
    consumes.

    ``vectors`` are in served order, ``canon`` maps served -> original
    id. Rows must hold exactly ``k_out`` distinct non-self neighbors
    (self-loop padding is inert: a row's owner is already visited when
    the row is expanded). Not module-jitted — the engine wraps it per
    (shape, params) so compile-cache keys stay static-arg-aware, like
    ``pagerank_spmv``.
    """
    n = g.num_vertices
    q = query.astype(jnp.float32)
    sent = jnp.int32(KNN_SENTINEL)

    def dists(ids):
        diff = vectors[ids] - q
        return jnp.sum(diff * diff, axis=-1)

    e = entry.astype(jnp.int32)
    bits0 = jnp.full((beam_width,), sent, jnp.int32)
    bits0 = bits0.at[0].set(_dist_bits(dists(e[None])[0]))
    tie0 = jnp.full((beam_width,), sent, jnp.int32)
    tie0 = tie0.at[0].set(canon[e])
    ids0 = jnp.zeros((beam_width,), jnp.int32).at[0].set(e)
    exp0 = jnp.zeros((beam_width,), jnp.bool_)
    visited0 = jnp.zeros((n,), jnp.bool_).at[e].set(True)

    def cond(state):
        bits, _, _, exp, _, step = state
        return (~exp & (bits < sent)).any() & (step < max_steps)

    def body(state):
        bits, tie, ids, exp, visited, step = state
        # nearest unexpanded slot under the (bits, tie) order: min bits
        # first, canonical id breaks distance ties (each vertex enters
        # the beam at most once, so ties are genuinely distinct vertices)
        masked_bits = jnp.where(exp, sent, bits)
        m = masked_bits.min()
        slot = jnp.argmin(jnp.where(exp | (bits != m), sent, tie))
        v = ids[slot]
        exp = exp.at[slot].set(True)
        nbrs = lax.dynamic_slice(g.indices, (g.indptr[v],), (k_out,))
        fresh = ~visited[nbrs]
        visited = visited.at[nbrs].set(True)
        # gather(vectors, nbrs): the reuse-heavy read the reorder packs
        nbits = jnp.where(fresh, _dist_bits(dists(nbrs)), sent)
        ntie = jnp.where(fresh, canon[nbrs], sent)
        all_bits = jnp.concatenate([bits, nbits])
        all_tie = jnp.concatenate([tie, ntie])
        all_ids = jnp.concatenate([ids, nbrs.astype(jnp.int32)])
        all_exp = jnp.concatenate(
            [exp, jnp.zeros((k_out,), jnp.bool_)])
        keep = jnp.lexsort((all_tie, all_bits))[:beam_width]
        return (all_bits[keep], all_tie[keep], all_ids[keep],
                all_exp[keep], visited, step + 1)

    bits, _, ids, _, visited, _ = lax.while_loop(
        cond, body, (bits0, tie0, ids0, exp0, visited0, jnp.int32(0)))
    # the beam is kept sorted by every merge, so the head is the result
    top = jnp.where(bits[:k_return] < sent, ids[:k_return], -1)
    return top, visited


def knn_search_multi(g: GraphArrays, vectors: jnp.ndarray,
                     canon: jnp.ndarray, entry: jnp.ndarray,
                     queries: jnp.ndarray, valid: jnp.ndarray, *,
                     k_out: int, beam_width: int, k_return: int,
                     max_steps: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Batched search: (S, d) queries -> ((S, k_return) served ids,
    (V,) int32 visit counts). ``valid`` masks padded query lanes out of
    the visit accounting (pad lanes repeat row 0 and would otherwise
    inflate the telemetry)."""
    ids, visited = jax.vmap(
        lambda qv: knn_search(g, vectors, canon, entry, qv, k_out=k_out,
                              beam_width=beam_width, k_return=k_return,
                              max_steps=max_steps))(queries)
    visits = (visited & valid[:, None]).sum(axis=0).astype(jnp.int32)
    return ids, visits


# ---------------------------------------------- batched multi-source variants
#
# The serving engine amortizes one compile over many concurrent queries:
# sources become a batch axis, BFS's as bits of its frontier words (above)
# and the others' via `vmap`. The while/fori loops inside the single-source
# kernels batch cleanly — JAX's while_loop batching rule runs until every
# lane's predicate clears and select-freezes converged lanes.

@jax.jit
def bfs_multi(g: GraphArrays, sources: jnp.ndarray) -> jnp.ndarray:
    """Batched BFS: (S,) sources -> (S, V) depth rows, -1 unreached."""
    return _bfs_levels(g, _in_runs(g), sources)[0]


@jax.jit
def sssp_multi(g: GraphArrays, sources: jnp.ndarray) -> jnp.ndarray:
    """Batched Bellman-Ford: (S,) sources -> (S, V) distance rows."""
    return jax.vmap(sssp, in_axes=(None, 0))(g, sources)


@jax.jit
def bfs_multi_steps(g: GraphArrays, sources: jnp.ndarray
                    ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """`bfs_multi` plus each lane's level-loop trip count (S,) int32 and
    the segmented reduction's passes per level () int32 — the program the
    serving engine compiles, so that it can count the relaxation steps
    each launch ran (a lane's count is its own, one level past its
    deepest vertex, and the launch ran the lanes' maximum)."""
    runs = _in_runs(g)
    rows, trips = _bfs_levels(g, runs, sources)
    return rows, trips, runs.passes


@jax.jit
def sssp_multi_steps(g: GraphArrays, sources: jnp.ndarray
                     ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """`sssp_multi` plus each lane's relaxation-round count (S,) int32 and
    the passes per round, as `bfs_multi_steps` counts levels."""
    runs = _in_runs(g)
    rows, trips = jax.vmap(_sssp_rounds, in_axes=(None, None, None, 0))(
        g, runs, _in_weights(g), sources)
    return rows, trips, runs.passes


@jax.jit
def bc_multi(g: GraphArrays, sources: jnp.ndarray) -> jnp.ndarray:
    """Batched Brandes: (S,) sources -> (S, V) per-source dependencies."""
    return jax.vmap(bc_single_source, in_axes=(None, 0))(g, sources)


@jax.jit
def bc_weighted(g: GraphArrays, sources: jnp.ndarray,
                weights: jnp.ndarray) -> jnp.ndarray:
    """BC aggregate with per-source weights (0-weight lanes = padding)."""
    deltas = bc_multi(g, sources)
    return (deltas * weights[:, None]).sum(axis=0)


def bc(g: GraphArrays, sources, chunk: int = 16) -> jnp.ndarray:
    """BC over a source sample (GAP uses sampled sources for large graphs).

    Batched over sources via `vmap` (one fused device launch per chunk)
    instead of the former per-source Python loop. Chunking caps peak
    memory at ``chunk × V`` floats — the unchunked (S, V) dependency
    matrix would not fit for large V × many sampled sources. Numerically
    this only reorders the final float32 accumulation.
    """
    srcs = jnp.atleast_1d(jnp.asarray(sources, jnp.int32))
    out = jnp.zeros((g.num_vertices,), jnp.float32)
    for i in range(0, srcs.shape[0], chunk):
        out = out + bc_multi(g, srcs[i:i + chunk]).sum(axis=0)
    return out


KERNELS = {
    "bfs": lambda g, src=0: bfs(g, jnp.int32(src)),
    "pr": lambda g: pagerank(g),
    "cc": lambda g: cc_labelprop(g),
    "ccsv": lambda g: cc_shiloach_vishkin(g),
    "sssp": lambda g, src=0: sssp(g, jnp.int32(src)),
    "bc": lambda g, sources=(0, 1, 2, 3): bc(g, sources),
}
