"""BFS and SSSP pull over the in-CSR with a segmented scan: on the graphs
where a run-boundary fault would show, they equal a push step that
scatters onto the out-CSR's destinations (the formulation they replaced)
and the host oracles, entry for entry."""
from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from repro.algos import kernels as K
from repro.algos.graph_arrays import GraphArrays, edge_weights, to_device
from repro.core.baselines import sssp_baseline
from repro.core.csr import from_edges
from repro.core.traversal import bfs_levels
from repro.engine import SingleDeviceBackend
from repro.engine.backends import bucket_dims

INF = K.INF_I32


# ------------------------------------------------------ scatter oracles
@jax.jit
def _push_bfs(g: GraphArrays, source) -> jnp.ndarray:
    n = g.num_vertices

    def body(state):
        depth, front, level = state
        active = front[g.src]
        if g.edge_valid is not None:
            active &= g.edge_valid
        touched = jax.ops.segment_max(active, g.indices, num_segments=n)
        new = touched & (depth < 0)
        return jnp.where(new, level + 1, depth), new, level + 1

    depth0 = jnp.full((n,), -1, jnp.int32).at[source].set(0)
    front0 = jnp.zeros((n,), jnp.bool_).at[source].set(True)
    return lax.while_loop(lambda s: s[1].any(), body,
                          (depth0, front0, jnp.int32(0)))[0]


@jax.jit
def _push_sssp(g: GraphArrays, source) -> jnp.ndarray:
    n = g.num_vertices

    def body(state):
        dist, _, it = state
        du = dist[g.src]
        cand = jnp.where(du == INF, INF, du + g.weights)
        if g.edge_valid is not None:
            cand = jnp.where(g.edge_valid, cand, INF)
        new = jnp.minimum(dist, jax.ops.segment_min(cand, g.indices,
                                                    num_segments=n))
        return new, (new != dist).any(), it + 1

    dist0 = jnp.full((n,), INF).at[source].set(0)
    return lax.while_loop(lambda s: s[1] & (s[2] < n), body,
                          (dist0, jnp.bool_(True), jnp.int32(0)))[0]


def _assert_exact(graph, ga, sources):
    """Pull rows equal the push rows and the host oracles on ``[:V]``."""
    n = graph.num_vertices
    w = np.asarray(ga.weights)[:graph.num_edges]
    srcs = jnp.asarray(sources, jnp.int32)
    bfs_rows = np.asarray(K.bfs_multi(ga, srcs))
    sssp_rows = np.asarray(K.sssp_multi(ga, srcs))
    for i, s in enumerate(sources):
        src = jnp.int32(s)
        want_bfs = np.asarray(_push_bfs(ga, src))
        want_sssp = np.asarray(_push_sssp(ga, src))
        np.testing.assert_array_equal(bfs_rows[i], want_bfs)
        np.testing.assert_array_equal(sssp_rows[i], want_sssp)
        np.testing.assert_array_equal(np.asarray(K.bfs(ga, src)), want_bfs)
        np.testing.assert_array_equal(np.asarray(K.sssp(ga, src)),
                                      want_sssp)
        np.testing.assert_array_equal(bfs_rows[i, :n], bfs_levels(graph, s))
        np.testing.assert_array_equal(sssp_rows[i, :n].astype(np.int64),
                                      sssp_baseline(graph, w, s))


# ---------------------------------------------------------------- graphs
def _hub(in_degree: int):
    """Leaves 1..d point at hub 0, the hub points at a tail vertex d+1:
    from leaf 1, only the first arc of the hub's run is active, so the
    hub is reached only if the scan carries it the whole run."""
    d = in_degree
    src = list(range(1, d + 1)) + [0]
    dst = [0] * d + [d + 1]
    return from_edges(d + 2, src, dst, name=f"hub{d}")


def _random_directed(seed: int, n: int = 60, e: int = 150):
    rng = np.random.default_rng(seed)
    # sources and destinations from disjoint-ish ranges leave vertices
    # with no in-arcs, and others with no out-arcs
    src = rng.integers(0, n, e)
    dst = rng.integers(n // 4, n, e)
    return from_edges(n, src, dst, name=f"rand{seed}")


def test_zero_in_degree_vertices():
    g = _random_directed(0)
    assert (g.in_degree == 0).sum() >= 10
    _assert_exact(g, to_device(g), [0, 1, 2, 40])


@pytest.mark.parametrize("k", [1, 3, 5])
@pytest.mark.parametrize("extra", [0, 1])
def test_hub_at_and_past_a_power_of_two(k, extra):
    g = _hub(2**k + extra)
    ga = to_device(g)
    passes = int(K._in_runs(ga).passes)
    assert passes == k + extra
    _assert_exact(g, ga, [1, 2**k + extra, 0])
    assert int(K.bfs(ga, jnp.int32(1))[0]) == 1


def test_self_loops_and_duplicate_arcs():
    src = [0, 0, 0, 1, 1, 2, 2, 2, 3, 4, 4, 5, 3]
    dst = [0, 1, 1, 2, 2, 2, 3, 3, 3, 4, 5, 5, 1]
    g = from_edges(6, src, dst, name="loops")
    assert g.num_edges == len(src)
    _assert_exact(g, to_device(g), [0, 3, 4, 5])


@pytest.mark.parametrize("edges", [([], []), ([0], [0]), ([0, 0], [0, 0])])
def test_single_vertex_graph(edges):
    g = from_edges(1, *edges, name="one")
    _assert_exact(g, to_device(g), [0])


def test_padded_upload_with_a_long_sentinel_tail():
    # in-degrees up to 9, a sentinel tail of ~1,000 arcs on the padded
    # vertex: the passes follow the real runs, and the tail stays masked
    g = _random_directed(3, n=40, e=90)
    v_b, e_b = bucket_dims(g.num_vertices, g.num_edges)
    ga = to_device(g, pad_to=(v_b, e_b))
    tail = e_b - g.num_edges
    assert tail > 8 * int(g.in_degree.max())
    passes = int(K._in_runs(ga).passes)
    assert passes == int(np.ceil(np.log2(g.in_degree.max())))
    _assert_exact(g, ga, [0, 5, 17])


@pytest.mark.parametrize("kernel", ["bfs", "sssp"])
def test_multi_source_batch_with_pad_lanes(kernel):
    g = _random_directed(7, n=80, e=300)
    backend = SingleDeviceBackend()
    handle = backend.prepare(g)
    sources = [3, 11, 60]                    # padded to 4 lanes
    rows = np.asarray(backend.run(handle, kernel, sources))
    assert rows.shape == (3, g.num_vertices)
    w = np.asarray(to_device(g).weights)
    for row, s in zip(rows, sources):
        want = (bfs_levels(g, s) if kernel == "bfs"
                else sssp_baseline(g, w, s))
        np.testing.assert_array_equal(row.astype(np.int64), want)


@pytest.mark.parametrize("lanes", [31, 32, 33, 70])
def test_bfs_lanes_across_packed_words(lanes):
    # lane l is bit l % 32 of word l // 32: lanes on both sides of a word
    # boundary keep their own depths and trip counts
    g = _random_directed(9, n=90, e=400)
    sources = np.random.default_rng(lanes).integers(0, 90, lanes)
    rows, trips, _ = K.bfs_multi_steps(to_device(g),
                                       jnp.asarray(sources, jnp.int32))
    for row, trip, s in zip(np.asarray(rows), np.asarray(trips), sources):
        want = bfs_levels(g, int(s))
        np.testing.assert_array_equal(row, want)
        assert trip == want.max() + 1


def test_in_csr_weights_match_the_out_csr_arc():
    g = _random_directed(11, n=50, e=200)
    perm = np.random.default_rng(1).permutation(g.num_vertices)
    inv = np.empty_like(perm)
    inv[perm] = np.arange(len(perm))
    gp = g.apply_permutation(perm)
    for ga in (to_device(g), to_device(gp, canonical_ids=inv),
               to_device(g, pad_to=bucket_dims(g.num_vertices,
                                               g.num_edges))):
        e = g.num_edges
        out_w = {(int(s), int(d)): int(w) for s, d, w in zip(
            np.asarray(ga.src)[:e], np.asarray(ga.indices)[:e],
            np.asarray(ga.weights)[:e])}
        t_w = np.asarray(ga.t_weights)
        for u, v, w in zip(np.asarray(ga.t_indices)[:e],
                           np.asarray(ga.t_dst)[:e], t_w[:e]):
            assert out_w[(int(u), int(v))] == int(w)
        assert (t_w[e:] == 1).all()          # sentinels weigh 1
    # the hash is the one the out-CSR uses
    t = g.transpose
    np.testing.assert_array_equal(
        np.asarray(to_device(g).t_weights),
        edge_weights(t.indices, t.edge_src))


def test_upload_without_in_csr_weights_derives_them():
    # a GraphArrays built from the CSR fields alone (as a shape-only
    # compile does) gives the same distances
    g = _random_directed(5, n=70, e=260)
    for ga in (to_device(g), to_device(g, pad_to=bucket_dims(
            g.num_vertices, g.num_edges))):
        bare = ga._replace(t_weights=None)
        np.testing.assert_array_equal(np.asarray(K._in_weights(bare)),
                                      np.asarray(ga.t_weights))
        srcs = jnp.asarray([0, 9, 33], jnp.int32)
        np.testing.assert_array_equal(np.asarray(K.sssp_multi(bare, srcs)),
                                      np.asarray(K.sssp_multi(ga, srcs)))
