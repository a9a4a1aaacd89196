"""The BFS push step: a level whose frontier's out-arcs fit E/16 slots
scatters them to their in-CSR positions instead of gathering every
in-arc. On graphs whose shape makes one launch take both branches, the
push and pull words, the rows and the trip counts agree with a pull-only
loop and with scipy's breadth-first distances, entry for entry."""
from __future__ import annotations

import functools
import re

import numpy as np
import pytest
import scipy.sparse as sp
from scipy.sparse import csgraph

import jax
import jax.numpy as jnp
from jax import lax

from repro.algos import kernels as K
from repro.algos.graph_arrays import in_csr_positions, to_device
from repro.core.csr import from_edges
from repro.engine.backends import bucket_dims


# ---------------------------------------------------------------- graphs
def _path_into_hub(length=60, leaves=100, directed=False, copies=1):
    """A path 0 .. length-1 whose last vertex feeds a hub, which links to
    ``leaves`` leaves (each link ``copies`` times): the path's levels
    hold a few arcs and push, the hub's and the leaves' exceed E/16."""
    hub = length
    src = list(range(length - 1)) + [length - 1]
    dst = list(range(1, length)) + [hub]
    for _ in range(copies):
        src += [hub] * leaves
        dst += list(range(hub + 1, hub + 1 + leaves))
    if not directed:
        src, dst = src + dst, dst + src
    return from_edges(hub + 1 + leaves, src, dst, name="path_hub")


GRAPHS = {
    "undirected": _path_into_hub(),
    "directed": _path_into_hub(directed=True),
    "duplicate_arcs": _path_into_hub(copies=2),
}


def _upload(graph, padded: bool):
    if not padded:
        return to_device(graph)
    return to_device(graph, pad_to=bucket_dims(graph.num_vertices,
                                               graph.num_edges))


def _scipy_depths(graph, sources) -> np.ndarray:
    n = graph.num_vertices
    adj = sp.csr_matrix((np.ones(graph.num_edges), graph.indices,
                         graph.indptr), shape=(n, n))
    hops = csgraph.shortest_path(adj, unweighted=True, indices=sources)
    return np.where(np.isinf(hops), -1, hops).astype(np.int64)


@jax.jit
def _pull_bfs(g, sources):
    """The level loop with the pull branch alone."""
    runs = K._in_runs(g)
    n, s = g.num_vertices, sources.shape[0]
    start = jnp.arange(n, dtype=jnp.int32)[None, :] == sources[:, None]
    front0 = K._pack_lanes(start)

    def body(state):
        depth, front, seen, level = state
        touched = K._segment_reduce(K._pull_words(g, front), runs,
                                    jnp.bitwise_or, jnp.uint32(0))
        new = touched & ~seen
        depth = jnp.where(K._unpack_lanes(new, s), level + 1, depth)
        return depth, new, seen | new, level + 1

    depth0 = jnp.where(start, 0, -1).astype(jnp.int32)
    return lax.while_loop(lambda st: (st[1] != 0).any(), body,
                          (depth0, front0, front0, jnp.int32(0)))[0]


def _sources(graph, lanes: int) -> np.ndarray:
    rng = np.random.default_rng(lanes)
    return np.concatenate([[0], rng.integers(0, graph.num_vertices,
                                             lanes - 1)]).astype(np.int32)


# ----------------------------------------------------------------- words
@pytest.mark.parametrize("padded", [False, True])
@pytest.mark.parametrize("lanes", [1, 4, 32, 33])
@pytest.mark.parametrize("name", sorted(GRAPHS))
def test_push_words_equal_pull_words(name, lanes, padded):
    graph = GRAPHS[name]
    ga = _upload(graph, padded)
    slots = ga.num_edges // 16
    rng = np.random.default_rng(lanes)
    # random frontiers of every size whose out-arcs fit the slots
    for size in (1, 3, 10, 40):
        verts = rng.choice(graph.num_vertices, size, replace=False)
        flags = np.zeros((lanes, ga.num_vertices), bool)
        flags[rng.integers(0, lanes, size), verts] = True
        front = K._pack_lanes(jnp.asarray(flags))
        degree = jnp.where((front != 0).any(axis=0), ga.out_degree, 0)
        arcs = degree.sum()
        if int(arcs) > slots:
            continue
        pushed = K._push_words(ga, ga.out_to_in, front, degree, arcs, slots)
        np.testing.assert_array_equal(np.asarray(pushed),
                                      np.asarray(K._pull_words(ga, front)))


# -------------------------------------------------------------- launches
@pytest.mark.parametrize("padded", [False, True])
@pytest.mark.parametrize("lanes", [1, 4, 32, 33])
@pytest.mark.parametrize("name", sorted(GRAPHS))
def test_one_launch_takes_both_branches(name, lanes, padded):
    graph = GRAPHS[name]
    ga = _upload(graph, padded)
    sources = _sources(graph, lanes)
    rows, trips, _, pushes = K.bfs_multi_steps(ga, jnp.asarray(sources))
    rows = np.asarray(rows)
    np.testing.assert_array_equal(rows, np.asarray(_pull_bfs(
        ga, jnp.asarray(sources))))
    np.testing.assert_array_equal(rows[:, :graph.num_vertices],
                                  _scipy_depths(graph, sources))
    np.testing.assert_array_equal(np.asarray(trips), rows.max(axis=1) + 1)
    assert 0 < int(pushes) < int(trips.max())
    for public in (K.bfs_multi(ga, jnp.asarray(sources)),
                   K.bfs(ga, jnp.int32(sources[0]))[None]):
        np.testing.assert_array_equal(np.asarray(public),
                                      rows[:public.shape[0]])


@pytest.mark.parametrize("padded", [False, True])
@pytest.mark.parametrize("name,pushes", [
    # from the path's end: one push per path vertex's level, then the hub
    # and the leaves pull (a directed graph's leaves have no arcs: push)
    ("undirected", 60), ("duplicate_arcs", 60), ("directed", 61)])
def test_push_levels_of_a_known_launch(name, pushes, padded):
    ga = _upload(GRAPHS[name], padded)
    _, trips, _, counted = K.bfs_multi_steps(ga, jnp.asarray([0], jnp.int32))
    assert int(trips[0]) == 62 and int(counted) == pushes


def _fan(level_arcs: int, total_arcs: int):
    """Directed: root 0 -> children 1, 2, whose out-arcs (``level_arcs``
    in all) reach distinct leaves; a cycle of fresh vertices makes the
    graph ``total_arcs`` arcs."""
    a = level_arcs // 2
    leaves = list(range(3, 3 + level_arcs))
    src = [0, 0] + [1] * a + [2] * (level_arcs - a)
    dst = [1, 2] + leaves
    base = 3 + level_arcs
    ring = total_arcs - len(src)
    src += list(range(base, base + ring))
    dst += list(range(base + 1, base + ring)) + [base]
    return from_edges(base + ring, src, dst, name="fan")


@pytest.mark.parametrize("padded,total", [(False, 320), (True, 600)])
@pytest.mark.parametrize("extra", [0, 1])
def test_a_frontier_of_k_arcs_pushes_and_k_plus_one_pulls(extra, padded,
                                                          total):
    slots = (bucket_dims(1, total)[1] if padded else total) // 16
    graph = _fan(slots + extra, total)
    assert graph.num_edges == total
    ga = _upload(graph, padded)
    assert ga.num_edges // 16 == slots
    rows, trips, _, pushes = K.bfs_multi_steps(ga, jnp.asarray([0], jnp.int32))
    # the root's level and the leaves' push; the children's level holds
    # K + extra arcs
    assert int(trips[0]) == 3 and int(pushes) == 3 - extra
    np.testing.assert_array_equal(np.asarray(rows)[:, :graph.num_vertices],
                                  _scipy_depths(graph, [0]))


# -------------------------------------------------------------- out_to_in
@pytest.mark.parametrize("padded", [False, True])
@pytest.mark.parametrize("name", sorted(GRAPHS))
def test_out_to_in_is_each_out_arcs_in_csr_position(name, padded):
    graph = GRAPHS[name]
    ga = _upload(graph, padded)
    o2i = np.asarray(ga.out_to_in)
    np.testing.assert_array_equal(np.sort(o2i), np.arange(ga.num_edges))
    np.testing.assert_array_equal(np.asarray(ga.t_indices)[o2i],
                                  np.asarray(ga.src))
    np.testing.assert_array_equal(np.asarray(ga.t_dst)[o2i],
                                  np.asarray(ga.indices))
    # the sentinel tail maps to itself
    e = graph.num_edges
    np.testing.assert_array_equal(o2i[e:], np.arange(e, ga.num_edges))
    # the stable argsort's inverse of the destinations, as the derivation
    order = np.argsort(np.asarray(ga.indices), kind="stable")
    np.testing.assert_array_equal(o2i[order], np.arange(ga.num_edges))


@pytest.mark.parametrize("padded", [False, True])
def test_upload_without_out_to_in_derives_it(padded):
    graph = GRAPHS["duplicate_arcs"]
    ga = _upload(graph, padded)
    bare = ga._replace(out_to_in=None)
    np.testing.assert_array_equal(np.asarray(K._out_to_in(bare)),
                                  np.asarray(ga.out_to_in))
    np.testing.assert_array_equal(np.asarray(in_csr_positions(ga.indices)),
                                  np.asarray(ga.out_to_in))
    sources = jnp.asarray(_sources(graph, 4))
    for a, b in zip(K.bfs_multi_steps(bare, sources),
                    K.bfs_multi_steps(ga, sources)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -------------------------------------------------------------------- HLO
def _indexed_ops(hlo: str) -> list[tuple[str, str, int]]:
    """(op, name, number of indices) of every gather and scatter in an
    HLO module's text."""
    shapes, found = {}, []
    line_re = re.compile(r"^\s*(?:ROOT )?(%?[\w.\-]+) = (\w+\[[\d,]*\])"
                         r"\S* (\w+)\(([^)]*)\)")
    for line in hlo.splitlines():
        m = line_re.match(line)
        if not m:
            continue
        name, shape, op, args = m.groups()
        shapes[name] = [int(d) for d in shape.split("[")[1][:-1].split(",")
                        if d]
        if op in ("gather", "scatter"):
            index = [a.strip() for a in args.split(",")][1]
            dims = shapes[index]
            vec = int(re.search(r"index_vector_dim=(\d+)", line).group(1))
            count = int(np.prod([d for i, d in enumerate(dims) if i != vec]))
            found.append((op, name, count))
    return found


@pytest.mark.parametrize("words", [1, 2])
def test_push_branch_indexes_no_arc_axis(words):
    # V, E and K = E / 16 all differ, so every index count names its axis
    n, e = 300, 1600
    rng = np.random.default_rng(words)
    ga = to_device(from_edges(n, rng.integers(0, n, e),
                              rng.integers(0, n, e)))
    slots = e // 16
    push = jax.jit(functools.partial(K._push_words, slots=slots))
    hlo = push.lower(ga, ga.out_to_in, jnp.zeros((words, n), jnp.uint32),
                     jnp.zeros((n,), jnp.int32), jnp.int32(0)
                     ).as_text(dialect="hlo")
    ops = _indexed_ops(hlo)
    assert {op for op, _, _ in ops} == {"gather", "scatter"}
    assert all(count in (slots, n) for _, _, count in ops), ops
    # the pull branch, by contrast, gathers over every in-arc
    pull = jax.jit(K._pull_words).lower(
        ga, jnp.zeros((words, n), jnp.uint32)).as_text(dialect="hlo")
    assert [count for _, _, count in _indexed_ops(pull)] == [e]


@pytest.mark.parametrize("lanes", [1, 4])
def test_bc_runs_the_push_step_once_per_level(lanes):
    # BC's depths come from one multi-source BFS, not a BFS under `vmap`,
    # which would batch the level's `lax.cond` into a select over both
    # branches: the lowered program keeps the conditional, and its push
    # step's slot-indexed gathers and scatters appear as in BFS, unbatched
    n, e = 300, 1600
    rng = np.random.default_rng(lanes)
    ga = to_device(from_edges(n, rng.integers(0, n, e),
                              rng.integers(0, n, e)))
    slots = e // 16
    sources = jnp.arange(lanes, dtype=jnp.int32)

    def lowered(fn):
        hlo = fn.lower(ga, sources).as_text(dialect="hlo")
        counts = [count for _, _, count in _indexed_ops(hlo)]
        conds = len(re.findall(r" conditional\(", hlo))
        return conds, counts.count(slots), counts.count(lanes * slots)

    bfs_conds, bfs_slot_ops, _ = lowered(K.bfs_multi)
    assert bfs_conds >= 1 and bfs_slot_ops >= 1
    conds, slot_ops, batched = lowered(K.bc_multi)
    assert conds == bfs_conds
    assert slot_ops == bfs_slot_ops
    if lanes > 1:
        assert batched == 0
    np.testing.assert_allclose(
        np.asarray(K.bc_multi(ga, sources)),
        np.stack([np.asarray(K.bc_single_source(ga, s)) for s in sources]),
        rtol=1e-5)
