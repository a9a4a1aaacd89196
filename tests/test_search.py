"""k-NN search serving: builders, kernel parity, engine round-trips.

Covers the search subsystem end to end (docs/search.md):

- graph builders emit valid fixed-out-degree CSRs and the NSW insert
  path stays navigable across clusters (the diversity heuristic);
- the served `knn` kernel matches the host beam-search oracle
  bit-for-bit on integer-valued vectors (exact float32 sums), and holds
  recall >= 0.95 against the brute-force oracle on gaussian clusters;
- results are bit-identical across {kernel-vs-host, single/bucketed,
  sharded} execution and across {identity, full visitsort, patch}
  layouts — the composite (dist_bits, canonical_id) ranking key is the
  invariant under test;
- visit telemetry: per-vertex counts accumulate exactly (pad lanes
  excluded), flow into the registry EWMA, and drive the
  ``refresh_hotness`` full/patch tiers;
- vertex growth through ``update_graph(add_vertices=, vectors=)``.
"""
from __future__ import annotations

import os

import numpy as np
import pytest

from conftest import run_forced_four_devices
from repro.core.baselines import knn_search_baseline
from repro.core.generators import clustered_vectors
from repro.engine import EngineSession
from repro.search import (SearchParams, build_knn_graph, build_nsw_graph,
                          knn_brute_force, medoid_entry, nsw_insert_deltas,
                          pad_queries, query_digest, validate_search_graph,
                          visit_hot_mask, visit_order)

K_OUT = 8
K_RET = 10


@pytest.fixture(scope="module")
def corpus():
    vecs, labels = clustered_vectors(240, dim=8, num_clusters=5, seed=1)
    return vecs


@pytest.fixture(scope="module")
def nsw_graph(corpus):
    return build_nsw_graph(corpus, k=K_OUT)


def _queries(vecs, n=16, seed=0, jitter=0.01):
    rng = np.random.default_rng(seed)
    q = vecs[rng.integers(0, len(vecs), n)]
    return (q + rng.normal(0, jitter, q.shape)).astype(np.float32)


def _recall(got, oracle):
    k = oracle.shape[1]
    return float(np.mean([len(set(map(int, g)) & set(map(int, o))) / k
                          for g, o in zip(got, oracle)]))


# ---------------------------------------------------------------- builders
def test_builders_emit_valid_fixed_degree_csr(corpus, nsw_graph):
    for g in (build_knn_graph(corpus[:50], 4), nsw_graph):
        k = validate_search_graph(g)
        assert np.all(np.asarray(g.out_degree) == k)
    with pytest.raises(ValueError):
        build_knn_graph(corpus[:5], 5)     # k must be < n
    from repro.core.csr import from_edges
    ragged = from_edges(3, np.array([0, 0, 1]), np.array([1, 2, 0]))
    with pytest.raises(ValueError):        # ragged degrees rejected
        validate_search_graph(ragged)
    dup = from_edges(2, np.array([0, 0, 1, 1]), np.array([1, 1, 0, 0]))
    with pytest.raises(ValueError):        # duplicate non-self neighbors
        validate_search_graph(dup)


def test_nsw_graph_is_navigable_across_clusters(corpus, nsw_graph):
    """Cluster-sorted corpora are the failure mode: keep-the-nearest
    reverse links would converge to the (disconnected) exact k-NN graph.
    Every corpus point must find *itself* when queried exactly."""
    entry = medoid_entry(corpus)
    hits = 0
    probe = range(0, len(corpus), 7)
    for v in probe:
        ids, _ = knn_search_baseline(nsw_graph, corpus, corpus[v], entry,
                                     beam_width=32, k_return=1)
        hits += int(ids[0] == v)
    assert hits / len(list(probe)) >= 0.95


def test_medoid_entry_and_brute_force_tie_break(corpus):
    assert 0 <= medoid_entry(corpus) < len(corpus)
    dup = np.zeros((4, 3), np.float32)       # all-equal vectors: pure ties
    ids = knn_brute_force(dup, dup[:1], 3)
    assert ids.tolist() == [[0, 1, 2]]       # broken by id, deterministic


# ------------------------------------------------------------ serving glue
def test_query_digest_and_padding():
    q = np.arange(8, dtype=np.float32)
    assert query_digest(q) == query_digest(q.copy())
    assert query_digest(q) >= 0
    assert query_digest(q) != query_digest(q + 1)
    padded, valid, real = pad_queries(np.ones((5, 4), np.float32))
    assert padded.shape == (8, 4) and real == 5
    assert valid.sum() == 5 and valid[:5].all()
    padded, valid, real = pad_queries(np.ones((5, 4), np.float32),
                                      multiple=3)
    assert len(padded) % 3 == 0 and real == 5


def test_visit_order_is_a_hot_prefix_permutation():
    visits = np.array([0.0, 5.0, 1.0, 0.0, 9.0, 0.1])
    perm = visit_order(visits)
    assert sorted(perm) == list(range(6))
    hot = visit_hot_mask(visits)
    assert set(np.nonzero(hot)[0]) == {1, 4}
    assert perm[4] == 0 and perm[1] == 1     # hottest first
    cold = np.nonzero(~hot)[0]
    assert list(perm[cold]) == sorted(perm[cold])  # stable cold tail


# ------------------------------------------------------- kernel vs oracle
def test_kernel_matches_host_oracle_bit_for_bit_integer_vectors():
    """Integer-valued coordinates make float32 distance sums exact, so
    the device kernel and the host mirror must agree on every id —
    including tie-breaks, which the canonical-id key decides."""
    rng = np.random.default_rng(4)
    vecs = rng.integers(0, 12, (150, 6)).astype(np.float32)
    g = build_nsw_graph(vecs, k=6)
    entry = medoid_entry(vecs)
    queries = rng.integers(0, 12, (12, 6)).astype(np.float32)
    with EngineSession() as s:
        gid = s.register(g, "int-knn", vectors=vecs,
                         search_params=SearchParams(k_out=6, beam_width=16,
                                                    k_return=8))
        assert s.registry.get(gid).decision.scheme == "original"
        got = s.submit(gid, "knn", queries)
    for q, row in zip(queries, got):
        want, _ = knn_search_baseline(g, vecs, q, entry, beam_width=16,
                                      k_return=8)
        assert row.tolist() == want.tolist()


def test_visit_accounting_matches_host_and_masks_pad_lanes(corpus,
                                                           nsw_graph):
    entry = medoid_entry(corpus)
    queries = _queries(corpus, n=5, seed=3)   # pads 5 -> 8 device lanes
    with EngineSession() as s:
        gid = s.register(nsw_graph, "visits", vectors=corpus)
        s.submit(gid, "knn", queries)
        e = s.registry.get(gid)
    host_total = sum(int(knn_search_baseline(nsw_graph, corpus, q,
                                             entry)[1].sum())
                     for q in queries)
    assert e.visits_total == host_total       # pad lanes contribute 0
    assert e.visit_queries == 5
    assert e.visit_ewma is not None
    assert np.isclose(e.visit_ewma.sum(), host_total / 5)


# ----------------------------------------------------- engine round trips
def test_recall_at_10_through_engine(corpus, nsw_graph):
    queries = _queries(corpus, n=24, seed=0)
    oracle = knn_brute_force(corpus, queries, K_RET)
    with EngineSession() as s:
        gid = s.register(nsw_graph, "recall", vectors=corpus)
        got = s.submit(gid, "knn", queries)
    assert got.shape == (24, K_RET)
    assert _recall(got, oracle) >= 0.95


def test_bit_identity_across_layouts_and_backends(corpus, nsw_graph):
    """The acceptance invariant: identical ids from the identity layout,
    the full visitsort reorder, the patch-tier repack, a cache hit, and
    the sharded backend."""
    queries = _queries(corpus, n=16, seed=5)
    with EngineSession() as s:
        gid = s.register(nsw_graph, "bits", vectors=corpus)
        base = s.submit(gid, "knn", queries)

        r1 = s.refresh_hotness(gid)          # original -> visitsort
        assert r1["tier"] == "full"
        assert r1["scheme"] == "visitsort"
        assert r1["hotness_source"] == "visits"
        assert np.array_equal(s.submit(gid, "knn", queries), base)

        r2 = s.refresh_hotness(gid)          # same decision -> patch tier
        assert r2["tier"] == "patch"
        assert s._c_patches.value == 1
        assert np.array_equal(s.submit(gid, "knn", queries), base)

        hits0 = s.result_cache.hits          # repeat rides the cache
        assert np.array_equal(s.submit(gid, "knn", queries), base)
        assert s.result_cache.hits == hits0 + 16
        assert s.result_cache.pinned_count == 0   # digest keys never pin

    with EngineSession(device_budget_bytes=1024) as s2:   # force sharded
        gid2 = s2.register(nsw_graph, "bits-sh", vectors=corpus)
        assert s2.registry.get(gid2).backend == "sharded"
        assert np.array_equal(s2.submit(gid2, "knn", queries), base)


def test_refresh_hotness_sizes_prefix_from_visits(corpus, nsw_graph):
    with EngineSession() as s:
        gid = s.register(nsw_graph, "prefix", vectors=corpus)
        e = s.registry.get(gid)
        assert e.probes.family == "search"
        assert e.decision.scheme == "original"   # no telemetry yet
        s.submit(gid, "knn", _queries(corpus, n=16, seed=6))
        r = s.refresh_hotness(gid)
        assert r["tier"] == "full"
        assert e.decision.reason.startswith("search family")
        expected = int(round(e.probes.visit_hub_fraction
                             * e.graph.num_vertices))
        assert e.hot_prefix_len == expected > 0
        assert e.probes.visit_gini > 0
        rec = s.policy.history[-1]
        assert rec.family == "search"
        assert s.policy.calibrator.count("visitsort", family="search") == 1


def test_update_graph_grows_search_graph(corpus, nsw_graph):
    new_vecs, _ = clustered_vectors(30, dim=8, num_clusters=5, seed=9)
    nadd, add_e, rem_e = nsw_insert_deltas(nsw_graph, corpus, new_vecs)
    assert nadd == 30
    with EngineSession(async_full_reorder=False) as s:
        gid = s.register(nsw_graph, "grow", vectors=corpus)
        base_q = _queries(corpus, n=8, seed=7)
        s.submit(gid, "knn", base_q)
        info = s.update_graph(gid, add_edges=add_e, remove_edges=rem_e,
                              add_vertices=nadd, vectors=new_vecs)
        assert info["vertices_added"] == 30
        e = s.registry.get(gid)
        assert e.graph.num_vertices == len(corpus) + 30
        assert len(e.perm) == len(e.inv_perm) == len(e.vectors) \
            == len(corpus) + 30
        assert validate_search_graph(e.graph) == K_OUT
        # grown points are served and findable
        allv = np.concatenate([corpus, new_vecs])
        q2 = (new_vecs[:6] + 0.001).astype(np.float32)
        got = s.submit(gid, "knn", q2)
        assert _recall(got, knn_brute_force(allv, q2, K_RET)) >= 0.95
        # growth mismatches are rejected up front
        with pytest.raises(ValueError):
            s.update_graph(gid, add_vertices=2)          # vectors missing
        with pytest.raises(ValueError):
            s.update_graph(gid, add_vertices=2,
                           vectors=np.zeros((1, 8), np.float32))


def test_register_and_enqueue_validation(corpus, nsw_graph, tiny_graph):
    s = EngineSession()
    with pytest.raises(ValueError):
        s.register(nsw_graph, "bad-dim", vectors=corpus[:10])
    with pytest.raises(ValueError):          # k_out mismatch
        s.register(nsw_graph, "bad-k", vectors=corpus,
                   search_params=SearchParams(k_out=4))
    with pytest.raises(ValueError):          # search_params without vectors
        s.register(tiny_graph, "no-vecs",
                   search_params=SearchParams(k_out=2))
    gid = s.register(nsw_graph, "ok", vectors=corpus)
    with pytest.raises(ValueError):          # wrong query dimensionality
        s.enqueue(gid, "knn", np.ones((2, 3), np.float32))
    with pytest.raises(ValueError):          # empty batch
        s.enqueue(gid, "knn", np.empty((0, 8), np.float32))
    plain = s.register(tiny_graph, "plain")
    with pytest.raises(ValueError):          # knn needs a search graph
        s.enqueue(plain, "knn", np.ones((1, 8), np.float32))
    s.close()


# --------------------------------------------------------------- property
def test_random_clustered_corpora_property():
    """Hypothesis sweep: for random clustered vector sets the NSW build
    validates, stays navigable (exact-match queries find themselves),
    and the host oracle's ids are plain valid vertex ids."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=5, deadline=None, derandomize=True,
              database=None)
    @given(seed=st.integers(0, 2**16), n=st.integers(40, 90),
           dim=st.sampled_from([3, 6]), clusters=st.integers(2, 5))
    def check(seed, n, dim, clusters):
        vecs, _ = clustered_vectors(n, dim=dim, num_clusters=clusters,
                                    seed=seed)
        g = build_nsw_graph(vecs, k=4)
        assert validate_search_graph(g) == 4
        entry = medoid_entry(vecs)
        hits = 0
        probe = list(range(0, n, max(n // 10, 1)))
        for v in probe:
            ids, visited = knn_search_baseline(g, vecs, vecs[v], entry,
                                               beam_width=16, k_return=1)
            assert visited.shape == (n,) and 0 <= ids[0] < n
            hits += int(ids[0] == v)
        assert hits / len(probe) >= 0.8

    check()


# -------------------------------------------------------- distributed leg
def test_search_four_forced_devices():
    """Re-run this module on 4 forced host devices so the sharded knn
    path exercises a genuine mesh (same recipe as test_scheduler.py)."""
    res = run_forced_four_devices(
        ["-m", "pytest", "-q", os.path.abspath(__file__),
         "-k", "not four_forced"], timeout=900)
    assert res.returncode == 0, \
        f"stdout={res.stdout[-4000:]}\nstderr={res.stderr[-2000:]}"
