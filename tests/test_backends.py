"""Execution backends: bucketing mechanics, placement, hot-prefix policy.

Kernel-by-kernel result parity across backends lives in
tests/test_parity_matrix.py (six kernels x serving configs vs the numpy
baselines, incl. a 4-forced-device leg); this file covers the backend
*mechanics* — bucket geometry, compile sharing, routing guards, the
sharded runner-factory table, and how the policy derives
``hot_prefix_fraction`` and the ledger's sharded gain discount.
"""
from __future__ import annotations

import dataclasses

import numpy as np
import jax.numpy as jnp
import pytest

from repro.algos import kernels as K
from repro.algos.graph_arrays import to_device
from repro.core.generators import powerlaw_community
from repro.engine import (SHARDED_KERNELS, BatchedExecutor, EngineSession,
                          GraphHandle, GraphProbes, ReorderPolicy,
                          ShardedBackend, SingleDeviceBackend, bucket_dims,
                          estimate_device_bytes, probe_graph)
from repro.engine.backends import (_RUNNER_FACTORIES, GLOBAL, MULTI_SOURCE,
                                   launch_bytes, source_cap)


# ---------------------------------------------------------------- buckets
def test_bucket_dims_geometric_and_sentinel_room():
    v, e = bucket_dims(1000, 9000)
    assert v >= 1001 and e >= 9000          # room for sentinel self-loops
    assert bucket_dims(1000, 9000) == bucket_dims(900, 8500)  # shared bucket
    # no edge padding needed -> vertex bucket may equal V exactly
    assert bucket_dims(256, 1024) == (256, 1024)
    # floors apply to tiny graphs
    assert bucket_dims(8, 12) == (256, 1024)
    with pytest.raises(ValueError):
        bucket_dims(10, 10, growth=1.0)


@pytest.mark.parametrize("kernel", ["bfs", "sssp", "bc", "knn"])
def test_source_cap_is_the_largest_power_of_two_that_fits(kernel):
    v, e = 1 << 22, 16 << 22                 # Graph500 scale 22
    for free in (10**8, 10**9, 8 * 10**9, 15 * 10**9):
        cap = source_cap(kernel, v, e, free)
        assert cap >= 1 and cap & (cap - 1) == 0
        assert cap == 1 or launch_bytes(kernel, cap, v, e) <= free
        assert launch_bytes(kernel, 2 * cap, v, e) > free


# Temporaries the v5e compiler allocates for the multi-source kernels
# (`memory_analysis().temp_size_in_bytes`, AOT compiles against a
# described v5e, no chip), as (kernel, Graph500 scale, S, bytes). Up to
# scale 20 bfs/bc take a ~210-224 B/edge workspace whatever S is; from
# 21 they do not. The bfs/sssp rows up to scale 22 were taken while both
# scattered onto the out-CSR's destinations; the rows after the BC row at
# scale 22 are the pull programs over the in-CSR that serve them now.
# tests/test_tpu_compile.py re-checks scale 22 live.
_V5E_TEMPS = [
    ("bfs", 18, 8, 872_802_304), ("bfs", 19, 8, 1_778_933_248),
    ("bc", 19, 8, 1_729_213_440),
    ("bfs", 20, 4, 3_758_900_000), ("bfs", 20, 8, 3_758_700_000),
    ("bfs", 20, 16, 3_691_700_000), ("sssp", 20, 4, 268_700_000),
    ("sssp", 20, 8, 604_300_000), ("sssp", 20, 16, 1_073_900_000),
    ("bc", 20, 4, 3_692_500_000), ("bc", 20, 8, 3_692_300_000),
    ("bc", 20, 16, 4_699_100_000),
    ("bfs", 21, 8, 436_595_712), ("bc", 21, 8, 4_766_020_096),
    ("bfs", 22, 16, 1_476_800_000), ("sssp", 22, 16, 4_563_600_000),
    ("bc", 22, 8, 9_665_000_000),
    ("bfs", 20, 4, 2_490_000_000), ("bfs", 20, 32, 2_796_000_000),
    ("bfs", 22, 16, 2_706_000_000), ("bfs", 22, 32, 4_988_000_000),
    ("sssp", 20, 4, 610_000_000), ("sssp", 20, 32, 4_402_000_000),
    ("sssp", 22, 8, 4_719_000_000), ("sssp", 22, 16, 9_014_000_000),
]


@pytest.mark.parametrize("kernel,scale,batch,temp", _V5E_TEMPS)
def test_launch_bytes_cover_the_v5e_compiler(kernel, scale, batch, temp):
    v, e = 1 << scale, 16 << scale
    assert launch_bytes(kernel, batch, v, e) >= temp


def test_source_cap_bounds_bc_below_the_burst():
    """BC's per-source temporaries scale with E: next to a scale-22 CSR
    one 16 GB chip holds 8 sources, not a 32-source burst."""
    v, e = 1 << 22, 16 << 22
    assert source_cap("bc", v, e,
                      16 * 10**9 - estimate_device_bytes(v, e)) == 8
    with pytest.raises(ValueError):
        source_cap("pr", v, e, 10**9)        # global kernels take no batch


def test_estimate_device_bytes_monotone():
    assert estimate_device_bytes(100, 1000) < estimate_device_bytes(100, 2000)
    assert estimate_device_bytes(100, 1000) < estimate_device_bytes(200, 1000)


# ----------------------------------------------------- padded CSR parity
# (fixture-graph parity lives in the matrix; this helper backs the
# random-graph property test below)
def _parity_padded_vs_exact(g, srcs):
    bucketed = SingleDeviceBackend()
    handle = bucketed.prepare(g)
    assert handle.bucket[0] > g.num_vertices or handle.bucket == (
        g.num_vertices, g.num_edges)
    ga = to_device(g)
    for kernel in ("bfs", "sssp"):
        got = np.asarray(bucketed.run(handle, kernel, srcs))
        want = np.asarray(SingleDeviceBackend(bucketing=False).run_arrays(
            ga, kernel, srcs))
        assert got.shape == (len(srcs), g.num_vertices)
        np.testing.assert_array_equal(got, want)  # ints: bit-identical
    np.testing.assert_allclose(
        np.asarray(bucketed.run(handle, "pr")),
        np.asarray(K.pagerank(ga)), rtol=1e-5, atol=1e-9)
    for kernel in ("cc", "ccsv"):
        np.testing.assert_array_equal(
            np.asarray(bucketed.run(handle, kernel)),
            np.asarray(SingleDeviceBackend(bucketing=False).run_arrays(
                ga, kernel)))
    np.testing.assert_allclose(
        np.asarray(bucketed.run(handle, "bc", srcs)),
        np.asarray(K.bc_multi(ga, jnp.asarray(srcs, jnp.int32))),
        rtol=1e-5, atol=1e-5)


def test_bucket_padding_property_random_powerlaw():
    """Satellite: bucketed BFS/SSSP/PR == unpadded on random power-law
    graphs (hypothesis-driven when available)."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=8, deadline=None)
    @given(n=st.integers(min_value=60, max_value=900),
           avg_degree=st.floats(min_value=2.0, max_value=12.0),
           seed=st.integers(min_value=0, max_value=2**16))
    def check(n, avg_degree, seed):
        g = powerlaw_community(n, avg_degree=avg_degree, seed=seed)
        rng = np.random.default_rng(seed)
        srcs = rng.integers(0, n, size=3).astype(np.int32)
        _parity_padded_vs_exact(g, srcs)

    check()


def test_compiled_executable_cache_lru_eviction():
    """Satellite (ROADMAP): a long stream of distinct shapes must keep the
    compiled-executable cache bounded — LRU eviction with telemetry, and
    an evicted shape that returns recompiles correctly."""
    backend = SingleDeviceBackend(bucketing=False, max_cached_executables=3)
    graphs = [powerlaw_community(n, avg_degree=4.0, seed=n)
              for n in (60, 90, 120, 150, 180, 210)]
    assert len({(g.num_vertices, g.num_edges) for g in graphs}) == 6
    outs = [np.asarray(backend.run(backend.prepare(g), "bfs",
                                   np.array([0], np.int32)))
            for g in graphs]
    assert len(backend._cache) <= 3
    assert backend.cache_evictions == 3
    t = backend.telemetry()
    assert t["cache_evictions"] == 3
    assert t["max_cached_executables"] == 3
    assert len(t["cached_keys"]) <= 3
    # evicted shape returns: a counted miss, bit-identical result
    misses = backend.cache_misses
    again = np.asarray(backend.run(backend.prepare(graphs[0]), "bfs",
                                   np.array([0], np.int32)))
    assert backend.cache_misses == misses + 1
    np.testing.assert_array_equal(again, outs[0])
    # a hit refreshes recency: the just-used key survives the next insert
    backend.run(backend.prepare(graphs[0]), "bfs", np.array([0], np.int32))
    backend.run(backend.prepare(powerlaw_community(240, avg_degree=4.0,
                                                   seed=240)),
                "bfs", np.array([0], np.int32))
    assert ("bfs", graphs[0].num_vertices, graphs[0].num_edges,
            False) in backend._cache
    # unbounded by default; cap of zero is rejected
    assert SingleDeviceBackend().max_cached_executables is None
    with pytest.raises(ValueError):
        SingleDeviceBackend(max_cached_executables=0)


def test_compile_sharing_across_distinct_shapes():
    """Graphs of different (V, E) in one bucket share one compile key."""
    backend = SingleDeviceBackend()
    sizes = (300, 330, 360, 390)
    graphs = [powerlaw_community(n, avg_degree=4.0, seed=n) for n in sizes]
    assert len({(g.num_vertices, g.num_edges) for g in graphs}) == len(sizes)
    outs = []
    for g in graphs:
        h = backend.prepare(g)
        outs.append(backend.run(h, "bfs", np.array([0], np.int32)))
    exact = SingleDeviceBackend(bucketing=False)
    for g in graphs:
        exact.run(exact.prepare(g), "bfs", np.array([0], np.int32))
    assert exact.cache_misses == len(sizes)
    assert backend.cache_misses < exact.cache_misses
    assert backend.cache_misses * 2 <= exact.cache_misses


# ----------------------------------------------- executor facade + guards
def test_empty_sources_guard_before_cache_telemetry(plc_graph):
    """Satellite: an empty batch (or unknown kernel) must not touch the
    compile-cache counters — formerly it booked a miss before raising."""
    ex = BatchedExecutor()
    ga = to_device(plc_graph)
    with pytest.raises(ValueError):
        ex.run(ga, "bfs", [])
    with pytest.raises(ValueError):
        ex.run(ga, "bfs", np.empty(0, np.int32))
    with pytest.raises(ValueError):
        ex.run(ga, "nope", [0])
    assert (ex.cache_hits, ex.cache_misses) == (0, 0)
    assert ex.queries_run == 0 and ex.sources_run == 0


def test_executor_rejects_unknown_target_and_backend(plc_graph):
    ex = BatchedExecutor()
    with pytest.raises(TypeError):
        ex.run(plc_graph, "bfs", [0])  # host Graph is not a served target
    with pytest.raises(ValueError):
        ex.backend("tpu-pod")


def test_executor_prepare_routes_and_merges_telemetry(plc_graph):
    ex = BatchedExecutor()
    h = ex.prepare(plc_graph)
    assert isinstance(h, GraphHandle) and h.backend == "single"
    ex.run(h, "bfs", [0, 1])
    t = ex.telemetry()
    assert t["compile_cache_misses"] == 1
    assert t["single"]["bucketing"]["graphs_prepared"] == 1
    assert t["sharded"] is None  # lazy: never built


# -------------------------------------------------------------- placement
def test_policy_places_by_device_budget(plc_graph):
    probes = probe_graph(plc_graph)
    need = estimate_device_bytes(probes.num_vertices, probes.num_edges)
    fits = ReorderPolicy(device_budget_bytes=need * 10).decide(probes, 256)
    assert fits.backend == "single"
    over = ReorderPolicy(device_budget_bytes=need // 4).decide(probes, 256)
    assert over.backend == "sharded" and "placement" in over.reason
    default = ReorderPolicy().decide(probes, 256)
    assert default.backend == "single"


def test_sharded_runner_factory_covers_every_served_kernel(plc_graph):
    """Six-kernel parity is structural: every kernel the executor serves
    has a sharded runner factory, and the factory table *is* the
    SHARDED_KERNELS contract (the old NotImplementedError is unreachable
    and now an assertion)."""
    assert set(SHARDED_KERNELS) == set(MULTI_SOURCE) | set(GLOBAL)
    assert set(_RUNNER_FACTORIES) == set(SHARDED_KERNELS)
    for factory in _RUNNER_FACTORIES.values():
        assert callable(factory)
    # unknown kernels are rejected up front with the executor's ValueError
    backend = ShardedBackend(num_shards=1)
    handle = backend.prepare(plc_graph)
    with pytest.raises(ValueError, match="unknown kernel"):
        backend.run(handle, "nope")
    assert backend.queries_run == 0  # rejected before anything counted


def test_session_sharded_serves_all_kernels_and_discount(plc_graph):
    """Session-level sharded serving: every kernel routes (parity proper
    is the matrix's job), the ledger discount reflects the hot-prefix
    exchange, and telemetry surfaces the prefix statistics."""
    session = EngineSession(device_budget_bytes=1024,
                            redecide_min_queries=10**6)
    gid = session.register(plc_graph, graph_id="over-budget",
                           expected_queries=256)
    entry = session.registry.get(gid)
    assert entry.backend == "sharded"
    assert entry.ledger.backend == "sharded"
    # plc is hub-heavy: the policy thins the exchange, so the collective
    # dilution — and with it the ledger discount — shrinks vs full
    assert entry.hot_prefix_fraction is not None
    assert (session.sharded_gain_discount
            < entry.ledger.gain_discount < 1.0)
    srcs = np.array([5, 321], np.int64)
    for kernel in ("bfs", "sssp", "bc"):
        assert session.submit(gid, kernel, srcs).shape == (
            2, plc_graph.num_vertices)
    for kernel in ("pr", "cc", "ccsv"):
        assert session.submit(gid, kernel).shape == (
            plc_graph.num_vertices,)
    t = session.telemetry()
    assert t["graphs"][gid]["backend"] == "sharded"
    assert t["graphs"][gid]["hot_prefix_fraction"] == \
        entry.hot_prefix_fraction
    assert t["executor"]["sharded"]["queries_run"] == 6
    hp = t["executor"]["sharded"]["hot_prefix"]
    assert hp["steps_full"] > 0
    kernels_with_prefix = {r["kernel"] for r in hp["runners"]}
    # monotone kernels run thinned; pr/bc stay synchronous full-exchange
    # and ccsv aliases to the cc runner (one partition, one compile)
    assert kernels_with_prefix == {"bfs", "sssp", "cc"}
    runners = entry.handle.shard_state._runners
    assert "ccsv" not in runners and "cc" in runners
    for r in hp["runners"]:
        assert 0.0 < r["prefix_hit_rate"] <= 1.0
        assert 1 <= r["h_local"] <= r["per_shard_vertices"]


def _probes(**kw) -> GraphProbes:
    base = dict(num_vertices=100_000, num_edges=1_000_000, avg_degree=10.0,
                degree_gini=0.6, hub_fraction=0.1, hub_mass=0.7,
                diameter=12, probe_seconds=0.0)
    base.update(kw)
    return GraphProbes(**base)


def test_policy_hot_prefix_from_hub_mass():
    """hub mass >= threshold + a hub-packing scheme => thinned exchange,
    fraction = clamp(margin x hub_fraction)."""
    policy = ReorderPolicy(device_budget_bytes=1)  # everything sharded
    d = policy.decide(_probes(), 256)
    assert d.backend == "sharded"
    assert d.hot_prefix_fraction == pytest.approx(0.2)  # 2.0 x 0.1
    assert "hot-prefix" in d.reason
    # diffuse degree mass: nothing to concentrate, full exchange
    diffuse = policy.decide(_probes(hub_mass=0.3), 256)
    assert diffuse.backend == "sharded"
    assert diffuse.hot_prefix_fraction is None
    # no reorder => hubs stay scattered => no prefix to exploit
    low_vol = policy.decide(_probes(), 1)
    assert low_vol.scheme == "original"
    assert low_vol.hot_prefix_fraction is None
    # bounds clamp both ends
    wide = ReorderPolicy(device_budget_bytes=1).decide(
        _probes(hub_fraction=0.45), 256)
    assert wide.hot_prefix_fraction == pytest.approx(0.5)
    narrow = ReorderPolicy(device_budget_bytes=1).decide(
        _probes(hub_fraction=0.001), 256)
    assert narrow.hot_prefix_fraction == pytest.approx(0.05)
    # single-device placement never carries a fraction
    single = ReorderPolicy().decide(_probes(), 256)
    assert single.backend == "single"
    assert single.hot_prefix_fraction is None


# ------------------------------------------------------ benchmark driver
def test_run_py_parse_only_accepts_lists():
    from benchmarks.run import HARNESSES, parse_only
    assert parse_only(None) == list(HARNESSES)
    assert parse_only("engine") == ["engine"]
    assert parse_only("engine,reorder_time") == ["engine", "reorder_time"]
    assert parse_only(" engine , skew ") == ["engine", "skew"]
    with pytest.raises(SystemExit):
        parse_only("engine,nope")


def test_forced_cpu_phases_refuse_an_accelerator_parent(monkeypatch):
    """The 4-device phases run a child pinned to the CPU; from a parent
    serving on a TPU they would pass CPU timings off as the chip's."""
    import jax
    from benchmarks import engine as bench_engine
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(bench_engine, "run_forced_four_devices",
                        lambda *a, **k: pytest.fail("child started"))
    with pytest.raises(RuntimeError, match="CPU numbers"):
        bench_engine._run_four_devices("print('RESULT {}')")
