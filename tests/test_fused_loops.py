"""Differential harness for the fused on-device traversal loops.

Every sharded kernel now runs its step loop as a single ``XLA::While``
under shard_map (``fused=True``, the default) instead of a host loop
that dispatches one step at a time (``fused=False``, kept as the
reference). This module locks the fusion in three ways:

* **bit-identity** — for all six kernels, the fused drivers must produce
  exactly the bits of the host-loop reference, across hot-prefix
  fractions {None, 0.05, 0.5} and ``cold_every`` {1, 4}, and the
  engine's serving configs {exact, bucketed, sharded} must agree with
  the `core/baselines.py` oracles;
* **dispatch collapse** — the obs registry's
  ``engine_dispatches_total`` must count exactly one host->device launch
  per fused query where the host loop pays one per step (O(steps) ->
  O(1)), and the per-step exchange accounting (`ExchangeStats`) must be
  unchanged by fusion;
* **convergence bounds** — hypothesis-generated random graphs assert a
  step-count upper bound from `ExchangeStats` (diameter-based for
  BFS/CC whose step count is a hop count; V-based for weighted SSSP,
  whose hop-limited relaxation count is not bounded by the unweighted
  diameter), so convergence regressions fail loudly, not just value
  regressions.

The 4-forced-device leg re-runs this whole module in a subprocess so the
same differential holds on a genuine 4-shard mesh.
"""
from __future__ import annotations

import os

import numpy as np
import pytest

from conftest import run_forced_four_devices
from repro.algos.graph_arrays import to_device
from repro.core.baselines import (bc_baseline, bfs_baseline, cc_baseline,
                                  pagerank_baseline, sssp_baseline)
from repro.core.dist import (ExchangeStats, make_distributed_bc,
                             make_distributed_bfs, make_distributed_cc,
                             make_distributed_pagerank,
                             make_distributed_sssp, vertex_mesh)
from repro.core.generators import powerlaw_community
from repro.engine import BatchedExecutor, EngineSession

SOURCES = np.array([0, 17, 203])

# (hot_prefix_fraction, cold_every): fraction None ignores the cadence
# (every step is a full exchange), so one config covers it
EXCHANGE_CONFIGS = [(None, 1), (0.05, 1), (0.05, 4), (0.5, 1), (0.5, 4)]


@pytest.fixture(scope="module")
def fused_graph():
    return powerlaw_community(400, avg_degree=6.0, seed=11)


@pytest.fixture(scope="module")
def mesh():
    return vertex_mesh()


def _pair(factory, mesh, **kw):
    """Build (fused_runner, host_runner, fused_stats, host_stats)."""
    sf, sh = ExchangeStats(), ExchangeStats()
    fused = factory(mesh=mesh, stats=sf, fused=True, **kw)
    host = factory(mesh=mesh, stats=sh, fused=False, **kw)
    return fused, host, sf, sh


def _assert_stats_match(sf: ExchangeStats, sh: ExchangeStats):
    """Fusion must not change the exchange ledger — only the dispatch
    count: the fused While replays the same per-step full/hot sequence
    the host loop recorded, in one launch instead of one per step."""
    assert sf.snapshot()[:5] == sh.snapshot()[:5], (
        f"exchange accounting diverged: fused={sf.as_dict()} "
        f"host={sh.as_dict()}")
    assert sf.dispatches < sh.dispatches or sh.steps <= 1
    assert sh.dispatches >= sh.steps  # host pays >= one launch per step


@pytest.mark.parametrize("fraction,cold_every", EXCHANGE_CONFIGS,
                         ids=[f"f{f}-c{c}" for f, c in EXCHANGE_CONFIGS])
@pytest.mark.parametrize("kernel", ["bfs", "sssp", "cc"])
def test_fused_matches_host_loop_minrelax(fused_graph, mesh, kernel,
                                          fraction, cold_every):
    """Fused while_loop == host step loop, bit for bit, for the
    min-relaxation traversals across the full exchange-config matrix."""
    g = fused_graph
    factory = {"bfs": make_distributed_bfs, "sssp": make_distributed_sssp,
               "cc": make_distributed_cc}[kernel]
    fused, host, sf, sh = _pair(factory, mesh, g=g,
                                hot_prefix_fraction=fraction,
                                cold_every=cold_every)
    if kernel == "cc":
        got, want = np.asarray(fused()), np.asarray(host())
    else:
        got, want = np.asarray(fused(SOURCES)), np.asarray(host(SOURCES))
    np.testing.assert_array_equal(got, want)
    _assert_stats_match(sf, sh)
    # one launch per run after fusion (cc runs once, bfs/sssp once batched)
    assert sf.dispatches == 1


def test_fused_matches_host_loop_pagerank(fused_graph, mesh):
    fused_run, host_run, sf, sh = _pair(
        lambda mesh, stats, fused: make_distributed_pagerank(
            fused_graph, mesh, stats=stats, fused=fused)[0], mesh)
    np.testing.assert_array_equal(np.asarray(fused_run()),
                                  np.asarray(host_run()))
    _assert_stats_match(sf, sh)
    assert sf.dispatches == 1


def test_fused_matches_host_loop_bc(fused_graph, mesh):
    fused_run, host_run, sf, sh = _pair(
        lambda mesh, stats, fused: make_distributed_bc(
            fused_graph, mesh, stats=stats, fused=fused), mesh)
    np.testing.assert_array_equal(np.asarray(fused_run(SOURCES)),
                                  np.asarray(host_run(SOURCES)))
    _assert_stats_match(sf, sh)
    # BC is three passes compiled into one program: still one launch
    assert sf.dispatches == 1


# --------------------------------------------------- engine-level parity
def _session(config: str, fused: bool = True) -> EngineSession:
    if config == "exact":
        return EngineSession(executor=BatchedExecutor(bucketing=False,
                                                      fused=fused),
                             redecide_min_queries=10**6)
    if config == "bucketed":
        return EngineSession(executor=BatchedExecutor(fused=fused),
                             redecide_min_queries=10**6)
    return EngineSession(executor=BatchedExecutor(fused=fused),
                         device_budget_bytes=1024,
                         redecide_min_queries=10**6)


@pytest.fixture(scope="module")
def engine_outputs(fused_graph):
    """kernel -> config -> output, fused sessions across all three
    serving configs plus the host-loop sharded reference."""
    g = fused_graph
    out: dict[str, dict[str, np.ndarray]] = {}
    sessions = {}
    for config in ("exact", "bucketed", "sharded"):
        sessions[config] = _session(config)
    sessions["sharded-hostloop"] = _session("sharded", fused=False)
    for name, session in sessions.items():
        gid = session.register(g, graph_id=f"fused-{name}",
                               expected_queries=256)
        for kernel in ("bfs", "sssp", "bc", "pr", "cc", "ccsv"):
            srcs = None if kernel in ("pr", "cc", "ccsv") else SOURCES
            out.setdefault(kernel, {})[name] = np.asarray(
                session.submit(gid, kernel, srcs))
    return out, sessions


@pytest.mark.parametrize("kernel", ["bfs", "sssp", "bc", "pr", "cc", "ccsv"])
def test_engine_fused_matches_host_reference(engine_outputs, kernel):
    """The fused sharded engine path is bit-identical to the retired
    host-loop path, end-to-end through EngineSession.submit."""
    out, _ = engine_outputs
    np.testing.assert_array_equal(out[kernel]["sharded"],
                                  out[kernel]["sharded-hostloop"])


@pytest.mark.parametrize("kernel", ["bfs", "sssp", "bc", "pr", "cc", "ccsv"])
@pytest.mark.parametrize("config", ["exact", "bucketed", "sharded"])
def test_engine_fused_matches_oracles(engine_outputs, fused_graph, config,
                                      kernel):
    """All three serving configs against the numpy oracles: exact for
    the integer kernels, allclose for the float ones."""
    out, _ = engine_outputs
    g = fused_graph
    got = out[kernel][config]
    if kernel == "bfs":
        want = np.stack([bfs_baseline(g, int(s)) for s in SOURCES])
        np.testing.assert_array_equal(got, want)
    elif kernel == "sssp":
        w = np.asarray(to_device(g).weights)
        want = np.stack([sssp_baseline(g, w, int(s)) for s in SOURCES])
        np.testing.assert_array_equal(got.astype(np.int64), want)
    elif kernel == "bc":
        np.testing.assert_allclose(got.sum(axis=0),
                                   bc_baseline(g, SOURCES),
                                   rtol=1e-3, atol=1e-3)
    elif kernel == "pr":
        np.testing.assert_allclose(got, pagerank_baseline(g),
                                   rtol=1e-4, atol=1e-7)
    else:
        np.testing.assert_array_equal(got, cc_baseline(g))


def test_dispatch_counts_collapse(engine_outputs):
    """After fusion every sharded query is exactly one host->device
    launch; the host-loop reference pays one per exchange step. Counted
    by the obs registry (`engine_dispatches_total`, surfaced through
    backend telemetry)."""
    _, sessions = engine_outputs
    fused_t = sessions["sharded"].executor.sharded.telemetry()
    host_t = sessions["sharded-hostloop"].executor.sharded.telemetry()
    assert fused_t["fused"] and not host_t["fused"]
    # one compile per kernel (runner factories are cached per graph),
    # one launch per query
    assert fused_t["dispatches"] == fused_t["queries_run"]
    assert host_t["dispatches"] >= host_t["hot_prefix"]["steps"]
    assert host_t["dispatches"] > host_t["queries_run"]
    # fusion must not change how much data the exchange moves
    assert (fused_t["hot_prefix"]["steps"],
            fused_t["hot_prefix"]["bytes_exchanged"]) == \
           (host_t["hot_prefix"]["steps"],
            host_t["hot_prefix"]["bytes_exchanged"])
    # single-device launches were already 1:1 with queries
    for name in ("exact", "bucketed"):
        t = sessions[name].executor.single.telemetry()
        assert t["dispatches"] == t["queries_run"]


def test_fused_dispatch_is_per_query_not_per_runner(fused_graph, mesh):
    """Re-running an already-compiled fused runner adds exactly one
    dispatch (and replays the full per-step exchange ledger)."""
    stats = ExchangeStats()
    run = make_distributed_bfs(fused_graph, mesh, hot_prefix_fraction=0.05,
                               cold_every=4, stats=stats, fused=True)
    run(SOURCES)
    before = stats.snapshot()
    run(SOURCES)
    delta = stats.delta(before)
    assert delta.dispatches == 1
    assert delta.steps > 1  # the steps are still visible, in one launch


# ------------------------------------------------ convergence properties
def _bfs_ecc(g, src: int) -> int:
    d = bfs_baseline(g, src)
    return int(d.max(initial=0))


def _und_diameter(g) -> int:
    from repro.core.traversal import bfs_levels
    und = g.undirected
    return max(int(bfs_levels(und, v).max(initial=0))
               for v in range(und.num_vertices))


def test_fused_random_graphs_match_oracles_with_step_bound():
    """Satellite: hypothesis graphs through the fused sharded drivers vs
    the numpy oracles, with convergence asserted from `ExchangeStats`:

    * BFS steps  <= ecc(src) + cold_every + 2 (hop count + cadence slack)
    * CC  steps  <= und_diameter + cold_every + 2
    * SSSP steps <= V + cold_every + 2 (weighted relaxation counts hops
      of shortest *weighted* paths, which the unweighted diameter does
      not bound — V does)
    """
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    from test_properties import graphs

    mesh = vertex_mesh()

    @settings(max_examples=10, deadline=None)
    @given(g=graphs(max_v=40, max_e=128),
           fraction=st.sampled_from([None, 0.3]),
           cold_every=st.sampled_from([1, 4]),
           src_seed=st.integers(0, 10_000))
    def check(g, fraction, cold_every, src_seed):
        src = int(np.random.default_rng(src_seed).integers(g.num_vertices))

        stats = ExchangeStats()
        bfs = make_distributed_bfs(g, mesh, hot_prefix_fraction=fraction,
                                   cold_every=cold_every, stats=stats)
        np.testing.assert_array_equal(np.asarray(bfs([src]))[0],
                                      bfs_baseline(g, src))
        assert stats.steps <= _bfs_ecc(g, src) + cold_every + 2
        assert stats.dispatches == 1

        stats = ExchangeStats()
        cc = make_distributed_cc(g, mesh, hot_prefix_fraction=fraction,
                                 cold_every=cold_every, stats=stats)
        np.testing.assert_array_equal(np.asarray(cc()), cc_baseline(g))
        assert stats.steps <= _und_diameter(g) + cold_every + 2
        assert stats.dispatches == 1

        stats = ExchangeStats()
        sssp = make_distributed_sssp(g, mesh, hot_prefix_fraction=fraction,
                                     cold_every=cold_every, stats=stats)
        w = np.asarray(to_device(g).weights)
        np.testing.assert_array_equal(
            np.asarray(sssp([src]))[0].astype(np.int64),
            sssp_baseline(g, w, src))
        assert stats.steps <= g.num_vertices + cold_every + 2
        assert stats.dispatches == 1

    check()


# ----------------------------------------------------- 4-device sharded
def test_fused_four_forced_devices():
    """Re-run this module on a genuine 4-shard mesh: the same fused ==
    host differential, exchange ledger parity and dispatch collapse must
    hold when the collectives actually cross devices. (The hypothesis
    leg is skipped in the child — compile-bound, and shard-count
    independent by construction.)"""
    res = run_forced_four_devices(
        ["-m", "pytest", "-q", os.path.abspath(__file__),
         "-k", "not four_forced and not random_graphs"], timeout=900)
    assert res.returncode == 0, \
        f"stdout={res.stdout[-4000:]}\nstderr={res.stderr[-2000:]}"
