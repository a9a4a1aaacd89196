"""core/dist.py coverage: partition round-trips, hot-prefix exchange,
and true multi-shard parity.

The in-process suite runs on a single host device, so the genuinely
distributed checks (4 shards) run in a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` — the flag must be
set before jax initializes its backends.
"""
from __future__ import annotations

import textwrap

import numpy as np
import pytest

from conftest import run_forced_four_devices
from repro.core.dist import ExchangeStats, partition_edges, vertex_mesh


def test_vertex_mesh_is_one_auto_axis_over_the_devices():
    """JAX 0.9's make_mesh defaults to Explicit axes, under which the
    sharded knn program does not type-check; the engine's meshes are
    Auto."""
    import jax
    from jax.sharding import AxisType
    mesh = vertex_mesh()
    assert mesh.axis_names == ("data",)
    assert mesh.axis_types == (AxisType.Auto,)
    assert mesh.devices.size == jax.device_count()
    assert vertex_mesh(1).devices.size == 1


def _run_forced_four_devices(prog: str, timeout: int = 600):
    return run_forced_four_devices(["-c", prog], timeout=timeout)


@pytest.mark.parametrize("num_shards", [1, 2, 4, 7])
def test_partition_edges_round_trip(any_graph, num_shards):
    """No edge lost or invented; local dst indices reconstruct globals."""
    g = any_graph
    s_pad, d_pad, valid, per = partition_edges(g, num_shards)
    assert s_pad.shape == d_pad.shape == valid.shape
    assert valid.sum() == g.num_edges
    src_rt, dst_rt = [], []
    for i in range(num_shards):
        assert (0 <= d_pad[i][valid[i]]).all()
        assert (d_pad[i][valid[i]] < per).all()
        src_rt.append(s_pad[i][valid[i]])
        dst_rt.append(d_pad[i][valid[i]] + i * per)
    pairs_rt = np.stack([np.concatenate(src_rt).astype(np.int64),
                         np.concatenate(dst_rt).astype(np.int64)], 1)
    order = np.lexsort((pairs_rt[:, 1], pairs_rt[:, 0]))
    np.testing.assert_array_equal(pairs_rt[order], g.edge_multiset())


def test_partition_edges_empty_shards():
    """A graph whose edges all land in shard 0 still partitions cleanly."""
    from repro.core.csr import from_edges
    g = from_edges(40, [10, 11, 12], [0, 1, 2])  # dst < 10 => shard 0 of 4
    s_pad, d_pad, valid, per = partition_edges(g, 4)
    assert per == 10
    assert valid[0].sum() == 3 and valid[1:].sum() == 0


def test_partition_edges_weighted_round_trip_property():
    """Satellite: for random power-law graphs and shard counts,
    (src, dst, valid, edge_values) round-trips to the exact weighted edge
    multiset (hypothesis-driven when available)."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    from repro.algos.graph_arrays import edge_weights
    from repro.core.generators import powerlaw_community

    @settings(max_examples=12, deadline=None)
    @given(n=st.integers(min_value=60, max_value=600),
           avg_degree=st.floats(min_value=2.0, max_value=10.0),
           seed=st.integers(min_value=0, max_value=2**16),
           num_shards=st.integers(min_value=1, max_value=7))
    def check(n, avg_degree, seed, num_shards):
        g = powerlaw_community(n, avg_degree=avg_degree, seed=seed)
        w = edge_weights(g.edge_src, g.indices)
        s_pad, d_pad, valid, per, w_pad = partition_edges(
            g, num_shards, edge_values=w)
        assert s_pad.shape == d_pad.shape == valid.shape == w_pad.shape
        assert int(valid.sum()) == g.num_edges
        trips = []
        for i in range(num_shards):
            v = valid[i]
            assert (0 <= d_pad[i][v]).all() and (d_pad[i][v] < per).all()
            trips.append(np.stack([s_pad[i][v].astype(np.int64),
                                   d_pad[i][v].astype(np.int64) + i * per,
                                   w_pad[i][v].astype(np.int64)], 1))
        got = np.concatenate(trips)
        got = got[np.lexsort((got[:, 2], got[:, 1], got[:, 0]))]
        want = np.stack([g.edge_src.astype(np.int64),
                         np.asarray(g.indices, np.int64),
                         w.astype(np.int64)], 1)
        want = want[np.lexsort((want[:, 2], want[:, 1], want[:, 0]))]
        np.testing.assert_array_equal(got, want)

    check()


# ------------------------------------------------------ hot-prefix driver
def test_exchange_stats_accounting():
    st = ExchangeStats()
    assert st.bytes_per_step == 0.0 and st.savings_fraction == 0.0
    st.record_full(100)
    st.record_hot(10, 100)
    st.record_hot(10, 100)
    assert st.steps == 3 and (st.steps_full, st.steps_hot) == (1, 2)
    assert st.bytes_exchanged == 120
    assert st.bytes_full_equivalent == 300
    assert st.bytes_per_step == pytest.approx(40.0)
    assert st.savings_fraction == pytest.approx(0.6)
    d = st.as_dict()
    assert d["bytes_exchanged"] == 120 and d["savings_fraction"] == 0.6
    assert d["steps"] == 3


def test_exchange_stats_snapshot_delta():
    """Satellite: snapshot/delta attributes the shared counter to one run
    — what the scheduler stamps into per-request telemetry."""
    st = ExchangeStats()
    st.record_full(100)
    before = st.snapshot()
    st.record_full(50)
    st.record_hot(10, 50)
    run = st.delta(before)
    assert run.steps == 2 and run.bytes_exchanged == 60
    assert run.bytes_full_equivalent == 100
    assert run.savings_fraction == pytest.approx(0.4)
    # the aggregate keeps everything; the delta saw only its slice
    assert st.steps == 3 and st.bytes_exchanged == 160
    assert st.delta(st.snapshot()).steps == 0


def test_hot_prefix_exact_and_saves_bytes_four_shards():
    """4 forced devices, hub-packed layout: hot-prefix BFS/SSSP/CC are
    bit-identical to the single-device kernels while exchanging fewer
    bytes per step than the full all-gather of the same state."""
    prog = textwrap.dedent("""
        import numpy as np
        import jax, jax.numpy as jnp
        assert jax.device_count() == 4, jax.devices()
        from repro.algos import kernels as K
        from repro.algos.graph_arrays import to_device
        from repro.core.baselines import dbg_order
        from repro.core.dist import (ExchangeStats, make_distributed_bfs,
                                     make_distributed_cc,
                                     make_distributed_sssp, vertex_mesh)
        from repro.core.generators import powerlaw_community

        g0 = powerlaw_community(2000, avg_degree=8.0, seed=3)
        perm = np.asarray(dbg_order(g0))
        g = g0.apply_permutation(perm)      # hubs packed into the prefix
        inv = np.empty_like(perm); inv[perm] = np.arange(len(perm))
        mesh = vertex_mesh(4)
        ga = to_device(g, canonical_ids=inv)
        srcs = np.array([5, 321, 1500])

        hot = ExchangeStats()
        full = ExchangeStats()
        run_h = make_distributed_sssp(g, mesh, canonical_ids=inv,
                                      hot_prefix_fraction=0.15,
                                      cold_every=5, stats=hot)
        run_f = make_distributed_sssp(g, mesh, canonical_ids=inv,
                                      stats=full)
        want = np.stack([np.asarray(K.sssp(ga, jnp.int32(s)))
                         for s in srcs])
        np.testing.assert_array_equal(np.asarray(run_h(srcs)), want)
        np.testing.assert_array_equal(np.asarray(run_f(srcs)), want)
        assert hot.steps_hot > 0 and hot.steps_full > 0
        assert 0.0 < hot.savings_fraction < 1.0
        # a hot step moves h_local/per of a full step's payload
        assert hot.bytes_hot / hot.steps_hot \\
            < full.bytes_full / full.steps_full
        assert 0.0 < run_h.prefix_hit_rate <= 1.0
        assert run_h.h_local < run_h.per

        bfs_h = make_distributed_bfs(g, mesh, hot_prefix_fraction=0.15,
                                     cold_every=5)
        want = np.stack([np.asarray(K.bfs(ga, jnp.int32(s)))
                         for s in srcs])
        np.testing.assert_array_equal(np.asarray(bfs_h(srcs)), want)

        cc_h = make_distributed_cc(g, mesh, hot_prefix_fraction=0.15,
                                   cold_every=5)
        np.testing.assert_array_equal(np.asarray(cc_h()),
                                      np.asarray(K.cc_labelprop(ga)))
        print("HOT_PREFIX_OK")
    """)
    res = _run_forced_four_devices(prog)
    assert res.returncode == 0, \
        f"stdout={res.stdout}\nstderr={res.stderr}"
    assert "HOT_PREFIX_OK" in res.stdout


def test_distributed_pagerank_parity_four_shards():
    """Sharded PR on 4 forced host devices == single-device PR."""
    prog = textwrap.dedent("""
        import numpy as np
        import jax
        assert jax.device_count() == 4, jax.devices()
        from repro.algos.graph_arrays import to_device
        from repro.algos.kernels import pagerank
        from repro.core.dist import make_distributed_pagerank, vertex_mesh
        from repro.core.generators import powerlaw_community

        g = powerlaw_community(2000, avg_degree=8.0, seed=3)
        mesh = vertex_mesh(4)
        run, _ = make_distributed_pagerank(g, mesh, axis="data",
                                           num_iters=20)
        got = np.asarray(run())
        want = np.asarray(pagerank(to_device(g), num_iters=20))
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-7)
        print("PARITY_OK")
    """)
    res = _run_forced_four_devices(prog, timeout=300)
    assert res.returncode == 0, f"stdout={res.stdout}\nstderr={res.stderr}"
    assert "PARITY_OK" in res.stdout
