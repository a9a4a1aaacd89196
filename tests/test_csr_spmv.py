"""Pallas CSR-SpMV pack/kernel correctness vs the pure-jnp oracle.

This is the relaxation the fused single-device PageRank routes through
(`algos.kernels.pagerank_spmv`, served by
``SingleDeviceBackend(pallas_pr=...)``): `pack_edges` tiles the in-CSR
edge stream by destination and `csr_spmv_pallas` accumulates one
destination tile per grid row. Everything here runs in interpreter mode
so CI without TPUs executes the same kernel body.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.csr import from_edges
from repro.core.generators import powerlaw_community, rmat
from repro.kernels.csr_spmv.csr_spmv import (DST_TILE, csr_spmv_pallas,
                                             pack_edges)
from repro.kernels.csr_spmv.ref import csr_spmv_ref


def _pallas_vs_ref(t_indptr, t_indices, weights, x):
    src, dst_local, val, bpt, ntiles, n_pad = pack_edges(
        np.asarray(t_indptr), np.asarray(t_indices), weights)
    got = csr_spmv_pallas(jnp.asarray(src), jnp.asarray(dst_local),
                          jnp.asarray(val), jnp.asarray(x),
                          blocks_per_tile=bpt, num_tiles=ntiles,
                          n_pad=n_pad, interpret=True)
    w = (np.ones(len(t_indices), np.float32) if weights is None
         else np.asarray(weights, np.float32))
    want = csr_spmv_ref(jnp.asarray(t_indptr), jnp.asarray(t_indices),
                        jnp.asarray(w), jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)
    return np.asarray(got)


@pytest.mark.parametrize("gen,kw", [
    (powerlaw_community, dict(num_vertices=1500, avg_degree=6, seed=0)),
    (powerlaw_community, dict(num_vertices=700, avg_degree=20, seed=1)),
    (rmat, dict(scale=9, edge_factor=4, seed=2)),
])
def test_packed_spmv_matches_ref_ragged(gen, kw):
    """Ragged degree distributions (power-law + RMAT skew) spanning
    multiple destination tiles and blocks_per_tile > 1."""
    g = gen(**kw)
    t = g.transpose
    rng = np.random.default_rng(0)
    x = rng.standard_normal(g.num_vertices).astype(np.float32)
    w = rng.random(len(t.indices)).astype(np.float32)
    _pallas_vs_ref(t.indptr, t.indices, w, x)


def test_packed_spmv_empty_rows_and_dangling_dst():
    """Vertices with no in-edges must come out exactly zero, including
    a dangling destination tile (rows past the last edge)."""
    g = from_edges(DST_TILE + 88, [0, 1, 2], [5, 5, DST_TILE + 3])
    t = g.transpose
    x = np.arange(g.num_vertices, dtype=np.float32) + 1.0
    y = _pallas_vs_ref(t.indptr, t.indices, None, x)
    assert y[5] == x[0] + x[1]
    assert y[DST_TILE + 3] == x[2]
    mask = np.ones(g.num_vertices, bool)
    mask[[5, DST_TILE + 3]] = False
    assert np.abs(y[mask]).sum() == 0.0


def test_packed_spmv_no_edges():
    """The degenerate pack (0 edges) still emits a well-formed grid."""
    g = from_edges(17, np.array([], np.int64), np.array([], np.int64))
    t = g.transpose
    y = _pallas_vs_ref(t.indptr, t.indices, None,
                       np.ones(g.num_vertices, np.float32))
    assert np.abs(y).sum() == 0.0


def test_packed_spmv_sub_tile_graph():
    """n << DST_TILE: single-tile grid with the x slab zero-padded."""
    g = from_edges(7, [0, 1, 2, 6, 6], [3, 3, 3, 0, 0])
    t = g.transpose
    x = np.array([1, 2, 3, 4, 5, 6, 7], np.float32)
    y = _pallas_vs_ref(t.indptr, t.indices, None, x)
    assert y[3] == 6.0 and y[0] == 14.0  # parallel edges both counted


def test_packed_sentinel_edges_contribute_zero():
    """The bucketed serving path pads the CSR views with sentinel edges
    and marks them invalid; packed with val=edge_valid they must not
    perturb the result — compare a padded graph against its exact self."""
    from repro.algos.graph_arrays import to_device
    g = powerlaw_community(600, avg_degree=8.0, seed=11)
    exact = to_device(g)
    padded = to_device(g, pad_to=(1024, 8192))
    assert padded.edge_valid is not None
    rng = np.random.default_rng(3)
    x = rng.random(1024).astype(np.float32)  # junk beyond V must be masked

    def run(arrays, x_n):
        ev = arrays.edge_valid
        w = None if ev is None else np.asarray(ev, np.float32)
        src, dst_local, val, bpt, ntiles, n_pad = pack_edges(
            np.asarray(arrays.t_indptr), np.asarray(arrays.t_indices), w)
        return np.asarray(csr_spmv_pallas(
            jnp.asarray(src), jnp.asarray(dst_local), jnp.asarray(val),
            jnp.asarray(x_n), blocks_per_tile=bpt, num_tiles=ntiles,
            n_pad=n_pad, interpret=True))

    got = run(padded, x)[:g.num_vertices]
    want = run(exact, x[:g.num_vertices])
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


def test_pagerank_spmv_matches_segment_sum_kernel():
    """The fused-loop PR that routes its relaxation through the Pallas
    kernel == the segment-sum PR, on exact and bucketed arrays."""
    from repro.algos import kernels as K
    from repro.algos.graph_arrays import to_device
    g = powerlaw_community(800, avg_degree=8.0, seed=5)
    for pad_to in (None, (1024, 16384)):
        ga = to_device(g, pad_to=pad_to)
        ev = ga.edge_valid
        w = None if ev is None else np.asarray(ev, np.float32)
        src, dst_local, val, bpt, ntiles, n_pad = pack_edges(
            np.asarray(ga.t_indptr), np.asarray(ga.t_indices), w)
        got = np.asarray(K.pagerank_spmv(
            ga, jnp.asarray(src), jnp.asarray(dst_local), jnp.asarray(val),
            blocks_per_tile=bpt, num_tiles=ntiles, n_pad=n_pad,
            interpret=True))
        want = np.asarray(K.pagerank(ga))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-8)


def test_engine_pallas_pr_backend_parity():
    """`SingleDeviceBackend(pallas_pr="interpret")` serves PR through the
    packed kernel's interpreter (one launch per query, `pr@spmv` cache
    key) and matches the default XLA backend up to float tolerance.
    ``"auto"`` serves XLA, and ``True`` (the compiled kernel) refuses to
    run anywhere but on a TPU instead of falling back to the
    interpreter."""
    from repro.engine.backends import SingleDeviceBackend
    g = powerlaw_community(500, avg_degree=6.0, seed=7)
    ref = SingleDeviceBackend()
    pal = SingleDeviceBackend(pallas_pr="interpret")
    assert ref.telemetry()["pr_path"] == "xla"
    assert pal.telemetry()["pr_path"] == "pallas-interpret"
    with pytest.raises(ValueError, match="interpret"):
        SingleDeviceBackend(pallas_pr=True)        # CPU: no silent fallback
    h_ref, h_pal = ref.prepare(g), pal.prepare(g)
    assert h_ref.spmv is None and h_pal.spmv is not None
    out_ref = np.asarray(ref.run(h_ref, "pr"))
    out_pal = np.asarray(pal.run(h_pal, "pr"))
    np.testing.assert_allclose(out_pal, out_ref, rtol=1e-5, atol=1e-8)
    assert any(k[0] == "pr@spmv" for k in pal._cache)
    assert pal.telemetry()["dispatches"] == 1
