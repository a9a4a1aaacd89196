"""The generators under ``bench/graphs/`` against the shapes their
sources define, at tiny sizes."""
from __future__ import annotations

import numpy as np
import pytest

from bench import harness
from bench.conftest import ROOT

GRAPHS = ROOT / "bench" / "graphs"


def _pairs(src, dst) -> np.ndarray:
    return np.sort(np.stack([src, dst], 1).view("i8,i8"), axis=0)


def test_kronecker_is_undirected_with_every_arc_both_ways():
    rmat = harness.load_module(GRAPHS / "rmat.py")
    cfg = {"scale": 8, "edge_factor": 16, "a": 0.57, "b": 0.19, "c": 0.19}
    n, src, dst = rmat.generate(cfg, 2**33 + 5)
    assert (n, len(src)) == rmat.sizes(cfg) == (256, 2 * 16 * 256)
    assert np.array_equal(_pairs(src, dst), _pairs(dst, src))
    again = rmat.generate(cfg, 2**33 + 5)
    assert np.array_equal(src, again[1]) and np.array_equal(dst, again[2])


@pytest.mark.parametrize("dims,n", [(2, 49), (3, 216)])
def test_grid_is_a_torus_of_the_stated_side(dims, n):
    grid = harness.load_module(GRAPHS / "grid.py")
    bfs = harness.load_module(ROOT / "bench" / "reference" / "bfs.py")
    cfg = {"n": n, "dims": dims, "jumble": True}
    side = round(n ** (1 / dims))
    v, src, dst = grid.generate(cfg, 7)
    assert (v, len(src)) == grid.sizes(cfg) == (side**dims,
                                                2 * dims * side**dims)
    # degree 2 * dims everywhere, no duplicate arc, every arc both ways
    assert np.all(np.bincount(src, minlength=v) == 2 * dims)
    assert len(np.unique(src * v + dst)) == len(src)
    assert np.array_equal(_pairs(src, dst), _pairs(dst, src))
    # every vertex is dims * (side // 2) levels from its farthest vertex
    depths = bfs.solve(v, src, dst, [0, v // 2, v - 1])
    assert np.all(depths.max(axis=1) == dims * (side // 2))
    # the seed draws the labels, not the lattice
    _, src2, _ = grid.generate(cfg, 8)
    assert not np.array_equal(src, src2)
    assert np.array_equal(np.bincount(src2, minlength=v),
                          np.bincount(src, minlength=v))
