"""The kernels' least-byte models, worked out by hand on a tiny graph."""
from __future__ import annotations

from bench import harness
from bench.conftest import ROOT


def test_bfs_least_bytes_by_hand():
    bfs = harness.load_module(ROOT / "bench/bytes/bfs.py")
    # V = 4, E = 5, one launch of 2 sources: the neighbor array (5 x 4 B)
    # and row offsets (4 x 4 B) once = 36, the 2 sources = 8, and two
    # (V,) int32 depth rows = 32
    assert bfs.least_bytes(4, 5, 1, 2) == 36 + 8 + 32
    # a second launch of 1 source reads the graph again: + 36 + 4 + 16
    assert bfs.least_bytes(4, 5, 2, 3) == 76 + 36 + 4 + 16


def test_sssp_least_bytes_by_hand():
    sssp = harness.load_module(ROOT / "bench/bytes/sssp.py")
    # as BFS, plus the int32 weights (5 x 4 B) per launch
    assert sssp.least_bytes(4, 5, 1, 2) == 36 + 20 + 8 + 32


def test_roofline_share_from_a_trace():
    from types import SimpleNamespace
    roofline = harness.load_module(ROOT / "bench/metrics/bfs_roofline.py")
    bfs = SimpleNamespace(program="bfs_multi", launches=2, sources=3,
                          bytes_model=ROOT / "bench/bytes/bfs.py")
    ctx = SimpleNamespace(
        classes={"bfs": bfs},
        trace={"programs": {"jit_bfs_multi": {"seconds": 2e-6, "count": 2}}},
        peaks={"hbm_bytes_per_s": 100e6}, num_vertices=4, num_edges=5)
    # 132 bytes at 100 MB/s take 1.32 us of the 2 us the program ran
    assert abs(roofline.read(ctx) - 66.0) < 1e-9
    ctx.classes = {"sssp": SimpleNamespace(
        program="sssp_multi", launches=2, sources=3,
        bytes_model=ROOT / "bench/bytes/sssp.py")}
    assert roofline.read(ctx) is None
