"""The plain references against the engine's answers at a tiny size: the
comparison accepts the served answers, rejects one corrupted entry, and
fails the control (the reference with one guarantee broken)."""
from __future__ import annotations

import numpy as np
import pytest

from bench import harness
from bench.conftest import ROOT, TINY

CELLS = {"kron20.bfs.burst32": "graph500-kron20",
         "grid100.bfs.burst4": "pbbs-3dgrid100",
         "kron20.sssp.burst4": "graph500-kron20"}


def _served(spec, seed: int, count: int = 6):
    from repro.core.csr import from_edges
    from repro.engine import EngineSession
    cfg = {**spec.config, **TINY[spec.config["name"]]}
    n, src, dst = harness.load_module(spec.generator).generate(cfg, seed)
    roots = harness.draw_roots(n, src, dst, seed)[:count]
    session = EngineSession()
    gid = session.register(from_edges(n, src, dst, dedup=bool(cfg["dedup"])),
                           "g", **cfg.get("assumed", {}).get("register", {}))
    kernel = spec.traffic["kernel"]
    futures = [session.enqueue(gid, kernel, [int(r)]) for r in roots]
    rows = [f.result()[0] for f in futures]
    return n, src, dst, list(zip(roots.tolist(), rows))


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_comparison_accepts_served_and_rejects_one_corrupt_entry(cell):
    spec = harness.resolve(ROOT, cell)
    (cls,) = spec.classes
    n, src, dst, sample = _served(spec, seed=2**33 + 17)
    assert harness.compare(cls, n, src, dst, sample)[
        "mismatched_entries"] == 0
    root, row = sample[-1]
    bad = np.array(row, copy=True)
    bad[(root + 1) % n] += 1
    assert harness.compare(cls, n, src, dst, sample[:-1] + [(root, bad)])[
        "mismatched_entries"] == 1


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_control_fails_the_comparison(cell):
    spec = harness.resolve(ROOT, cell)
    n, src, dst, sample = _served(spec, seed=5)
    roots = [r for r, _ in sample]
    (cls,) = spec.classes
    control = harness.load_module(cls.reference).control(n, src, dst, roots)
    assert harness.compare(cls, n, src, dst, list(zip(roots, control)))[
        "mismatched_entries"] > 0


def test_references_on_a_hand_checked_graph():
    # 0 -> 1 -> 2, 0 -> 2 twice (a duplicate), 3 isolated, 2 -> 2 loop
    src = np.array([0, 1, 0, 0, 2])
    dst = np.array([1, 2, 2, 2, 2])
    bfs = harness.load_module(ROOT / "bench/reference/bfs.py")
    sssp = harness.load_module(ROOT / "bench/reference/sssp.py")
    w = harness.load_module(ROOT / "bench/reference/weights.py").edge_weights
    assert bfs.solve(4, src, dst, [0]).tolist() == [[0, 1, 1, -1]]
    w01, w12, w02 = (int(w(np.array([a]), np.array([b]))[0])
                     for a, b in ((0, 1), (1, 2), (0, 2)))
    assert all(1 <= x <= 255 for x in (w01, w12, w02))
    assert sssp.solve(4, src, dst, [0]).tolist() == [
        [0, w01, min(w02, w01 + w12), 2**31 - 1]]
    assert bfs.control(4, src, dst, [0]).tolist() == [[0, -1, -1, -1]]


def test_depth_of_on_a_hand_checked_graph():
    # 0 -> 1 -> 2 -> 3 and a shortcut 0 -> 2
    src = np.array([0, 1, 2, 0])
    dst = np.array([1, 2, 3, 2])
    bfs = harness.load_module(ROOT / "bench/reference/bfs.py")
    sssp = harness.load_module(ROOT / "bench/reference/sssp.py")
    w = harness.load_module(ROOT / "bench/reference/weights.py").edge_weights
    w01, w12, w02 = (int(w(np.array([a]), np.array([b]))[0])
                     for a, b in ((0, 1), (1, 2), (0, 2)))
    assert bfs.depth_of(4, src, dst)([0, 3, 1]).tolist() == [2, 0, 2]
    # 3 lies one arc past 2, which the shortcut reaches in one arc unless
    # the way through 1 is shorter
    assert sssp.depth_of(4, src, dst)([0, 1]).tolist() == [
        2 if w02 <= w01 + w12 else 3, 2]


@pytest.mark.parametrize("cell", ["kron20.bfs.burst32", "kron20.sssp.burst4"])
def test_depth_of_agrees_with_the_deepest_answer(cell):
    spec = harness.resolve(ROOT, cell)
    cfg = {**spec.config, **TINY[spec.config["name"]]}
    n, src, dst = harness.load_module(spec.generator).generate(cfg, 11)
    roots = harness.draw_roots(n, src, dst, 11)[:70]
    reference = harness.load_module(spec.classes[0].reference)
    got = reference.depth_of(n, src, dst)(roots)
    if spec.traffic["kernel"] == "bfs":
        # more roots than one pass holds, each row's deepest level
        assert len(roots) > reference.DEPTH_BATCH
        assert got.tolist() == reference.solve(n, src, dst, roots).max(
            axis=1).tolist()
    else:
        # no shortest path needs fewer arcs than the hops to its end
        hops = harness.load_module(ROOT / "bench/reference/bfs.py").solve(
            n, src, dst, roots)
        assert (got >= hops.max(axis=1)).all() and (got < n).all()


@pytest.mark.parametrize("seed", [3000000007, 2**33 + 5])
@pytest.mark.parametrize("cell", ["kron20.bfs.burst32", "kron20.sssp.burst4"])
def test_a_mix_with_a_root_depth_gives_every_request_that_depth(tiny_root,
                                                                cell, seed):
    spec = harness.resolve(tiny_root, cell)
    n, src, dst = harness.load_module(spec.generator).generate(
        spec.config, seed)
    roots = harness.draw_roots(n, src, dst, seed)
    warm, window = harness.split_roots(spec, n, src, dst, roots)
    depth = harness.load_module(spec.classes[0].reference).depth_of(
        n, src, dst)
    assert len(window) == spec.traffic["window_roots"]
    assert set(depth(window).tolist()) == {spec.traffic["root_depth"]}
    assert len(warm) == spec.traffic["burst"]
    assert not set(warm.tolist()) & set(window.tolist())
    # the warm-up takes the roots of the smallest components
    from scipy.sparse import csr_matrix
    from scipy.sparse.csgraph import connected_components
    _, label = connected_components(
        csr_matrix((np.ones(len(src)), (src, dst)), shape=(n, n)))
    size = np.bincount(label)
    others = np.setdiff1d(roots, np.r_[warm, window])
    assert size[label[warm]].max() <= size[label[others]].min()
    again = harness.split_roots(spec, n, src, dst, roots)
    assert np.array_equal(again[0], warm) and np.array_equal(again[1], window)
    assert list(window) == [r for r in roots if r in set(window.tolist())]


def test_a_mix_without_a_root_depth_warms_up_on_its_last_burst():
    spec = harness.resolve(ROOT, "grid100.bfs.burst4")
    assert "root_depth" not in spec.traffic
    roots = np.arange(100)
    warm, window = harness.split_roots(spec, 100, roots, roots, roots)
    assert warm.tolist() == list(range(96, 100))
    assert window.tolist() == list(range(96))


def test_a_graph_short_of_roots_of_the_depth_is_refused(tiny_root):
    spec = harness.resolve(tiny_root, "kron20.sssp.burst4")
    spec.classes[0].root_depth = 10**6
    n, src, dst = harness.load_module(spec.generator).generate(spec.config, 7)
    with pytest.raises(harness.CellError, match="depth"):
        harness.split_roots(spec, n, src, dst,
                            harness.draw_roots(n, src, dst, 7))
