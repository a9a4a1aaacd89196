"""The reader of the push step counter, on synthetic windows: BFS
launches with some levels pushed, a mix whose SSSP steps it leaves out,
and windows of a program that counts no push steps."""
from __future__ import annotations

from types import SimpleNamespace

import pytest

from bench import harness
from bench.conftest import ROOT


def _read(ctx):
    return harness.load_module(
        ROOT / "bench/metrics/push_step_pct.py").read(ctx)


def _window(kernel_counters: dict) -> SimpleNamespace:
    return SimpleNamespace(spans=[], bench_dir=ROOT / "bench", trace=None,
                           counters={}, kernel_counters=kernel_counters)


def test_share_of_levels_that_pushed():
    # launches of 7 levels, 4 of each pushed
    ctx = _window({"bfs": {"engine_kernel_steps_total": 14,
                           "engine_push_steps_total": 8}})
    assert _read(ctx) == pytest.approx(100 * 8 / 14)


def test_every_level_pushed_and_none():
    full = {"engine_kernel_steps_total": 151, "engine_push_steps_total": 151}
    assert _read(_window({"bfs": full})) == 100.0
    none = {"engine_kernel_steps_total": 7, "engine_push_steps_total": 0}
    assert _read(_window({"bfs": none})) == 0.0


def test_a_mix_reads_the_bfs_levels_alone():
    ctx = _window({"bfs": {"engine_kernel_steps_total": 10,
                           "engine_push_steps_total": 5},
                   "sssp": {"engine_kernel_steps_total": 30}})
    assert _read(ctx) == pytest.approx(50.0)


@pytest.mark.parametrize("kernel_counters", [
    # a program before the push step: levels, no push counter
    {"bfs": {"engine_kernel_steps_total": 12}},
    # a window with no BFS launch
    {"sssp": {"engine_kernel_steps_total": 13}},
    {},
])
def test_a_window_without_the_counter_reads_nothing(kernel_counters):
    assert _read(_window(kernel_counters)) is None


def test_the_backend_counts_what_the_reader_reads():
    # a served launch through the engine: its counters, read per kernel
    # as the harness reads them, give the share of its levels that pushed
    from repro.core.csr import from_edges
    from repro.engine import EngineSession

    hub, leaves = 30, 100
    src = list(range(hub)) + [hub] * leaves
    dst = list(range(1, hub)) + [hub] + list(range(hub + 1,
                                                   hub + 1 + leaves))
    graph = from_edges(hub + 1 + leaves, src + dst, dst + src)
    session = EngineSession()
    gid = session.register(graph, "hub")
    before = harness._kernel_counters(session)
    session.submit(gid, "bfs", [0])
    counters = harness._kernel_deltas(before,
                                      harness._kernel_counters(session))
    # 30 path levels push; the hub's and the leaves' pull
    assert _read(_window(counters)) == pytest.approx(100 * 30 / 32)
