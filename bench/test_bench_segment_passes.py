"""The reader of the segmented-reduction pass counter, on a synthetic
window: two launches of a counted BFS program."""
from __future__ import annotations

from types import SimpleNamespace

import pytest

from bench import harness
from bench.conftest import ROOT


def _read(ctx):
    return harness.load_module(
        ROOT / "bench/metrics/segment_passes_per_step.py").read(ctx)


def _window(counters: dict) -> SimpleNamespace:
    return SimpleNamespace(spans=[], bench_dir=ROOT / "bench", trace=None,
                           traffic={"kernel": "bfs", "program": "bfs_multi"},
                           counters=counters)


def test_passes_per_step():
    # launches of 7 and 5 steps, 18 passes a step
    ctx = _window({"engine_launches_total": 2,
                   "engine_kernel_steps_total": 12,
                   "engine_segment_passes_total": 18 * 12})
    assert _read(ctx) == pytest.approx(18.0)


def test_a_graph_of_in_degree_one_reads_zero():
    ctx = _window({"engine_launches_total": 1,
                   "engine_kernel_steps_total": 9,
                   "engine_segment_passes_total": 0})
    assert _read(ctx) == 0.0


@pytest.mark.parametrize("counters", [
    # a program before the pull reduction: steps, no passes
    {"engine_launches_total": 2, "engine_kernel_steps_total": 12},
    # a window without the step counters at all
    {"engine_launches_total": 2},
])
def test_a_window_without_the_counter_reads_nothing(counters):
    assert _read(_window(counters)) is None
