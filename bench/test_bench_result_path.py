"""The readers of the result-path spans and the step counters, on a
synthetic window: two launches of a counted BFS program."""
from __future__ import annotations

from types import SimpleNamespace

import pytest

from bench import harness
from bench.conftest import ROOT


def _read(name: str, ctx):
    return harness.load_module(ROOT / f"bench/metrics/{name}.py").read(ctx)


def _span(name: str, dur_us: float) -> dict:
    return {"name": name, "ph": "X", "ts": 0.0, "dur": dur_us, "args": {}}


def _window(**over) -> SimpleNamespace:
    # per launch: launch ⊃ device_sync, d2h → unpermute → cache_fill →
    # slice_out; the launches ran 7 and 5 loop steps over 4 lanes each,
    # whose real lanes needed 7 + 7 + 6 and 5 + 5 steps
    spans = []
    for d2h, unperm, fill, out in ((30e3, 600e3, 400e3, 100e3),
                                   (10e3, 200e3, 200e3, 50e3)):
        spans += [_span("translate", 5e3), _span("device_sync", 8e6),
                  _span("d2h", d2h), _span("launch", 8e6 + d2h),
                  _span("unpermute", unperm), _span("cache_fill", fill),
                  _span("slice_out", out)]
    ctx = SimpleNamespace(
        spans=spans, bench_dir=ROOT / "bench",
        classes={"bfs": SimpleNamespace(program="bfs_multi")},
        trace={"programs": {"jit_bfs_multi_steps": {"seconds": 16.8,
                                                    "count": 2}}},
        counters={"engine_launches_total": 2,
                  "engine_kernel_steps_total": 12,
                  "engine_lane_steps_total": 30,
                  "engine_lane_slots_total": 48})
    for key, value in over.items():
        setattr(ctx, key, value)
    return ctx


def test_result_path_spans_per_launch():
    ctx = _window()
    assert _read("d2h_ms", ctx) == pytest.approx(20.0)
    assert _read("unpermute_ms", ctx) == pytest.approx(400.0)
    # (400 + 100 + 200 + 50) ms over two launches
    assert _read("result_copy_ms", ctx) == pytest.approx(375.0)


def test_result_copy_without_the_cache_is_the_slice_alone():
    ctx = _window()
    ctx.spans = [s for s in ctx.spans if s["name"] != "cache_fill"]
    assert _read("result_copy_ms", ctx) == pytest.approx(75.0)


def test_step_counters_per_launch():
    ctx = _window()
    assert _read("steps_per_launch", ctx) == pytest.approx(6.0)
    # 8,400 ms of device time per execution over 6 steps per launch
    assert _read("device_ms_per_step", ctx) == pytest.approx(1400.0)
    assert _read("lane_occupancy_pct", ctx) == pytest.approx(62.5)


@pytest.mark.parametrize("name", ["d2h_ms", "unpermute_ms", "result_copy_ms",
                                  "steps_per_launch", "device_ms_per_step",
                                  "lane_occupancy_pct"])
def test_a_program_without_the_spans_and_counters_reads_nothing(name):
    # what a window of the program before the result path was spanned
    # holds: launches and slices, no d2h, unpermute or cache_fill, and no
    # step counters
    ctx = _window(counters={"engine_launches_total": 2})
    ctx.spans = [s for s in ctx.spans
                 if s["name"] not in ("d2h", "unpermute", "cache_fill")]
    assert _read(name, ctx) is None


def test_device_ms_per_step_needs_the_trace():
    assert _read("device_ms_per_step", _window(trace=None)) is None
