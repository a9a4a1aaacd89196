"""The trace reduction on recorded traces with known answers."""
from __future__ import annotations

import json
import pathlib

import pytest

from bench.trace_reduce import load_xplane, reduce_trace

DATA = pathlib.Path(__file__).resolve().parent / "testdata"


def test_synthetic_trace_known_answers():
    planes = json.loads((DATA / "synthetic_trace.json").read_text())["planes"]
    r = reduce_trace(planes)
    assert r["window_s"] == pytest.approx(1000e-9)
    # device ops cover [120, 320], [700, 800] and [990, 1000]; the
    # SparseCore plane is not a device plane of its own
    assert r["busy_s"] == pytest.approx(310e-9)
    assert r["programs"] == {
        "jit_bfs_multi": {"seconds": pytest.approx(300e-9), "count": 2}}
    assert [k for k, _ in r["device_ops"]] == ["fusion.1", "scatter.2",
                                               "copy.3"]
    assert r["device_ops"][0][1] == pytest.approx(160e-9)
    # gaps [320, 700], [800, 990], [0, 120], named by the innermost host
    # event around their middles
    assert [[k, pytest.approx(v)] for k, v in r["idle_gaps"]] == [
        ["$scheduler.py:2 flush", 380e-9],
        ["$harness.py:3 serve_burst", 190e-9],
        ["$scheduler.py:2 flush", 120e-9]]


def test_trace_without_window_or_device_is_refused():
    planes = json.loads((DATA / "synthetic_trace.json").read_text())["planes"]
    with pytest.raises(ValueError):
        reduce_trace([p for p in planes if p["name"].startswith("/device")])
    with pytest.raises(ValueError):
        reduce_trace([p for p in planes if p["name"].startswith("/host")])



def test_xplane_loader_on_a_recorded_cpu_trace():
    """A profile the JAX profiler wrote on the CPU: the window annotation
    around two steps named ``bfs``. It has no device plane, so it can
    only be loaded, not reduced."""
    planes = load_xplane(DATA / "cpu_window.xplane.pb")
    host = {ev[0]: ev for p in planes if p["name"] == "/host:CPU"
            for line in p["lines"] for ev in line["events"]}
    assert host["bench.window"] == ["bench.window", 11203.0, 213281.0]
    steps = [ev for p in planes for line in p["lines"]
             for ev in line["events"] if ev[0] == "bfs"]
    assert len(steps) == 2
    with pytest.raises(ValueError, match="no device plane"):
        reduce_trace(planes)
