"""The knee sweep of an open-loop mix: the rule that finds the knee and
the rate a cell takes from it, and a whole sweep on the CPU at a tiny
size, which must leave the benchmark's files as they were."""
from __future__ import annotations

import json

import pytest

from bench import harness, knee
from bench.conftest import TINY_MIX, add_mix


def _line(rate, p95, failed=0):
    return {"rate_per_s": rate, "arrivals": 20, "answers": 20 - failed,
            "failed": failed, "latency_p95_ms": p95}


@pytest.mark.parametrize("lines,knee_per_s,rate_per_s", [
    # p95 rises past twice the lowest rate's at 4 req/s
    ([_line(0.5, 8000.0), _line(1, 8500.0), _line(2, 9000.0),
      _line(4, 17000.0)], 2.0, 1.6),
    # 4/5 of the knee, rounded down to 0.05 req/s
    ([_line(1, 100.0), _line(3, 150.0)], 3.0, 2.4),
    ([_line(1, 100.0), _line(1.3, 150.0)], 1.3, 1.0),
    ([_line(0.5, 100.0), _line(1, 200.0)], 1.0, 0.8),
    # a request that failed is not sustained, whatever its latency
    ([_line(1, 100.0), _line(2, 120.0, failed=1)], 1.0, 0.8),
    # nothing sustained: no knee
    ([_line(1, 100.0, failed=2)], None, None),
])
def test_the_knee_and_the_cell_rate(lines, knee_per_s, rate_per_s):
    got = knee.knee_of(lines)
    assert (got["knee_per_s"], got["rate_per_s"]) == (knee_per_s, rate_per_s)
    assert got["swept"] == [ln["rate_per_s"] for ln in lines]


def _files(root) -> dict:
    return {str(p.relative_to(root)): p.read_bytes()
            for p in root.rglob("*") if p.is_file()
            and "__pycache__" not in p.parts}


def test_a_sweep_on_the_cpu_prints_a_line_per_rate_and_writes_nothing(
        tiny_root):
    mix = json.loads(json.dumps(TINY_MIX))
    del mix["arrivals"]["burst_every_s"], mix["arrivals"]["burst_size"]
    cell = add_mix(tiny_root, mix)
    before = _files(tiny_root)
    printed: list[str] = []
    out = knee.sweep(tiny_root, cell, 3000000007, [20.0, 10.0], seconds=0.5,
                     emit=printed.append)
    assert _files(tiny_root) == before
    lines = [json.loads(p) for p in printed]
    assert lines[:-1] == out["lines"] and 1 <= len(out["lines"]) <= 2
    assert lines[-1] == {k: out[k] for k in ("knee_per_s", "rate_per_s",
                                             "swept")}
    shares = [c["share"] for c in mix["classes"]]
    for line, rate in zip(out["lines"], (10.0, 20.0)):
        arrivals = harness.arrival_schedule(
            {**mix["arrivals"], "rate_per_s": rate}, shares, 0.5)
        assert line["rate_per_s"] == rate
        assert line["arrivals"] == line["answers"] == len(arrivals) > 0
        assert line["failed"] == 0 and line["correct"] is True
        assert 0 < line["latency_p50_ms"] <= line["latency_p95_ms"]
        assert line["window_s"] == pytest.approx(
            line["answers"] / line["answers_per_s"])
    assert out["knee_per_s"] in (10.0, 20.0)


def test_a_sweep_of_a_closed_loop_is_refused(tiny_root):
    with pytest.raises(harness.CellError, match="closed loop"):
        knee.sweep(tiny_root, "kron20.bfs.burst32", 1, [1.0], seconds=0.5)


def test_the_tool_refuses_a_cpu(tiny_root, capsys):
    cell = add_mix(tiny_root)
    assert knee.main(["--workload", cell, "--seed", "1", "--rates", "1",
                      "--root", str(tiny_root)]) == 2
    assert "needs 1 TPU chip" in capsys.readouterr().err
