"""The knee of an open-loop mix: the highest arrival rate it sustains.

    python bench/knee.py --workload <mix cell> --seed <n> --rates r1 r2 ...
        [--root <benchmark root>]

For each rate, lowest first, it runs the cell once through the harness,
untraced, with only the traffic's ``rate_per_s`` changed, in a temporary
copy of the benchmark (the checkout's traffic file is never written),
and prints one JSON line: the rate, the arrivals offered, the answers,
the failed requests, ``answers_per_s``, ``latency_p50_ms``,
``latency_p95_ms``, the window's seconds and ``correct``. A rate is
sustained where every arrival was answered, none failed, and its p95
latency is at most twice that at the lowest rate swept; the sweep stops
after the first rate that is not. The last line gives the knee, the
highest rate sustained, and the ``rate_per_s`` a mix cell takes from it:
4/5 of the knee, rounded down to 0.05 req/s.

Every rate sees the same graph and roots (``--seed``) and one arrival
schedule (the traffic's ``schedule_seed``) at its own density, over a
window of the benchmark's ``run_seconds``. ``--root`` sweeps a mix that
is not yet a cell: a copy of the benchmark with the cell and its traffic
file added. Like ``bench/run.py`` it refuses to run without the chips
the cell asks for; the benchmark's own runs do not run it.
"""
from __future__ import annotations

import argparse
import json
import math
import pathlib
import shutil
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
# a cell's rate, as a share of the knee, and the step it is rounded down to
CELL_SHARE = 0.8
RATE_STEP = 0.05
# how far a rate's p95 latency may rise over the lowest rate's and still
# be sustained
P95_RISE = 2.0


def sustained(line: dict, lowest: dict) -> bool:
    """Whether the rate of ``line`` is sustained against the lowest rate
    swept: no request failed (an arrival left unanswered counts as
    failed, so every one was answered) and p95 within the rise."""
    return (line["failed"] == 0 and line["latency_p95_ms"] is not None
            and lowest["latency_p95_ms"] is not None
            and line["latency_p95_ms"] <= P95_RISE * lowest["latency_p95_ms"])


def knee_of(lines: list[dict]) -> dict:
    """The knee of a sweep's lines, lowest rate first, and the rate a
    mix cell takes from it (None where no rate was sustained)."""
    rates = [ln["rate_per_s"] for ln in lines if sustained(ln, lines[0])]
    knee = max(rates, default=None)
    cell = (None if knee is None else
            round(math.floor(CELL_SHARE * knee / RATE_STEP + 1e-9)
                  * RATE_STEP, 2))
    return {"knee_per_s": knee, "rate_per_s": cell,
            "swept": [ln["rate_per_s"] for ln in lines]}


def _line(rate: float, result: dict) -> dict:
    metrics = {k: m["value"] for k, m in result["metrics"].items()}
    answers = result["attempted"] - result["failed"]
    per_s = metrics.get("answers_per_s")
    return {"rate_per_s": rate, "arrivals": result["attempted"],
            "answers": answers, "failed": result["failed"],
            "answers_per_s": per_s,
            "latency_p50_ms": metrics.get("latency_p50_ms"),
            "latency_p95_ms": metrics.get("latency_p95_ms"),
            # the window as answers_per_s takes it: answers over seconds
            "window_s": answers / per_s if per_s else None,
            "correct": result["correct"]}


def _print(line: str) -> None:
    print(line, flush=True)


def sweep(root: pathlib.Path, workload: str, seed: int, rates: list[float],
          seconds: float | None = None, emit=_print) -> dict:
    """Run the mix cell ``workload`` of the benchmark under ``root`` once
    per rate, lowest first, for ``seconds`` (``run_seconds`` if None),
    in a temporary copy whose traffic file differs only in
    ``rate_per_s``; ``emit`` each rate's line as it comes, stop after
    the first rate not sustained, and return the lines with the knee."""
    from bench import harness
    root = pathlib.Path(root)
    spec = harness.resolve(root, workload)
    if spec.arrivals is None:
        raise harness.CellError(f"{workload!r} is a closed loop; only an "
                                "open loop has an arrival rate")
    rates = sorted(set(float(r) for r in rates))
    if not rates or rates[0] <= 0:
        raise harness.CellError(f"rates must be above 0, not {rates}")
    if seconds is None:
        seconds = harness.read_json(root / "BENCHMARK.json")["run_seconds"]
    lines: list[dict] = []
    with tempfile.TemporaryDirectory(prefix="bench_knee_") as tmp:
        copy = pathlib.Path(tmp)
        shutil.copy(root / "BENCHMARK.json", copy / "BENCHMARK.json")
        shutil.copytree(root / "bench", copy / "bench",
                        ignore=shutil.ignore_patterns("__pycache__",
                                                      "test_*", "testdata"))
        traffic_file = copy / "bench/traffic" / f"{spec.cell['traffic']}.json"
        for rate in rates:
            traffic = harness.read_json(traffic_file)
            traffic["arrivals"]["rate_per_s"] = rate
            traffic_file.write_text(json.dumps(traffic, indent=2))
            # untraced: no reader of the end-to-end metrics takes the peaks
            result = harness.run_cell(harness.resolve(copy, workload), seed,
                                      seconds, False, time.perf_counter())
            lines.append(_line(rate, result))
            emit(json.dumps(lines[-1]))
            if not sustained(lines[-1], lines[0]):
                break
    knee = knee_of(lines)
    emit(json.dumps(knee))
    return {"lines": lines, **knee}


def main(argv=None) -> int:
    from bench import harness
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--root", type=pathlib.Path, default=ROOT)
    args = ap.parse_args(argv)
    try:
        spec = harness.resolve(args.root, args.workload)
        import repro  # noqa: F401  the system under test
        harness.chip_peaks(spec)
    except (harness.CellError, ImportError, KeyError, OSError) as exc:
        harness.log(f"knee: cannot sweep {args.workload!r}: {exc}")
        return 2
    harness.use_compile_cache()
    try:
        sweep(args.root, args.workload, args.seed, args.rates)
    except harness.CellError as exc:
        harness.log(f"knee: {exc}")
        return 2
    return 0


if __name__ == "__main__":
    # the checkout root (for `bench`) and its `src` (the system under
    # test), in place of this file's own directory
    sys.path[0:1] = [str(ROOT), str(ROOT / "src")]
    sys.exit(main())
