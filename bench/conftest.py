"""Fixtures for the benchmark's own tests: a copy of the benchmark whose
configurations are cut to sizes a CPU test run can hold."""
from __future__ import annotations

import json
import pathlib
import shutil
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

# generator keys cut for the CPU: every cell keeps its kernel, burst and
# traffic file, on a graph of a few hundred vertices
TINY = {"graph500-kron20": {"scale": 8}, "pbbs-3dgrid100": {"n": 343}}
# depths that the tiny Kronecker graphs offer many roots of, and a window
# that they hold (at scale 8 most shortest-path trees are 7 to 11 arcs
# deep and most BFS trees 3 levels)
TINY_TRAFFIC = {"sssp.burst4": {"root_depth": 9},
                "bfs.burst32": {"root_depth": 3, "window_roots": 64}}


def copy_benchmark(dest: pathlib.Path, sizes: dict | None = None,
                   check_sample: int | None = None,
                   traffic: dict | None = None) -> pathlib.Path:
    """``BENCHMARK.json`` and ``bench/`` copied under ``dest``, with the
    configurations' keys in ``sizes`` and the traffic mixes' keys in
    ``traffic`` replaced and, if given, every traffic file (each class
    of a mix) checking ``check_sample`` answers."""
    shutil.copy(ROOT / "BENCHMARK.json", dest / "BENCHMARK.json")
    shutil.copytree(BENCH, dest / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "test_*",
                                                  "testdata"))
    bench = json.loads((dest / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        path = dest / c["file"]
        cfg = json.loads(path.read_text())
        cfg.update((sizes or {}).get(c["name"], {}))
        path.write_text(json.dumps(cfg))
    for path in (dest / "bench" / "traffic").glob("*.json"):
        mix = json.loads(path.read_text())
        mix.update((traffic or {}).get(path.stem, {}))
        if check_sample is not None:
            for cls in mix.get("classes", [mix]):
                cls["check_sample"] = check_sample
        path.write_text(json.dumps(mix))
    return dest


@pytest.fixture
def tiny_root(tmp_path) -> pathlib.Path:
    return copy_benchmark(tmp_path, TINY, check_sample=10**6,
                          traffic=TINY_TRAFFIC)


# a mix of two classes a tiny Kronecker graph can serve in half a second
# on the CPU: single-root BFS requests of depth 3 and 4-root SSSP
# requests, Poisson arrivals with a burst overlay
TINY_MIX = {
    "classes": [
        {"kernel": "bfs", "program": "bfs_multi", "check_sample": 4,
         "root_depth": 3, "share": 0.75},
        {"kernel": "sssp", "program": "sssp_multi", "check_sample": 2,
         "sources": 4, "share": 0.25}],
    "arrivals": {"process": "poisson", "rate_per_s": 20.0,
                 "burst_every_s": 0.2, "burst_size": 3, "schedule_seed": 7},
    "why": "a test mix"}


def add_mix(root: pathlib.Path, mix: dict | None = None,
            name: str = "kron20.mix.test") -> str:
    """A traffic file ``mix.test`` (``TINY_MIX`` by default) and a cell
    ``name`` on the Kronecker configuration that serves it, added to the
    benchmark copied under ``root``; returns the cell's name."""
    (root / "bench/traffic/mix.test.json").write_text(
        json.dumps(TINY_MIX if mix is None else mix))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": name, "config": "graph500-kron20",
                               "traffic": "mix.test", "chips": 1,
                               "why": "an open-loop mix"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return name
