"""BENCHMARK.json against the benchmark contract, and the
harness's lookups by name."""
from __future__ import annotations

import json
import os
import pathlib
import re
import subprocess
import sys

import pytest

from bench import harness
from bench.conftest import (ROOT, TINY, TINY_MIX, TINY_TRAFFIC, add_mix,
                            copy_benchmark)

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]
CLOSED_CELLS = [w["name"] for w in BENCH["workloads"] if "arrivals" not in
                json.loads((ROOT / "bench/traffic" /
                            f"{w['traffic']}.json").read_text())]
# the repo's open-loop cells; where it has none yet, the test mix added
# to a copy of the benchmark (None) stands in, so that the open loop's
# check always has a case
OPEN_CELLS = [c for c in CELLS if c not in CLOSED_CELLS] or [None]
# what a class's reference gives the harness: the answers, the size of a
# request (``depth_of``, ``DEPTH_BATCH`` roots at a time) and the control
REFERENCE_NAMES = ("solve", "depth_of", "DEPTH_BATCH", "control")
KEYS = {"config": {"name", "source", "file", "reduced", "why"},
        "workload": {"name", "config", "traffic", "chips", "why"},
        "end_to_end": {"name", "unit", "better", "bound", "source"},
        "per_layer": {"name", "unit", "better", "source", "layer", "moves"}}


def _line(text: str) -> bool:
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_size():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    assert BENCH["paths"] == ["bench"]
    assert BENCH["command"][1] == "bench/run.py"
    assert all(_line(w) for w in BENCH["command"])


def test_names_units_and_one_line_fields():
    for kind, items in (("config", BENCH["configs"]),
                        ("workload", BENCH["workloads"]),
                        ("end_to_end", BENCH["end_to_end"]),
                        ("per_layer", BENCH["per_layer"])):
        names = [i["name"] for i in items]
        assert len(names) == len(set(names)), kind
        for item in items:
            assert set(item) - {"workloads"} == KEYS[kind], item["name"]
            assert NAME.match(item["name"]), item["name"]
            if "unit" in item:
                assert UNIT.match(item["unit"]), item["unit"]
                assert item["better"] in ("lower", "higher")
            for field in ("why", "layer", "source"):
                if field in item and kind in ("config", "workload",
                                              "per_layer"):
                    assert _line(item[field]), (item["name"], field)
    metric_names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(metric_names) == len(set(metric_names))


def test_cells_metrics_and_bounds():
    configs = {c["name"] for c in BENCH["configs"]}
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for w in BENCH["workloads"]:
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert NAME.match(w["traffic"])
    assert {w["config"] for w in BENCH["workloads"]} == configs
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
        if "roofline" in m["name"]:
            assert m["name"].endswith("_roofline") and m["unit"] == "%"
    layers = {m["layer"] for m in BENCH["per_layer"]}
    assert all(_line(layer) for layer in layers)


def test_run_seconds_fit_a_full_check():
    rs = BENCH["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    # 24 cells: 2 + 14 runs each, run_seconds + 60 per run, 2 x 90 s of
    # compile per cell, 1200 s spare, within 43200 s
    assert (2 + 14 * 24) * (rs + 60) + 24 * 180 + 1200 <= 43200


def test_configs_state_their_cuts():
    for c in BENCH["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert c["file"].startswith("bench/configs/")
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        for key in c["reduced"]:
            assert key in cfg and not key.endswith(("_dim", "_rank"))


def check_files_found(root: pathlib.Path, cell: str) -> None:
    """Every file the cell names is found by name under ``root``, and
    the cell reports every end-to-end metric and some per-layer one."""
    spec = harness.resolve(root, cell)
    assert spec.generator.is_file(), spec.generator
    for cls in spec.classes:
        for path in (cls.reference, cls.bytes_model):
            assert path.is_file(), path
    names = {m["name"] for m in spec.end_to_end + spec.per_layer}
    for name in names:
        assert (spec.bench_dir / "metrics" / f"{name}.py").is_file()
    assert {m["name"] for m in spec.end_to_end} == {
        m["name"] for m in harness.read_json(root / "BENCHMARK.json")[
            "end_to_end"]}
    assert spec.per_layer


def check_traffic(root: pathlib.Path, cell: str) -> None:
    """The cell's traffic as the harness drives it. A closed loop (no
    ``arrivals``) is one class, the traffic file's kernel and program,
    one source a request, share 1, with the file's burst. An open loop
    has its arrivals and no burst, one class per kernel whose shares add
    up to 1, and each class a reference that gives the harness what it
    calls, a bytes model and at least one checked answer."""
    spec = harness.resolve(root, cell)
    if "arrivals" not in spec.traffic:
        (cls,) = spec.classes
        assert (cls.kernel, cls.program, cls.sources, cls.share) == (
            spec.traffic["kernel"], spec.traffic["program"], 1, 1.0)
        assert spec.burst == spec.traffic["burst"] and spec.arrivals is None
        return
    assert spec.arrivals == spec.traffic["arrivals"] and spec.burst is None
    kernels = [c.kernel for c in spec.classes]
    assert kernels and len(set(kernels)) == len(kernels), kernels
    assert sum(c.share for c in spec.classes) == pytest.approx(1.0)
    for cls in spec.classes:
        assert cls.bytes_model.is_file(), cls.bytes_model
        assert cls.check_sample >= 1, cls.kernel
        reference = harness.load_module(cls.reference)
        missing = [n for n in REFERENCE_NAMES if not hasattr(reference, n)]
        assert not missing, (cls.reference, missing)


# every check of one cell, each a function of (benchmark root, cell)
CELL_CHECKS = (check_files_found, check_traffic)


@pytest.mark.parametrize("cell", CELLS)
def test_every_file_of_a_cell_is_found_by_name(cell):
    check_files_found(ROOT, cell)


def test_a_mix_cell_added_with_files_alone_passes_every_cell_check(
        tmp_path):
    root = copy_benchmark(tmp_path)
    cell = add_mix(root, name="kron20.mix.open")
    for check in CELL_CHECKS:
        check(root, cell)


def test_a_new_traffic_file_and_cell_need_no_edit(tmp_path):
    root = copy_benchmark(tmp_path)
    mix = json.loads((root / "bench/traffic/bfs.burst4.json").read_text())
    mix["burst"] = 8
    (root / "bench/traffic/bfs.burst8.json").write_text(json.dumps(mix))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "grid100.bfs.burst8",
                               "config": "pbbs-3dgrid100",
                               "traffic": "bfs.burst8", "chips": 1,
                               "why": "a wider burst on the same grid"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    spec = harness.resolve(root, "grid100.bfs.burst8")
    assert spec.traffic["burst"] == 8
    assert spec.config["name"] == "pbbs-3dgrid100"
    with pytest.raises(harness.CellError):
        harness.resolve(root, "grid100.bfs.burst16")


def test_a_new_configuration_and_metric_need_no_edit(tmp_path):
    root = copy_benchmark(tmp_path)
    cfg = json.loads((root / "bench/configs/pbbs-3dgrid100.json").read_text())
    cfg.update(name="pbbs-2dgrid", dims=2)
    (root / "bench/configs/pbbs-2dgrid.json").write_text(json.dumps(cfg))
    (root / "bench/metrics/bursts_per_s.py").write_text(
        "def read(ctx):\n    return ctx.answered / 4 / ctx.window_s\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({**bench["configs"][-1], "name": "pbbs-2dgrid",
                             "file": "bench/configs/pbbs-2dgrid.json"})
    bench["workloads"].append({"name": "grid2d.bfs.burst4",
                               "config": "pbbs-2dgrid",
                               "traffic": "bfs.burst4", "chips": 1,
                               "why": "the same burst on a 2-D torus"})
    bench["per_layer"].append({"name": "bursts_per_s", "unit": "1/s",
                               "better": "higher", "source": "host_clock",
                               "layer": "request plane", "moves":
                               "answers_per_s",
                               "workloads": ["grid2d.bfs.burst4"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    spec = harness.resolve(root, "grid2d.bfs.burst4")
    assert spec.config["dims"] == 2
    v, e = harness.load_module(spec.generator).sizes(spec.config)
    assert (v, e) == (1000 * 1000, 4 * 1000 * 1000)
    extra = [m for m in spec.per_layer if m["name"] == "bursts_per_s"]
    ctx = type("Ctx", (), {"answered": 80, "window_s": 2.0})()
    assert harness.read_metrics(extra, ctx, spec.bench_dir) == {
        "bursts_per_s": {"value": 10.0, "unit": "1/s"}}
    assert "bursts_per_s" not in {
        m["name"] for m in harness.resolve(root, CELLS[0]).per_layer}


def test_a_traffic_key_the_harness_does_not_drive_is_refused(tmp_path):
    root = copy_benchmark(tmp_path)
    path = root / "bench/traffic/bfs.burst4.json"
    mix = json.loads(path.read_text())
    path.write_text(json.dumps({**mix, "loop": "open", "clients": 4}))
    with pytest.raises(harness.CellError, match="clients"):
        harness.resolve(root, "grid100.bfs.burst4")


def test_a_mix_file_resolves(tmp_path):
    root = copy_benchmark(tmp_path)
    spec = harness.resolve(root, add_mix(root))
    assert [(c.kernel, c.program, c.sources, c.share, c.root_depth)
            for c in spec.classes] == [("bfs", "bfs_multi", 1, 0.75, 3),
                                       ("sssp", "sssp_multi", 4, 0.25, None)]
    for c in spec.classes:
        assert c.reference.is_file() and c.bytes_model.is_file()
    # a mix is an open loop: it has arrivals and no burst
    assert spec.arrivals == TINY_MIX["arrivals"] and spec.burst is None


def _mix(**changes) -> dict:
    mix = json.loads(json.dumps(TINY_MIX))
    for where, value in changes.items():
        if where == "top":
            mix.update(value)
        elif where == "arrivals":
            mix["arrivals"].update(value)
        else:
            mix["classes"][int(where[-1])].update(value)
    return mix


@pytest.mark.parametrize("mix,match", [
    (_mix(top={"clients": 4}), "clients"),
    (_mix(class1={"priority": 2}), "priority"),
    (_mix(arrivals={"jitter_s": 0.1}), "jitter_s"),
    (_mix(class0={"burst": 4}), "burst"),
])
def test_an_unknown_key_of_a_mix_or_its_classes_is_refused(tmp_path, mix,
                                                           match):
    root = copy_benchmark(tmp_path)
    with pytest.raises(harness.CellError, match=match):
        harness.resolve(root, add_mix(root, mix))


def _one_kernel_with_arrivals() -> dict:
    mix = json.loads((ROOT / "bench/traffic/bfs.burst4.json").read_text())
    return {**mix, "arrivals": dict(TINY_MIX["arrivals"])}


@pytest.mark.parametrize("mix,match", [
    ({k: v for k, v in TINY_MIX.items() if k != "arrivals"}, "arrivals"),
    (_mix(class1={"share": 0.5}), "shares"),
    (_mix(class1={"kernel": "bfs", "program": "bfs_multi"}), "one class"),
    (_mix(arrivals={"process": "uniform"}), "poisson"),
    (_mix(arrivals={"schedule_seed": None}), "schedule_seed"),
    # an open loop of one kernel is a mix of one class
    (_one_kernel_with_arrivals(), "arrivals"),
])
def test_a_mix_the_harness_cannot_drive_is_refused(tmp_path, mix, match):
    root = copy_benchmark(tmp_path)
    with pytest.raises(harness.CellError, match=match):
        harness.resolve(root, add_mix(root, mix))


@pytest.mark.parametrize("cell", CLOSED_CELLS)
def test_a_closed_loop_is_one_class_with_its_burst(cell):
    check_traffic(ROOT, cell)


@pytest.mark.parametrize("cell", OPEN_CELLS)
def test_an_open_loop_is_one_class_per_kernel_with_its_arrivals(tmp_path,
                                                                cell):
    root = ROOT
    if cell is None:
        root = copy_benchmark(tmp_path)
        cell = add_mix(root)
    check_traffic(root, cell)


@pytest.mark.parametrize("fault,match", [("no_reference", "bc.py"),
                                         ("no_control", "control")])
def test_an_open_loop_the_benchmark_cannot_check_fails_its_cell_check(
        tmp_path, fault, match):
    root = copy_benchmark(tmp_path)
    mix = json.loads(json.dumps(TINY_MIX))
    if fault == "no_reference":
        # a class whose kernel has no reference to check its answers by
        mix["classes"][1].update(kernel="bc", program="bc_multi")
    else:
        # a reference without the control that bench/control.py runs
        path = root / "bench/reference/sssp.py"
        path.write_text(path.read_text().replace("def control(",
                                                 "def _control("))
    cell = add_mix(root, mix)
    with pytest.raises(AssertionError, match=match):
        for check in CELL_CHECKS:
            check(root, cell)


# (warm-up roots, window roots) of each existing traffic file at the
# tiny sizes of the tests, as the harness drew them before it drove
# mixes: their count and a digest of their int64 bytes
ROOTS_BEFORE = {
    ("kron20.bfs.burst32", 3000000007): (32, "771d4bbe0830427a",
                                         64, "409d4b77eb9f0e45"),
    ("kron20.bfs.burst32", 2**33 + 5): (32, "2ed70ecb8a5c540f",
                                        64, "f38b75f32b406a9f"),
    ("grid100.bfs.burst4", 3000000007): (4, "8b5c0da00bbc9cbf",
                                         339, "28dc208f21cb9a33"),
    ("grid100.bfs.burst4", 2**33 + 5): (4, "5d80cf9e9980430b",
                                        339, "546c1eb07f52f578"),
    ("kron20.sssp.burst4", 3000000007): (4, "bf6f4468177cf922",
                                         8, "1016dc4183d2df8b"),
    ("kron20.sssp.burst4", 2**33 + 5): (4, "ef07601751ae7799",
                                        8, "211a5b3368ddc1df"),
}


@pytest.mark.parametrize("cell,seed", sorted(ROOTS_BEFORE))
def test_each_traffic_file_draws_the_roots_it_drew_before(tmp_path, cell,
                                                          seed):
    import hashlib

    import numpy as np
    root = copy_benchmark(tmp_path, TINY, traffic=TINY_TRAFFIC)
    spec = harness.resolve(root, cell)
    n, src, dst = harness.load_module(spec.generator).generate(
        spec.config, seed)
    warm, window = harness.split_roots(
        spec, n, src, dst, harness.draw_roots(n, src, dst, seed))

    def digest(a):
        return hashlib.sha256(np.asarray(a, np.int64).tobytes()).hexdigest()
    assert (len(warm), digest(warm)[:16], len(window),
            digest(window)[:16]) == ROOTS_BEFORE[cell, seed]


def test_unknown_device_kind_is_an_error():
    assert harness.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(harness.CellError):
        harness.peaks_for("TPU v9 imaginary")


def _run(cwd: pathlib.Path) -> subprocess.CompletedProcess:
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "3000000001", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_a_cpu_and_prints_no_result():
    proc = _run(ROOT)
    assert proc.returncode != 0
    assert "needs 1 TPU chip" in proc.stderr
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


def test_run_refuses_without_the_program(tmp_path):
    proc = _run(copy_benchmark(tmp_path))
    assert proc.returncode != 0
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())
