"""Run one cell of ``BENCHMARK.json`` once and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one item is found by name, so a cell, a
configuration, a traffic mix or a metric is added with files and
entries, never with an edit here:

- ``BENCHMARK.json``: the cells, the configurations' files, the metrics;
- ``bench/configs/<config>.json``: the deployment (its generator, sizes,
  source, cuts and the registration hints it assumes);
- ``bench/graphs/<generator>.py``: ``generate(config, seed)``;
- ``bench/traffic/<mix>.json``: the kernel, its compiled program, the
  burst, the number of answers checked and, where the mix fixes the
  size of each request, the depth of its roots (``root_depth``) and how
  many the window serves at most (``window_roots``);
- ``bench/metrics/<metric>.py``: ``read(ctx)``, the value or None;
- ``bench/reference/<kernel>.py``: ``solve`` and ``control``;
- ``bench/bytes/<kernel>.py``: the kernel's least HBM traffic;
- ``bench/peaks.json``: the device's peaks, by ``device_kind``.

A run: set-up (generate the graph from the seed, ``from_edges`` and
``EngineSession.register``, one burst of the cell's own shape to compile
and warm, result cache emptied), then the measured window, a closed loop
of bursts through ``enqueue`` -> ``QueryFuture.result()`` that ends when
the burst in flight at ``--seconds`` completes. Then, with the program's
state freed, a sample of the window's answers drawn from the seed is
compared with the plain reference. ``--trace 1`` runs the window under
the engine's profiler hook and reports the per-layer metrics; ``--trace
0`` reports the end-to-end ones.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import importlib.util
import json
import os
import pathlib
import shutil
import sys
import tempfile
import time
import types

import numpy as np

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# what a traffic file may set: one client's closed loop of bursts is the
# only traffic this harness drives, so any other key (an arrival process,
# more clients) is refused rather than silently ignored
TRAFFIC_KEYS = {"kernel", "program", "burst", "check_sample", "root_depth",
                "window_roots", "why"}
# roots looked at, per root the window needs, before a graph is said to
# lack roots of the mix's depth
DEPTH_TRIES = 40


class CellError(Exception):
    """The cell cannot be run as BENCHMARK.json and its files state it."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def read_json(path) -> dict:
    return json.loads(pathlib.Path(path).read_text())


def load_module(path: pathlib.Path):
    """Import one file of the benchmark by its path."""
    if not path.is_file():
        raise CellError(f"no file {path}")
    name = "bench_file_" + "".join(c if c.isalnum() else "_"
                                   for c in str(path))
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def resolve(root: pathlib.Path, workload: str) -> types.SimpleNamespace:
    """The cell and every file it names, found by name under ``root``."""
    root = pathlib.Path(root)
    bench = read_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise CellError(f"no cell {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    bench_dir = root / "bench"
    traffic_file = bench_dir / "traffic" / f"{cell['traffic']}.json"
    if not traffic_file.is_file():
        raise CellError(f"no traffic file {traffic_file}")
    config = read_json(root / configs[cell["config"]]["file"])
    traffic = read_json(traffic_file)
    unknown = sorted(set(traffic) - TRAFFIC_KEYS)
    if unknown:
        raise CellError(f"{traffic_file} sets {unknown}, which this harness "
                        f"does not implement (it reads {sorted(TRAFFIC_KEYS)})")
    metrics = {}
    for kind in ("end_to_end", "per_layer"):
        metrics[kind] = [m for m in bench[kind] if _applies(m, workload)]
        for m in metrics[kind]:
            path = bench_dir / "metrics" / f"{m['name']}.py"
            if not path.is_file():
                raise CellError(f"no reader {path} for metric {m['name']!r}")
    return types.SimpleNamespace(
        root=root, bench_dir=bench_dir, cell=cell, config=config,
        traffic=traffic, end_to_end=metrics["end_to_end"],
        per_layer=metrics["per_layer"],
        generator=bench_dir / "graphs" / f"{config['generator']}.py",
        reference=bench_dir / "reference" / f"{traffic['kernel']}.py",
        bytes_model=bench_dir / "bytes" / f"{traffic['kernel']}.py")


def peaks_for(device_kind: str, bench_dir: pathlib.Path = BENCH_DIR) -> dict:
    table = read_json(bench_dir / "peaks.json")["devices"]
    if device_kind not in table:
        raise CellError(f"device kind {device_kind!r} is not in "
                        f"bench/peaks.json ({sorted(table)})")
    return table[device_kind]


def draw_roots(num_vertices: int, src: np.ndarray, dst: np.ndarray,
               seed: int) -> np.ndarray:
    """Every vertex with an out-edge that is not a self-loop (Graph500's
    root rule), in an order drawn from the seed."""
    deg = np.bincount(src[src != dst], minlength=num_vertices)
    return np.random.default_rng(seed).permutation(np.flatnonzero(deg > 0))


def split_roots(spec, num_vertices: int, src: np.ndarray, dst: np.ndarray,
                roots: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """(warm-up roots, window roots), both in the seed's order.

    By default the last burst of ``roots`` warms up and the window takes
    the rest. A mix that sets ``root_depth`` gives every request the same
    size, so that every seed brings the same work: the window takes the
    first ``window_roots`` roots whose deepest vertex lies ``root_depth``
    arcs away (``depth_of`` of the cell's reference, which looks at
    ``DEPTH_BATCH`` roots at a time), and the warm-up the
    roots of the smallest components, whose searches end in a round or
    two, since a burst at the window's depth would cost set-up a window.
    """
    burst = int(spec.traffic["burst"])
    if "root_depth" not in spec.traffic:
        return roots[-burst:], roots[:-burst]
    from scipy.sparse import csr_matrix
    from scipy.sparse.csgraph import connected_components

    target, wanted = int(spec.traffic["root_depth"]), int(
        spec.traffic["window_roots"])
    reference = load_module(spec.reference)
    depths = reference.depth_of(num_vertices, src, dst)
    window: list = []
    step = int(reference.DEPTH_BATCH)
    for lo in range(0, min(len(roots), wanted * DEPTH_TRIES), step):
        chunk = roots[lo:lo + step]
        window += chunk[depths(chunk) == target].tolist()
        if len(window) >= wanted:
            window = window[:wanted]
            break
    if len(window) < wanted:
        raise CellError(f"{len(window)} of {wanted} roots of depth {target} "
                        f"among the first {wanted * DEPTH_TRIES}")
    adj = csr_matrix((np.ones(len(src), np.int32), (src, dst)),
                     shape=(num_vertices, num_vertices))
    _, label = connected_components(adj, connection="weak")
    rest = roots[~np.isin(roots, window)]
    size = np.bincount(label)[label[rest]]
    warm = rest[np.argsort(size, kind="stable")[:burst]]
    return warm, np.asarray(window, roots.dtype)


class Reservoir:
    """A uniform sample of ``size`` items from a stream (Algorithm R),
    drawn from the seed: the same stream gives the same sample."""

    def __init__(self, size: int, seed: int):
        self.size = size
        self.items: list = []
        self.seen = 0
        self.rng = np.random.default_rng([seed, 1])

    def offer(self, item) -> None:
        self.seen += 1
        if len(self.items) < self.size:
            self.items.append(item)
            return
        j = int(self.rng.integers(0, self.seen))
        if j < self.size:
            self.items[j] = item


def serve_burst(session, graph_id: str, kernel: str, roots, on_answer=None):
    """One client burst: enqueue every root, then wait for each answer.
    Returns (attempted, failed, errors)."""
    pending = [(time.perf_counter(), int(r),
                session.enqueue(graph_id, kernel, [int(r)])) for r in roots]
    failed, errors = 0, []
    for t_enqueue, root, future in pending:
        try:
            row = future.result()
        except Exception as exc:  # counted, and the run is not correct
            failed += 1
            errors.append(f"{type(exc).__name__}: {exc}")
            continue
        if on_answer is not None:
            on_answer(time.perf_counter() - t_enqueue, root, row[0])
    return len(pending), failed, errors


def _counters(session) -> dict:
    out = {}
    for name, value in session.metrics().snapshot()["counters"].items():
        out[name] = sum(value.values()) if isinstance(value, dict) else value
    return out


def _find_xplane(directory: str) -> pathlib.Path:
    found = sorted(pathlib.Path(directory).rglob("*.xplane.pb"))
    if not found:
        raise CellError(f"the profiler wrote no .xplane.pb under {directory}")
    return found[-1]


def read_metrics(entries: list[dict], ctx, bench_dir: pathlib.Path) -> dict:
    """Each metric's reader, by name; a reader that finds nothing to
    read returns None and the metric is left out."""
    out = {}
    for m in entries:
        value = load_module(bench_dir / "metrics" / f"{m['name']}.py").read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def compare(spec, num_vertices: int, src, dst, sample: list) -> dict:
    """The sampled answers against the plain reference: every entry of
    every sampled row must be equal."""
    reference = load_module(spec.reference)
    roots = [root for root, _ in sample]
    got = np.stack([np.asarray(row, np.int64) for _, row in sample])
    want = reference.solve(num_vertices, src, dst, roots)
    return {"mismatched_entries": int((got != want).sum()),
            "entries": int(got.size)}


def run_cell(spec, seed: int, seconds: float, trace: bool,
             t_start: float, peaks: dict | None = None) -> dict:
    """Set up, measure and check one run; returns the result object."""
    import jax

    from repro.core.csr import from_edges
    from repro.engine import EngineSession

    cfg, traffic = spec.config, spec.traffic
    kernel, burst = traffic["kernel"], int(traffic["burst"])
    t = time.perf_counter()
    num_vertices, src, dst = load_module(spec.generator).generate(cfg, seed)
    roots = draw_roots(num_vertices, src, dst, seed)
    log(f"generate: {cfg['name']} V={num_vertices} E={len(src)} in "
        f"{time.perf_counter() - t:.3f}s; {len(roots)} eligible roots")
    t = time.perf_counter()
    warm_roots, roots = split_roots(spec, num_vertices, src, dst, roots)
    log(f"roots: {len(roots)} for the window, {len(warm_roots)} to warm up, "
        f"in {time.perf_counter() - t:.3f}s")
    assumed = cfg.get("assumed", {})
    profile_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    session = EngineSession(profiler_dir=profile_dir,
                            **assumed.get("session", {}))
    t = time.perf_counter()
    graph = from_edges(num_vertices, src, dst, dedup=bool(cfg["dedup"]),
                       name=cfg["name"])
    graph_id = session.register(graph, cfg["name"],
                                **assumed.get("register", {}))
    register_s = time.perf_counter() - t
    entry = session.registry.get(graph_id)
    log(f"register: {register_s:.3f}s, scheme {entry.decision.scheme!r}, "
        f"bucket {entry.bucket_shape}")

    # warm-up: one burst of the cell's own shape, from roots the window
    # never draws; then the result cache is emptied
    t = time.perf_counter()
    _, failed, errors = serve_burst(session, graph_id, kernel, warm_roots)
    if failed:
        raise CellError(f"warm-up burst failed: {errors[0]}")
    if session.result_cache is not None:
        session.result_cache.invalidate_graph(graph_id)
    log(f"warm-up burst: {time.perf_counter() - t:.3f}s")
    if trace and not session.start_profiler():
        raise CellError(f"profiler did not start: {session.profiler.error}")

    latencies: list[float] = []
    sample = Reservoir(int(traffic["check_sample"]), seed)

    def on_answer(latency, root, row):
        latencies.append(latency)
        sample.offer((root, row))

    attempted = failed = bursts = 0
    errors: list[str] = []
    compiles = [0]

    def on_event(event, duration, **_):
        if event == COMPILE_EVENT:
            compiles[0] += 1

    events0, counters0 = len(session.tracer.events), _counters(session)
    jax.monitoring.register_event_duration_secs_listener(on_event)
    annotate = (jax.profiler.TraceAnnotation("bench.window") if trace
                else contextlib.nullcontext())
    with annotate:
        t0 = time.perf_counter()
        # roots are never repeated, so no answer comes from the result
        # cache; the window ends early once its roots are all served
        while (bursts + 1) * burst <= len(roots):
            lo = bursts * burst
            n, f, e = serve_burst(session, graph_id, kernel,
                                  roots[lo:lo + burst], on_answer)
            attempted, failed, bursts = attempted + n, failed + f, bursts + 1
            errors += e
            if time.perf_counter() - t0 >= seconds:
                break
        t1 = time.perf_counter()
    jax.monitoring.unregister_event_duration_listener(on_event)
    window_s = t1 - t0
    setup_s = t0 - t_start
    device = jax.devices()[0]
    stats = device.memory_stats() or {}
    spans = session.tracer.events[events0:]
    counters1 = _counters(session)
    counters = {k: v - counters0.get(k, 0) for k, v in counters1.items()}
    reduced = None
    if trace:
        session.stop_profiler()
        if session.profiler.error:
            raise CellError(f"profiler: {session.profiler.error}")
        from bench.trace_reduce import load_xplane, reduce_trace
        planes = load_xplane(_find_xplane(profile_dir))
        shutil.rmtree(profile_dir, ignore_errors=True)
        try:
            reduced = reduce_trace(planes)
        except ValueError as exc:
            shape = {p["name"]: [ln["name"] for ln in p["lines"]]
                     for p in planes}
            raise CellError(f"trace: {exc}; planes and lines {shape}")
    log(f"window: {bursts} bursts, {len(latencies)} answers, {failed} "
        f"failed, {window_s:.3f}s; setup {setup_s:.3f}s")
    ctx = types.SimpleNamespace(
        cell=spec.cell, config=cfg, traffic=traffic, bench_dir=spec.bench_dir,
        setup_s=setup_s, register_s=register_s, window_s=window_s,
        latencies_s=latencies, answered=len(latencies), attempted=attempted,
        spans=spans, counters=counters, xla_compiles=compiles[0],
        trace=reduced, peaks=peaks, num_vertices=num_vertices,
        num_edges=len(src), bytes_model=spec.bytes_model)
    metrics = read_metrics(spec.per_layer if trace else spec.end_to_end, ctx,
                           spec.bench_dir)
    session.close()
    del session, entry, graph
    gc.collect()

    t = time.perf_counter()
    checked = (compare(spec, num_vertices, src, dst, sample.items)
               if sample.items else {"mismatched_entries": None, "entries": 0})
    log(f"reference: {len(sample.items)} of {len(latencies)} answers, "
        f"{checked['entries']} entries, in {time.perf_counter() - t:.3f}s")
    for err in errors[:3]:
        log(f"failed request: {err}")
    compared = {
        "mismatched_entries": {"value": checked["mismatched_entries"],
                               "limit": 0},
        "failed_requests": {"value": failed, "limit": 0},
    }
    correct = (bool(sample.items) and failed == 0
               and checked["mismatched_entries"] == 0)
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics,
              "device": {"platform": device.platform,
                         "kind": device.device_kind,
                         "count": len(jax.devices()),
                         "memory_peak_bytes": stats.get("peak_bytes_in_use")}}
    if reduced is not None:
        result["device"]["busy_s"] = reduced["busy_s"]
        result["device"]["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["compared"] = compared
    for name, c in compared.items():
        log(f"compared {name} {c['value']} limit {c['limit']}")
    return result


def main(argv=None, t_start: float | None = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="run one benchmark cell once")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        spec = resolve(ROOT, args.workload)
        import repro  # noqa: F401  the system under test
    except (CellError, ImportError, KeyError, OSError) as exc:
        log(f"bench: cannot run {args.workload!r}: {exc}")
        return 2
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < spec.cell["chips"]:
        log(f"bench: {args.workload!r} needs {spec.cell['chips']} TPU chip(s); "
            f"JAX reports {len(devices)} {devices[0].platform!r} device(s)")
        return 2
    try:
        peaks = peaks_for(devices[0].device_kind, spec.bench_dir)
    except CellError as exc:
        log(f"bench: {exc}")
        return 2
    # the cache lives at a fixed path inside the checkout, whatever the
    # environment names, so that two checkouts never share one
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    from repro.compile_cache import enable_compile_cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    log(f"compile cache: {enable_compile_cache()}")
    result = run_cell(spec, args.seed, args.seconds, bool(args.trace),
                      t_start, peaks)
    print(json.dumps(result), flush=True)
    return 0
