"""Run one cell of ``BENCHMARK.json`` once and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one item is found by name, so a cell, a
configuration, a traffic mix or a metric is added with files and
entries, never with an edit here:

- ``BENCHMARK.json``: the cells, the configurations' files, the metrics;
- ``bench/configs/<config>.json``: the deployment (its generator, sizes,
  source, cuts and the registration hints it assumes);
- ``bench/graphs/<generator>.py``: ``generate(config, seed)``;
- ``bench/traffic/<mix>.json``: the traffic, in one of two forms (below);
- ``bench/metrics/<metric>.py``: ``read(ctx)``, the value or None;
- ``bench/reference/<kernel>.py``: ``solve``, ``depth_of`` and
  ``control``, and optionally ``mismatched(got, want)`` where a kernel's
  answers are compared within a tolerance (the default is exact);
- ``bench/bytes/<kernel>.py``: the kernel's least HBM traffic;
- ``bench/peaks.json``: the device's peaks, by ``device_kind``.

A traffic file is in one of two forms. The one-kernel form names a
``kernel``, its compiled ``program``, ``burst``, ``check_sample`` and,
where the mix fixes the size of each request, the depth of its roots,
``root_depth``, and how many the window serves at most,
``window_roots``: a closed loop of one client's bursts, read as one
class. A mix gives ``classes``, each with those keys less ``burst`` plus
``sources`` per request (default 1) and ``share``, the fraction of
arrivals in the class, and ``arrivals`` (``process`` "poisson",
``rate_per_s``, an optional burst overlay ``burst_every_s`` /
``burst_size``, and ``schedule_seed``): an open loop. A mix cell's
``rate_per_s`` is 4/5 of the knee that ``bench/knee.py`` finds for it
on the chip (the highest swept rate whose p95 latency is at most twice
that at the lowest, every arrival answered), rounded down to 0.05 req/s.
Any key the harness does not drive is refused.

A run: set-up (generate the graph from the seed, choose the roots,
``from_edges`` and ``EngineSession.register``, warm up, result cache
emptied), then the measured window, then, with the program's state
freed, a sample of the window's answers drawn from the seed is compared
with the plain reference, every row of every sampled request. ``--trace
1`` runs the window under the engine's profiler hook and reports the
per-layer metrics; ``--trace 0`` reports the end-to-end ones.

Closed loop: the warm-up is one burst of the cell's own shape; the
window is whole bursts through ``enqueue`` -> ``QueryFuture.result()``
and ends when the burst in flight at ``--seconds`` completes; a
request's latency runs from its enqueue to the return of its
``result()``, read in enqueue order.

Open loop: the arrival times and the class of each arrival come from
``schedule_seed`` alone, so every ``--seed`` sees the same schedule and
only the graph and the roots change with it. The warm-up serves, for
each class, one request and then every larger number of requests at
once that the scheduler coalesces into one launch, since the backend
compiles a program per real source count. The engine's auto-flush
thread starts with the window. In the window each request is enqueued
when it is due, whether or not earlier ones have been answered, and
launches are left to the engine's flush policy (its ``max_delay`` tick,
on every ``enqueue`` and on the auto-flush thread); the harness never
flushes. The window covers the arrivals due in ``[0, --seconds)`` and
closes when the last of them is answered. A request's latency runs from
its due time to the moment its answer is ready: a watcher thread notes
each future as it resolves, so a fast answer is not timed behind a slow
one enqueued earlier. An answer that a request's own ``enqueue`` served
(the engine's tick runs inside ``enqueue``) is ready when ``enqueue``
returns, as it is for any client. How late the generator enqueued each
request (``enqueue`` returned - due) is logged and kept in
``ctx.arrival_lag_s``. The requests each class checks are drawn from
the seed before the window; the rows of every other answer are let go
as soon as it resolves.
"""
from __future__ import annotations

import argparse
import collections
import contextlib
import gc
import importlib.util
import json
import os
import pathlib
import shutil
import sys
import tempfile
import threading
import time
import types

import numpy as np

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# what a traffic file may set, in each of its forms: any other key (more
# clients, another arrival process) is refused rather than silently
# ignored
TRAFFIC_KEYS = {"kernel", "program", "burst", "check_sample", "root_depth",
                "window_roots", "why"}
MIX_KEYS = {"classes", "arrivals", "why"}
CLASS_KEYS = {"kernel", "program", "check_sample", "sources", "root_depth",
              "window_roots", "share"}
ARRIVAL_KEYS = {"process", "rate_per_s", "burst_every_s", "burst_size",
                "schedule_seed"}
# roots looked at, per root the window needs, before a graph is said to
# lack roots of the mix's depth
DEPTH_TRIES = 40
# how often the open loop's watcher looks for answers that are ready
POLL_S = 1e-3
# how long past the last arrival's enqueue the open loop waits for
# answers before it counts the missing ones as failed
PATIENCE_S = 120.0


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


def _refuse_unknown(items: dict, allowed: set, where: str) -> None:
    unknown = sorted(set(items) - allowed)
    if unknown:
        raise CellError(f"{where} sets {unknown}, which this harness does "
                        f"not implement (it reads {sorted(allowed)})")


def traffic_classes(traffic: dict, bench_dir: pathlib.Path,
                    where: str = "traffic") -> list:
    """The request classes a traffic file describes, in its order, each
    a namespace with the class's keys (``sources`` 1 and ``share`` 1.0
    in the one-kernel form) and the paths of its kernel's reference and
    bytes model. Refuses, with a `CellError`, any key or combination the
    harness does not drive."""
    if "classes" in traffic:
        _refuse_unknown(traffic, MIX_KEYS, where)
        if "arrivals" not in traffic:
            raise CellError(f"{where}: a closed loop drives one kernel; a "
                            "mix of classes needs arrivals")
        entries = traffic["classes"]
        if not isinstance(entries, list) or not entries:
            raise CellError(f"{where}: classes must be a non-empty list")
        for i, entry in enumerate(entries):
            _refuse_unknown(entry, CLASS_KEYS, f"{where} class {i}")
        _check_arrivals(traffic["arrivals"], where)
    else:
        _refuse_unknown(traffic, TRAFFIC_KEYS, where)
        if "burst" not in traffic:
            raise CellError(f"{where}: a closed loop needs a burst")
        if "root_depth" in traffic and "window_roots" not in traffic:
            raise CellError(f"{where}: a closed loop with a root_depth "
                            "needs window_roots")
        entries = [{"share": 1.0, **{k: traffic[k] for k in CLASS_KEYS
                                     if k in traffic}}]
    classes = []
    for i, entry in enumerate(entries):
        missing = sorted({"kernel", "program", "check_sample", "share"}
                         - set(entry))
        if missing:
            raise CellError(f"{where} class {i} lacks {missing}")
        cls = types.SimpleNamespace(
            kernel=entry["kernel"], program=entry["program"],
            check_sample=int(entry["check_sample"]),
            sources=int(entry.get("sources", 1)),
            root_depth=entry.get("root_depth"),
            window_roots=entry.get("window_roots"),
            share=float(entry["share"]),
            reference=bench_dir / "reference" / f"{entry['kernel']}.py",
            bytes_model=bench_dir / "bytes" / f"{entry['kernel']}.py")
        if cls.sources < 1 or cls.check_sample < 1 or not cls.share > 0:
            raise CellError(f"{where} class {i}: sources and check_sample "
                            "must be at least 1, and share above 0")
        classes.append(cls)
    kernels = [c.kernel for c in classes]
    if len(set(kernels)) != len(kernels):
        raise CellError(f"{where}: one class per kernel ({kernels}), so "
                        "that launches are attributed to a class by kernel")
    if abs(sum(c.share for c in classes) - 1.0) > 1e-9:
        raise CellError(f"{where}: the classes' shares add up to "
                        f"{sum(c.share for c in classes)}, not 1")
    return classes


def _check_arrivals(arrivals: dict, where: str) -> None:
    _refuse_unknown(arrivals, ARRIVAL_KEYS, f"{where} arrivals")
    if arrivals.get("process") != "poisson":
        raise CellError(f"{where}: the arrival process must be "
                        f"'poisson', not {arrivals.get('process')!r}")
    if not float(arrivals.get("rate_per_s", 0)) > 0:
        raise CellError(f"{where}: arrivals need a rate_per_s above 0")
    if ("burst_every_s" in arrivals) != ("burst_size" in arrivals):
        raise CellError(f"{where}: a burst overlay needs both "
                        "burst_every_s and burst_size")
    if "burst_every_s" in arrivals and not (
            float(arrivals["burst_every_s"]) > 0
            and int(arrivals["burst_size"]) >= 1):
        raise CellError(f"{where}: burst_every_s must be above 0 and "
                        "burst_size at least 1")
    seed = arrivals.get("schedule_seed")
    if not isinstance(seed, int) or seed < 0:
        raise CellError(f"{where}: arrivals need a schedule_seed, a "
                        "whole number of at least 0")


def resolve(root: pathlib.Path, workload: str) -> types.SimpleNamespace:
    """The cell and every file it names, found by name under ``root``:
    its request ``classes``, and either the closed loop's ``burst`` or
    the open loop's ``arrivals`` (the other None)."""
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
    classes = traffic_classes(traffic, bench_dir, str(traffic_file))
    metrics = {}
    for kind in ("end_to_end", "per_layer"):
        metrics[kind] = [m for m in bench[kind] if _applies(m, workload)]
        for m in metrics[kind]:
            path = bench_dir / "metrics" / f"{m['name']}.py"
            if not path.is_file():
                raise CellError(f"no reader {path} for metric {m['name']!r}")
    return types.SimpleNamespace(
        root=root, bench_dir=bench_dir, cell=cell, config=config,
        traffic=traffic, classes=classes, end_to_end=metrics["end_to_end"],
        per_layer=metrics["per_layer"],
        generator=bench_dir / "graphs" / f"{config['generator']}.py",
        burst=int(traffic["burst"]) if "burst" in traffic else None,
        arrivals=traffic.get("arrivals"))


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


def roots_of_depth(reference_path: pathlib.Path, num_vertices: int,
                   src: np.ndarray, dst: np.ndarray, roots: np.ndarray,
                   target: int, wanted: int) -> np.ndarray:
    """The first ``wanted`` of ``roots``, in their order, whose deepest
    vertex lies ``target`` away by the reference's ``depth_of`` (which
    looks at ``DEPTH_BATCH`` roots at a time)."""
    if wanted <= 0:
        return roots[:0]
    reference = load_module(reference_path)
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
    return np.asarray(window, roots.dtype)


def smallest_component_roots(num_vertices: int, src: np.ndarray,
                             dst: np.ndarray, rest: np.ndarray,
                             count: int) -> np.ndarray:
    """``count`` of ``rest`` whose weakly connected components are the
    smallest: their searches end in a round or two."""
    from scipy.sparse import csr_matrix
    from scipy.sparse.csgraph import connected_components

    adj = csr_matrix((np.ones(len(src), np.int32), (src, dst)),
                     shape=(num_vertices, num_vertices))
    _, label = connected_components(adj, connection="weak")
    size = np.bincount(label)[label[rest]]
    return rest[np.argsort(size, kind="stable")[:count]]


def split_roots(spec, num_vertices: int, src: np.ndarray, dst: np.ndarray,
                roots: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """(warm-up roots, window roots) of a closed loop, both in the
    seed's order.

    By default the last burst of ``roots`` warms up and the window takes
    the rest. A mix that sets ``root_depth`` gives every request the same
    size, so that every seed brings the same work: the window takes the
    first ``window_roots`` roots of that depth (`roots_of_depth`), and
    the warm-up the roots of the smallest components, since a burst at
    the window's depth would cost set-up a window.
    """
    (cls,) = spec.classes
    if cls.root_depth is None:
        return roots[-spec.burst:], roots[:-spec.burst]
    window = roots_of_depth(cls.reference, num_vertices, src, dst, roots,
                            int(cls.root_depth), int(cls.window_roots))
    rest = roots[~np.isin(roots, window)]
    return (smallest_component_roots(num_vertices, src, dst, rest,
                                     spec.burst), window)


def arrival_schedule(arrivals: dict, shares: list[float],
                     seconds: float) -> list[tuple[float, int]]:
    """(due time in seconds from the window's start, class index) of
    every arrival due in ``[0, seconds)``, in due order, from the file's
    ``schedule_seed`` alone: Poisson arrivals at ``rate_per_s`` plus,
    where the file gives one, ``burst_size`` arrivals at once every
    ``burst_every_s`` (the first at ``burst_every_s``); each arrival's
    class drawn by ``shares``. A longer window only appends arrivals."""
    seed = int(arrivals["schedule_seed"])
    gaps = np.random.default_rng([seed, 0])
    mean_gap = 1.0 / float(arrivals["rate_per_s"])
    times: list[float] = []
    t = float(gaps.exponential(mean_gap))
    while t < seconds:
        times.append(t)
        t += float(gaps.exponential(mean_gap))
    if "burst_every_s" in arrivals:
        every, size = (float(arrivals["burst_every_s"]),
                       int(arrivals["burst_size"]))
        k = 1
        while k * every < seconds:
            times += [k * every] * size
            k += 1
    times.sort()
    draws = np.random.default_rng([seed, 1]).random(len(times))
    bounds = np.cumsum(shares)
    picks = np.minimum(np.searchsorted(bounds, draws, side="right"),
                       len(shares) - 1)
    return [(at, int(i)) for at, i in zip(times, picks)]


def plan_open_loop(spec, seed: int, seconds: float, num_vertices: int,
                   src: np.ndarray, dst: np.ndarray) -> types.SimpleNamespace:
    """The open loop's work: ``arrivals``, one namespace per arrival in
    due order (``at``, its due time from the window's start, ``cls``,
    ``roots`` and ``checked``), whose times and classes come from the
    schedule alone and whose roots, distinct across every request and
    class, from ``seed``; ``counts``, the arrivals of each class by
    kernel; and ``pool``, roots of the smallest components, none of them
    in the window, for the warm-up. Each class checks ``check_sample``
    of its arrivals (all, where it has fewer), drawn from ``seed``."""
    classes = spec.classes
    schedule = arrival_schedule(spec.arrivals, [c.share for c in classes],
                                seconds)
    counts = collections.Counter(classes[i].kernel for _, i in schedule)
    rest = draw_roots(num_vertices, src, dst, seed)
    window, checked = {}, {}
    pick = np.random.default_rng([seed, 1])
    for c in classes:
        count = counts[c.kernel]
        wanted = count * c.sources
        if c.window_roots is not None and wanted > int(c.window_roots):
            raise CellError(f"the {c.kernel} class's {count} arrivals need "
                            f"{wanted} roots; window_roots is "
                            f"{c.window_roots}")
        if c.root_depth is None:
            picked = rest[:wanted]
        else:
            picked = roots_of_depth(c.reference, num_vertices, src, dst,
                                    rest, int(c.root_depth), wanted)
        window[c.kernel] = picked
        rest = rest[~np.isin(rest, picked)]
        checked[c.kernel] = set(pick.choice(
            count, min(c.check_sample, count), replace=False).tolist())
    largest = max(max(counts[c.kernel], 1) * c.sources for c in classes)
    pool = smallest_component_roots(num_vertices, src, dst, rest, largest)
    if len(pool) < largest:
        raise CellError(f"{len(pool)} roots left to warm up, {largest} "
                        "needed")
    served = collections.Counter()
    arrivals = []
    for at, i in schedule:
        c = classes[i]
        k = served[c.kernel]
        arrivals.append(types.SimpleNamespace(
            at=at, cls=c, roots=window[c.kernel][k * c.sources:
                                                 (k + 1) * c.sources],
            checked=k in checked[c.kernel]))
        served[c.kernel] += 1
    return types.SimpleNamespace(arrivals=arrivals, counts=counts, pool=pool)


def warm_up_open(session, graph_id: str, classes: list, counts: dict,
                 pool: np.ndarray) -> dict:
    """For each class, one request, then 2, 3, ... requests at once up
    to the class's arrivals in the window, each set served together, so
    that every launch size the scheduler can coalesce them into has run:
    the backend compiles a program per real source count of a launch,
    not only per bucket. A set that the scheduler splits into two
    launches or more has met its source cap; larger sets would only
    repeat sizes already run. The result cache is emptied after each
    set, so that every launch runs all of its sources. Returns, by
    kernel, the number of requests in each set served."""
    served = {}
    for c in classes:
        served[c.kernel] = []
        for k in range(1, max(counts[c.kernel], 1) + 1):
            launches = session.scheduler.launches
            futures = [session.enqueue(graph_id, c.kernel,
                                       pool[i * c.sources:
                                            (i + 1) * c.sources].tolist())
                       for i in range(k)]
            try:
                for future in futures:
                    future.result()
            except Exception as exc:
                raise CellError(f"warm-up of {k} {c.kernel} requests "
                                f"failed: {exc}") from exc
            if session.result_cache is not None:
                session.result_cache.invalidate_graph(graph_id)
            served[c.kernel].append(k)
            if session.scheduler.launches - launches > 1:
                break
    return served


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


def _answered(future) -> bool:
    """Whether a future has resolved, read without a call into the
    scheduler (``done()`` runs its flush tick and waits for its lock):
    the scheduler fills ``telemetry`` as it serves a request, and sets
    ``exception()`` where the request failed."""
    return bool(future.telemetry) or future.exception() is not None


def serve_open(session, graph_id: str, arrivals: list, t0: float,
               patience_s: float = PATIENCE_S) -> list:
    """The open loop: each of ``arrivals`` (namespaces with ``at``, the
    due time after ``t0``, ``cls``, ``roots`` and ``checked``) is
    enqueued when it is due, whatever is still pending, while a watcher
    thread notes the moment each answer is ready. Returns one record per
    arrival, in due order: ``kernel``, ``roots``, ``checked``, ``due``,
    ``enqueued`` (when ``enqueue`` returned), ``ready`` (None where no
    answer came within ``patience_s`` of the last enqueue), ``error``,
    and the ``future`` of each checked request that was answered; the
    others' futures, and with them their rows, are let go as each
    resolves."""
    lock = threading.Lock()
    pending: list = []
    records: list = []
    sent = threading.Event()
    give_up = [float("inf")]

    def watch():
        while True:
            with lock:
                waiting = list(pending)
            ready = []
            for rec in waiting:
                if _answered(rec.future):
                    rec.ready = time.perf_counter()
                    ready.append(rec)
            for rec in ready:
                exc = rec.future.exception()
                if exc is not None:
                    rec.error = f"{type(exc).__name__}: {exc}"
                if exc is not None or not rec.checked:
                    rec.future = None
            with lock:
                for rec in ready:
                    pending.remove(rec)
                left = len(pending)
            if sent.is_set() and (not left
                                  or time.perf_counter() > give_up[0]):
                return
            time.sleep(POLL_S)

    watcher = threading.Thread(target=watch, name="bench-answers",
                               daemon=True)
    watcher.start()
    try:
        for arrival in arrivals:
            due = t0 + arrival.at
            wait = due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            rec = types.SimpleNamespace(
                kernel=arrival.cls.kernel, roots=arrival.roots,
                checked=arrival.checked, due=due, enqueued=None, ready=None,
                future=None, error=None)
            records.append(rec)
            try:
                future = session.enqueue(graph_id, rec.kernel,
                                         [int(r) for r in rec.roots])
            except Exception as exc:  # counted, and the run is not correct
                rec.enqueued = rec.ready = time.perf_counter()
                rec.error = f"{type(exc).__name__}: {exc}"
                continue
            rec.enqueued = time.perf_counter()
            rec.future = future
            with lock:
                pending.append(rec)
    finally:
        give_up[0] = time.perf_counter() + patience_s
        sent.set()
        watcher.join()
    for rec in records:
        if rec.ready is None and rec.error is None:
            rec.error = f"no answer within {patience_s:.0f}s of the last enqueue"
            rec.future = None
    return records


def _counters(session) -> dict:
    out = {}
    for name, value in session.metrics().snapshot()["counters"].items():
        out[name] = sum(value.values()) if isinstance(value, dict) else value
    return out


def _kernel_counters(session) -> dict:
    """By kernel: every counter summed over its label sets whose
    ``kernel`` label names that kernel (the backend's step, lane and
    pass counters), so that a mix's classes are read apart."""
    out: dict = collections.defaultdict(dict)
    for name, value in session.metrics().snapshot()["counters"].items():
        if not isinstance(value, dict):
            continue
        for key, count in value.items():
            labels = dict(part.split("=", 1) for part in key.split(",")
                          if "=" in part)
            if "kernel" in labels:
                mine = out[labels["kernel"]]
                mine[name] = mine.get(name, 0) + count
    return dict(out)


def _kernel_deltas(before: dict, after: dict) -> dict:
    """The window's delta of each kernel's counters."""
    return {kernel: {name: value - before.get(kernel, {}).get(name, 0)
                     for name, value in counters.items()}
            for kernel, counters in after.items()}


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


def _exact_mismatches(got: np.ndarray, want: np.ndarray) -> int:
    return int((np.asarray(got, np.int64) != np.asarray(want)).sum())


def compare(cls, num_vertices: int, src, dst, sample: list) -> dict:
    """The sampled answers against the plain reference of a request
    class's kernel (``cls.reference``). Each item is (roots, rows): a
    request's root or roots and the rows it was served, one per root;
    every row is compared. The entries count as mismatched by the
    reference's ``mismatched(got, want)`` where it has one, and else
    where they differ as int64; a request served the wrong number of rows
    mismatches in every entry it should have had."""
    reference = load_module(cls.reference)
    mismatched = getattr(reference, "mismatched", _exact_mismatches)
    roots = [np.atleast_1d(np.asarray(r, np.int64)) for r, _ in sample]
    want = reference.solve(num_vertices, src, dst, np.concatenate(roots))
    bad = entries = lo = 0
    for request_roots, (_, rows) in zip(roots, sample):
        expected = want[lo:lo + len(request_roots)]
        lo += len(request_roots)
        got = np.atleast_2d(np.asarray(rows))
        entries += expected.size
        bad += (mismatched(got, expected) if got.shape == expected.shape
                else expected.size)
    return {"mismatched_entries": int(bad), "entries": int(entries)}


def _class_context(classes: list, spans: list, latencies: dict,
                   kernel_counters: dict) -> dict:
    """Per class, by kernel: its program, bytes model, the requests it
    answered and their latencies, the real sources they carried, the
    launches the window's ``launch`` spans attribute to its kernel, with
    each span's seconds (``launch_s``), and the window's delta of the
    counters labelled with its kernel (``counters``)."""
    launch_s = collections.defaultdict(list)
    for s in spans:
        if s["name"] == "launch":
            launch_s[s["args"].get("kernel")].append(1e-6 * s["dur"])
    out = {}
    for c in classes:
        mine = latencies[c.kernel]
        out[c.kernel] = types.SimpleNamespace(
            kernel=c.kernel, program=c.program, bytes_model=c.bytes_model,
            answered=len(mine), latencies_s=mine,
            launches=len(launch_s[c.kernel]), launch_s=launch_s[c.kernel],
            sources=len(mine) * c.sources,
            counters=kernel_counters.get(c.kernel, {}))
    return out


def _percentiles(values: list) -> str:
    if not values:
        return "none"
    p50, p95 = np.percentile(values, [50, 95])
    return f"p50 {p50:.6f}s p95 {p95:.6f}s max {max(values):.6f}s"


# flushes of an open-loop window logged one to a line, at most
TIMELINE_LINES = 40


def _flush_timeline(spans: list) -> list[str]:
    """One line per flush of the window, from the engine's spans: when
    it began, in seconds after the window's first ``enqueue``, how many
    requests it took, and each of its launches' kernel, lanes and
    seconds; the order in which arrivals coalesced."""
    starts = [s["ts"] for s in spans if s["name"] == "enqueue"]
    if not starts:
        return []
    origin = min(starts)
    lines = []
    for flush in (s for s in spans if s["name"] == "flush"):
        end = flush["ts"] + flush["dur"]
        launches = [f"{s['args'].get('kernel')} {s['args'].get('lanes')} "
                    f"lanes {1e-6 * s['dur']:.3f}s"
                    for s in spans if s["name"] == "launch"
                    and flush["ts"] <= s["ts"] <= end]
        if launches:
            lines.append(f"flush at {1e-6 * (flush['ts'] - origin):.3f}s: "
                         f"{flush['args'].get('requests')} requests; "
                         + ", ".join(launches))
    if len(lines) > TIMELINE_LINES:
        lines = lines[:TIMELINE_LINES] + [
            f"... {len(lines) - TIMELINE_LINES} more flushes"]
    return lines


def run_cell(spec, seed: int, seconds: float, trace: bool,
             t_start: float, peaks: dict | None = None) -> dict:
    """Set up, measure and check one run; returns the result object."""
    import jax

    from repro.core.csr import from_edges
    from repro.engine import EngineSession

    cfg, classes = spec.config, spec.classes
    open_loop = spec.arrivals is not None
    t = time.perf_counter()
    num_vertices, src, dst = load_module(spec.generator).generate(cfg, seed)
    if open_loop:
        log(f"generate: {cfg['name']} V={num_vertices} E={len(src)} in "
            f"{time.perf_counter() - t:.3f}s")
        t = time.perf_counter()
        plan = plan_open_loop(spec, seed, seconds, num_vertices, src, dst)
        log(f"roots: {dict(plan.counts)} arrivals by kernel, "
            f"{len(plan.pool)} to warm up, in "
            f"{time.perf_counter() - t:.3f}s")
    else:
        (only,) = classes
        roots = draw_roots(num_vertices, src, dst, seed)
        log(f"generate: {cfg['name']} V={num_vertices} E={len(src)} in "
            f"{time.perf_counter() - t:.3f}s; {len(roots)} eligible roots")
        t = time.perf_counter()
        warm_roots, roots = split_roots(spec, num_vertices, src, dst, roots)
        log(f"roots: {len(roots)} for the window, {len(warm_roots)} to warm "
            f"up, in {time.perf_counter() - t:.3f}s")
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
    log(f"register: {register_s:.3f}s, scheme "
        f"{session.registry.get(graph_id).decision.scheme!r}, bucket "
        f"{session.registry.get(graph_id).bucket_shape}")

    # warm-up, from roots the window never draws; then the result cache
    # is emptied
    t = time.perf_counter()
    if open_loop:
        sets = warm_up_open(session, graph_id, classes, plan.counts,
                            plan.pool)
        log(f"warm-up: requests served together, by kernel: {sets}")
    else:
        _, failed, errors = serve_burst(session, graph_id, only.kernel,
                                        warm_roots)
        if failed:
            raise CellError(f"warm-up burst failed: {errors[0]}")
    if session.result_cache is not None:
        session.result_cache.invalidate_graph(graph_id)
    log(f"warm-up {'requests' if open_loop else 'burst'}: "
        f"{time.perf_counter() - t:.3f}s")
    if trace and not session.start_profiler():
        raise CellError(f"profiler did not start: {session.profiler.error}")

    latencies: list[float] = []
    by_class: dict = {c.kernel: [] for c in classes}
    lags: list[float] = []
    attempted = failed = bursts = 0
    errors: list[str] = []
    samples: dict = {c.kernel: [] for c in classes}
    compiles = [0]

    def on_event(event, duration, **_):
        if event == COMPILE_EVENT:
            compiles[0] += 1

    events0, counters0 = len(session.tracer.events), _counters(session)
    kernels0 = _kernel_counters(session)
    jax.monitoring.register_event_duration_secs_listener(on_event)
    annotate = (jax.profiler.TraceAnnotation("bench.window") if trace
                else contextlib.nullcontext())
    with annotate:
        if open_loop:
            # started with the window, so that its ticks keep one phase
            # against the schedule in every run
            session.scheduler.start_auto_flush()
        t0 = t_window = time.perf_counter()
        if open_loop:
            records = serve_open(session, graph_id, plan.arrivals, t0)
            answered = [r for r in records if r.error is None]
            for rec in answered:
                latencies.append(rec.ready - rec.due)
                by_class[rec.kernel].append(rec.ready - rec.due)
            # rows, not futures, which would keep the session alive
            for rec in records:
                if rec.future is not None:
                    samples[rec.kernel].append((rec.roots,
                                                rec.future.result()))
                    rec.future = None
            lags = [r.enqueued - r.due for r in records]
            attempted, failed = len(records), len(records) - len(answered)
            errors = [r.error for r in records if r.error is not None]
            # the window runs from the first arrival's due time to the
            # last answer
            t0 = records[0].due if records else t0
            t1 = max((r.ready for r in answered), default=t0)
        else:
            reservoir = Reservoir(only.check_sample, seed)
            mine = by_class[only.kernel]

            def on_answer(latency, root, row):
                latencies.append(latency)
                mine.append(latency)
                reservoir.offer((root, row))

            # roots are never repeated, so no answer comes from the result
            # cache; the window ends early once its roots are all served
            burst = spec.burst
            while (bursts + 1) * burst <= len(roots):
                lo = bursts * burst
                n, f, e = serve_burst(session, graph_id, only.kernel,
                                      roots[lo:lo + burst], on_answer)
                attempted, failed, bursts = (attempted + n, failed + f,
                                             bursts + 1)
                errors += e
                if time.perf_counter() - t0 >= seconds:
                    break
            t1 = time.perf_counter()
            samples[only.kernel] = reservoir.items
    jax.monitoring.unregister_event_duration_listener(on_event)
    window_s = t1 - t0
    setup_s = t_window - t_start
    device = jax.devices()[0]
    stats = device.memory_stats() or {}
    spans = session.tracer.events[events0:]
    counters1 = _counters(session)
    counters = {k: v - counters0.get(k, 0) for k, v in counters1.items()}
    kernel_counters = _kernel_deltas(kernels0, _kernel_counters(session))
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
    if open_loop:
        log(f"window: {len(plan.arrivals)} arrivals, {len(latencies)} "
            f"answers, {failed} failed, {window_s:.3f}s; setup "
            f"{setup_s:.3f}s")
        log(f"generator lateness: {_percentiles(lags)}")
        for line in _flush_timeline(spans):
            log(line)
    else:
        log(f"window: {bursts} bursts, {len(latencies)} answers, {failed} "
            f"failed, {window_s:.3f}s; setup {setup_s:.3f}s")
    ctx = types.SimpleNamespace(
        cell=spec.cell, config=cfg, traffic=spec.traffic,
        bench_dir=spec.bench_dir, setup_s=setup_s, register_s=register_s,
        window_s=window_s, latencies_s=latencies, answered=len(latencies),
        attempted=attempted, spans=spans, counters=counters,
        kernel_counters=kernel_counters,
        xla_compiles=compiles[0], trace=reduced, peaks=peaks,
        num_vertices=num_vertices, num_edges=len(src),
        classes=_class_context(classes, spans, by_class, kernel_counters),
        arrival_lag_s=lags)
    if open_loop:
        for c in ctx.classes.values():
            line = (f"class {c.kernel}: {c.answered} answers, latency "
                    f"{_percentiles(c.latencies_s)}; {c.launches} launches, "
                    f"span {_percentiles(c.launch_s)}")
            if reduced is not None:
                prog = [p for name, p in reduced["programs"].items()
                        if c.program in name]
                count = sum(p["count"] for p in prog)
                line += (f"; {c.program} {count} runs, "
                         f"{sum(p['seconds'] for p in prog)}s on the device")
            log(line)
    metrics = read_metrics(spec.per_layer if trace else spec.end_to_end, ctx,
                           spec.bench_dir)
    session.close(drain=failed == 0)
    del session, graph
    gc.collect()

    t = time.perf_counter()
    mismatched = entries = checked_answers = 0
    for c in classes:
        sample = samples[c.kernel]
        if not sample:
            continue
        checked = compare(c, num_vertices, src, dst, sample)
        mismatched += checked["mismatched_entries"]
        entries += checked["entries"]
        checked_answers += len(sample)
        if open_loop:
            log(f"class {c.kernel}: {len(sample)} answers checked, "
                f"{checked['mismatched_entries']} of {checked['entries']} "
                "entries mismatched")
    log(f"reference: {checked_answers} of {len(latencies)} answers, "
        f"{entries} entries, in {time.perf_counter() - t:.3f}s")
    for err in errors[:3]:
        log(f"failed request: {err}")
    compared = {
        "mismatched_entries": {"value": mismatched if checked_answers
                               else None, "limit": 0},
        "failed_requests": {"value": failed, "limit": 0},
    }
    correct = bool(checked_answers) and failed == 0 and mismatched == 0
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


def chip_peaks(spec) -> dict:
    """The peaks of the chip JAX finds, from ``bench/peaks.json``; a
    `CellError` where it finds no TPU, fewer chips than the cell asks
    for, or a kind the table lacks."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < spec.cell["chips"]:
        raise CellError(f"{spec.cell['name']!r} needs {spec.cell['chips']} "
                        f"TPU chip(s); JAX reports {len(devices)} "
                        f"{devices[0].platform!r} device(s)")
    return peaks_for(devices[0].device_kind, spec.bench_dir)


def use_compile_cache() -> None:
    """JAX's persistent compile cache, at a fixed path inside the
    checkout whatever the environment names, so that two checkouts never
    share one and only a cell's first run in a checkout compiles."""
    import jax
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    from repro.compile_cache import enable_compile_cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    log(f"compile cache: {enable_compile_cache()}")


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
    try:
        peaks = chip_peaks(spec)
    except CellError as exc:
        log(f"bench: {exc}")
        return 2
    use_compile_cache()
    result = run_cell(spec, args.seed, args.seconds, bool(args.trace),
                      t_start, peaks)
    print(json.dumps(result), flush=True)
    return 0
