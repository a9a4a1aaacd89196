"""The open loop and the mix of request classes: the schedule, the
honest timing of answers, the per-class checks and the context handed to
the readers, and whole open-loop runs on the CPU at a tiny size."""
from __future__ import annotations

import threading
import time
import types

import numpy as np
import pytest

import jax.numpy as jnp

from bench import harness
from bench.conftest import ROOT, TINY_MIX, add_mix

SEEDS = (3000000007, 2**33 + 5)


def _plan(root, seed: int, seconds: float = 0.5):
    spec = harness.resolve(root, add_mix(root))
    n, src, dst = harness.load_module(spec.generator).generate(
        spec.config, seed)
    return spec, (n, src, dst), harness.plan_open_loop(
        spec, seed, seconds, n, src, dst)


def test_the_schedule_and_classes_are_the_same_for_every_seed(tiny_root):
    plans = [_plan(tiny_root, seed) for seed in SEEDS]
    times = [[(a.at, a.cls.kernel) for a in plan.arrivals]
             for _, _, plan in plans]
    assert times[0] == times[1]
    assert {k for _, k in times[0]} == {"bfs", "sssp"}
    assert all(0 <= at < 0.5 for at, _ in times[0])
    # only the roots change with the seed
    roots = [[tuple(a.roots) for a in plan.arrivals] for _, _, plan in plans]
    assert roots[0] != roots[1]
    for (spec, (n, src, dst), plan), drawn in zip(plans, roots):
        flat = [r for request in drawn for r in request]
        # distinct across every request and class, and none warms up
        assert len(flat) == len(set(flat))
        assert not set(flat) & set(plan.pool.tolist())
        for a in plan.arrivals:
            assert len(a.roots) == a.cls.sources
        bfs = [r for a in plan.arrivals if a.cls.kernel == "bfs"
               for r in a.roots]
        depth = harness.load_module(spec.classes[0].reference).depth_of(
            n, src, dst)
        assert set(depth(np.asarray(bfs)).tolist()) == {3}


def test_a_longer_window_only_appends_arrivals():
    shares = [c["share"] for c in TINY_MIX["classes"]]
    short = harness.arrival_schedule(TINY_MIX["arrivals"], shares, 1.0)
    long = harness.arrival_schedule(TINY_MIX["arrivals"], shares, 3.0)
    assert long[:len(short)] == short and len(long) > len(short)


def test_the_burst_overlay_adds_its_arrivals_at_once():
    arrivals = {"process": "poisson", "rate_per_s": 1e-9,
                "burst_every_s": 0.5, "burst_size": 3, "schedule_seed": 1}
    schedule = harness.arrival_schedule(arrivals, [0.5, 0.5], 1.6)
    assert [at for at, _ in schedule] == [0.5] * 3 + [1.0] * 3 + [1.5] * 3
    # about 20 per second, each class by its share
    schedule = harness.arrival_schedule(
        {"process": "poisson", "rate_per_s": 20.0, "schedule_seed": 3},
        [0.75, 0.25], 100.0)
    assert 1800 < len(schedule) < 2200
    share = np.mean([i == 0 for _, i in schedule])
    assert 0.72 < share < 0.78


def test_each_class_checks_requests_drawn_from_the_seed(tiny_root):
    checked = {}
    for seed in SEEDS + SEEDS[:1]:
        _, _, plan = _plan(tiny_root, seed, seconds=2.0)
        picked = {}
        for i, a in enumerate(plan.arrivals):
            picked.setdefault(a.cls.kernel, [])
            if a.checked:
                picked[a.cls.kernel].append(i)
        for a in plan.arrivals:
            # check_sample of them, or all where the class has fewer
            assert len(picked[a.cls.kernel]) == min(
                a.cls.check_sample, plan.counts[a.cls.kernel])
        checked.setdefault(seed, []).append(picked)
    # the same seed draws the same requests, another seed others
    assert checked[SEEDS[0]][0] == checked[SEEDS[0]][1]
    assert checked[SEEDS[0]][0] != checked[SEEDS[1]][0]


def _tiny_session(**options):
    from repro.core.csr import from_edges
    from repro.engine import EngineSession
    spec = harness.resolve(ROOT, "kron20.bfs.burst32")
    cfg = {**spec.config, "scale": 8}
    n, src, dst = harness.load_module(spec.generator).generate(cfg, 5)
    session = EngineSession(**options)
    gid = session.register(from_edges(n, src, dst, dedup=False), "g",
                           expected_queries=16)
    return session, gid, harness.draw_roots(n, src, dst, 5)


@pytest.mark.parametrize("sources,count,cap,sets", [
    (1, 6, None, [1, 2, 3, 4, 5, 6]),
    (4, 3, None, [1, 2, 3]),
    # 12 sources over a cap of 8: two launches, of 8 and 4, both run
    (4, 3, 8, [1, 2, 3]),
    (1, 6, 4, [1, 2, 3, 4, 5]),
    (1, 1, None, [1]),
    # a class with no arrivals in the window still warms up one request
    (1, 0, None, [1]),
])
def test_the_warm_up_serves_every_set_the_scheduler_coalesces(
        sources, count, cap, sets):
    session, gid, roots = _tiny_session(max_batch_sources=cap)
    cls = types.SimpleNamespace(kernel="bfs", sources=sources)
    try:
        served = harness.warm_up_open(session, gid, [cls], {"bfs": count},
                                      roots[:max(count, 1) * sources])
    finally:
        session.close()
    assert served == {"bfs": sets}


def test_a_warm_up_of_every_launch_size_leaves_nothing_to_compile():
    # the backend compiles a program per real source count of a launch
    # (its rows sliced on the device), so warming the buckets alone would
    # leave compiles for the window
    import jax
    session, gid, roots = _tiny_session(result_cache=False)
    cls = types.SimpleNamespace(kernel="bfs", sources=1)
    compiles = []

    def on_event(event, duration, **_):
        if event == harness.COMPILE_EVENT:
            compiles.append(event)
    jax.monitoring.register_event_duration_secs_listener(on_event)
    try:
        harness.warm_up_open(session, gid, [cls], {"bfs": 6}, roots[:6])
        compiles.clear()
        for size in (3, 6, 5, 1):
            session.enqueue(gid, "bfs", roots[10:10 + size].tolist()).result()
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
        session.close()
    assert compiles == []


class _Future:
    """A future resolved from a timer thread, after a delay of its own."""

    def __init__(self, delay: float, rows, error=None):
        self.telemetry: dict = {}
        self._rows, self._error = None, None

        def resolve():
            if error is not None:
                self._error = error
            else:
                self._rows = rows
                self.telemetry = {"kernel": "bfs"}
        self.timer = threading.Timer(delay, resolve)
        self.timer.start()

    def exception(self):
        return self._error

    def result(self):
        if self._error is not None:
            raise self._error
        return self._rows


class _Session:
    """Answers the i-th request after ``delays[i]`` seconds, or never
    where the delay is None, or fails it where it is an exception."""

    def __init__(self, delays):
        self.delays = list(delays)
        self.futures: list = []

    def enqueue(self, graph_id, kernel, roots):
        delay = self.delays.pop(0)
        error = delay if isinstance(delay, Exception) else None
        future = _Future(0.05 if error else (3600 if delay is None
                                             else delay),
                         np.asarray([roots]), error)
        self.futures.append(future)
        return future

    def stop(self):
        for f in self.futures:
            f.timer.cancel()
            f.timer.join(timeout=5)
            assert not f.timer.is_alive()


def _arrivals(*times, checked=True):
    cls = types.SimpleNamespace(kernel="bfs", sources=1)
    return [types.SimpleNamespace(at=at, cls=cls, roots=[i], checked=checked)
            for i, at in enumerate(times)]


def test_an_answer_ready_first_is_timed_at_its_readiness():
    # the first request is answered after 0.8 s, the second, due 0.05 s
    # later, after 0.1 s: it is not timed behind the first
    session = _Session([0.8, 0.1])
    try:
        first, second = harness.serve_open(
            session, "g", _arrivals(0.0, 0.05), time.perf_counter())
    finally:
        session.stop()
    assert first.error is None and second.error is None
    assert second.ready < first.ready
    assert 0.1 <= second.ready - second.due < 0.4
    assert 0.8 <= first.ready - first.due < 1.1
    assert second.enqueued - second.due < 0.04
    assert first.future.result().tolist() == [[0]]


def test_a_failed_or_missing_answer_is_counted_with_its_cause():
    session = _Session([RuntimeError("launch failed"), None, 0.05])
    try:
        failed, missing, sound = harness.serve_open(
            session, "g", _arrivals(0.0, 0.0, 0.0), time.perf_counter(),
            patience_s=0.3)
    finally:
        session.stop()
    assert failed.error == "RuntimeError: launch failed"
    assert failed.future is None
    assert missing.ready is None and "no answer" in missing.error
    assert sound.error is None


def test_only_the_checked_answers_are_kept():
    session = _Session([0.05, 0.05])
    arrivals = (_arrivals(0.0, checked=False)
                + _arrivals(0.0, checked=True))
    try:
        let_go, kept = harness.serve_open(session, "g", arrivals,
                                          time.perf_counter())
    finally:
        session.stop()
    assert let_go.error is None and let_go.ready is not None
    assert let_go.future is None
    assert kept.future.result().tolist() == [[0]]


def _reference_module(tmp_path, tolerance: str | None) -> types.SimpleNamespace:
    """A reference that answers (S, 4) rows, floats with the given
    ``mismatched``, or int64 with none."""
    step = "/ 8" if tolerance is not None else ""
    text = ("import numpy as np\n"
            "def solve(n, src, dst, sources):\n"
            "    s = np.asarray(sources, np.int64)[:, None]\n"
            f"    return s + np.arange(4) {step}\n")
    if tolerance is not None:
        text += ("def mismatched(got, want):\n"
                 f"    return int((abs(got - want) > {tolerance}).sum())\n")
    path = tmp_path / f"ref_{tolerance}.py"
    path.write_text(text)
    return types.SimpleNamespace(reference=path)


def test_a_reference_tolerance_holds_and_the_default_stays_exact(tmp_path):
    want = np.array([[2.0, 2.125, 2.25, 2.375]])
    near, far = want + 1e-6, want + 1e-2
    tolerant = _reference_module(tmp_path, "1e-4")
    exact = _reference_module(tmp_path, None)

    def bad(spec, rows):
        return harness.compare(spec, 4, None, None, [(2, rows)])[
            "mismatched_entries"]
    assert bad(tolerant, near) == 0
    assert bad(tolerant, far) == 4
    # the default compares entries as int64, exactly
    assert bad(exact, np.array([[2, 3, 4, 5]])) == 0
    assert bad(exact, np.array([[2, 3, 5, 5]])) == 1


def test_every_row_of_a_multi_source_request_is_compared():
    # 0 -> 1 -> 2 -> 3
    src, dst = np.array([0, 1, 2]), np.array([1, 2, 3])
    spec = types.SimpleNamespace(reference=ROOT / "bench/reference/bfs.py")
    reference = harness.load_module(spec.reference)
    roots = [0, 1, 2, 3]
    rows = reference.solve(4, src, dst, roots)
    sample = [(roots, rows), (2, rows[2])]
    assert harness.compare(spec, 4, src, dst, sample) == {
        "mismatched_entries": 0, "entries": 20}
    wrong = rows.copy()
    wrong[3, 0] = 7           # the last row of the 4-source request
    assert harness.compare(spec, 4, src, dst, [(roots, wrong)])[
        "mismatched_entries"] == 1
    # a request served fewer rows than roots mismatches in every entry
    assert harness.compare(spec, 4, src, dst, [(roots, rows[:3])])[
        "mismatched_entries"] == 16


def test_launches_are_attributed_to_a_class_by_kernel(tiny_root):
    spec = harness.resolve(tiny_root, add_mix(tiny_root))
    spans = [{"name": "launch", "dur": dur, "args": {"kernel": k}}
             for k, dur in (("bfs", 4e6), ("sssp", 2.5e6), ("bfs", 5e6),
                            ("bfs", 3e6))]
    spans += [{"name": "translate", "dur": 1e3, "args": {"kernel": "bfs"}}]
    classes = harness._class_context(
        spec.classes, spans, {"bfs": [0.5, 0.25, 1.0], "sssp": [2.0]},
        {"bfs": {"engine_kernel_steps_total": 21}})
    assert (classes["bfs"].launches, classes["bfs"].sources,
            classes["bfs"].answered) == (3, 3, 3)
    assert classes["bfs"].launch_s == pytest.approx([4.0, 5.0, 3.0])
    assert (classes["sssp"].launches, classes["sssp"].sources,
            classes["sssp"].program) == (1, 4, "sssp_multi")
    assert (classes["bfs"].counters, classes["sssp"].counters) == (
        {"engine_kernel_steps_total": 21}, {})
    # each kernel's roofline reads its own class of the mix
    ctx = types.SimpleNamespace(
        traffic=spec.traffic, classes=classes, num_vertices=4, num_edges=5,
        peaks={"hbm_bytes_per_s": 100e6},
        trace={"programs": {"jit_bfs_multi_steps": {"seconds": 6e-6,
                                                    "count": 3},
                            "jit_sssp_multi_steps": {"seconds": 2e-6,
                                                     "count": 1}}})
    bfs = harness.load_module(ROOT / "bench/metrics/bfs_roofline.py")
    sssp = harness.load_module(ROOT / "bench/metrics/sssp_roofline.py")
    kernel_ms = harness.load_module(ROOT / "bench/metrics/kernel_device_ms.py")
    # BFS: 3 launches x 36 bytes + 3 sources x 20 = 168 bytes, 1.68 us
    # of 6; SSSP: 56 + 4 x 20 = 136 bytes, 1.36 us of 2
    assert bfs.read(ctx) == pytest.approx(28.0)
    assert sssp.read(ctx) == pytest.approx(68.0)
    assert kernel_ms.read(ctx) is None


@pytest.mark.parametrize("cell", ["kron20.bfs.burst32", "grid100.bfs.burst4",
                                  "kron20.sssp.burst4"])
def test_a_closed_loop_class_counts_what_the_counters_count(
        tiny_root, monkeypatch, cell):
    # the rooflines read a class's launches and sources; in a closed loop
    # they are the backend's counters over the window, as before mixes
    seen = []
    monkeypatch.setattr(harness, "read_metrics",
                        lambda entries, ctx, bench_dir: seen.append(ctx)
                        or {})
    harness.run_cell(harness.resolve(tiny_root, cell), 3000000007, 0.3,
                     False, time.perf_counter())
    (ctx,) = seen
    (cls,) = ctx.classes.values()
    assert cls.launches == ctx.counters["engine_launches_total"] > 0
    assert cls.sources == ctx.counters["engine_sources_total"] > 0
    assert cls.latencies_s == ctx.latencies_s


def _run(root, cell: str, seed: int = 3000000007) -> dict:
    spec = harness.resolve(root, cell)
    return harness.run_cell(spec, seed, 0.5, False, time.perf_counter())


def test_a_sound_open_loop_run_is_correct(tiny_root, capsys):
    cell = add_mix(tiny_root)
    result = _run(tiny_root, cell)
    err = capsys.readouterr().err
    assert result["correct"] and result["failed"] == 0
    _, _, plan = _plan(tiny_root, 3000000007)
    assert result["attempted"] == len(plan.arrivals)
    assert result["compared"]["mismatched_entries"]["value"] == 0
    assert list(result)[-1] == "compared"
    assert set(result["metrics"]) == {"answers_per_s", "latency_p50_ms",
                                      "latency_p95_ms", "setup_s"}
    assert "generator lateness: p50" in err
    assert "class sssp: 2 answers checked" in err
    # the compared numbers are the last lines on standard error
    assert err.strip().splitlines()[-2:] == [
        "compared mismatched_entries 0 limit 0",
        "compared failed_requests 0 limit 0"]


@pytest.mark.parametrize("fault", ["answer_altered", "half_batch"])
def test_a_broken_open_loop_is_not_correct(tiny_root, monkeypatch, fault):
    from repro.engine import backends
    cell = add_mix(tiny_root)
    run = backends.SingleDeviceBackend.run

    def altered(self, handle, k, sources=None):
        return run(self, handle, k, sources).at[:, -1].add(1)

    def half_batch(self, handle, k, sources=None):
        srcs = np.atleast_1d(np.asarray(sources))
        half = max(len(srcs) // 2, 1)
        out = run(self, handle, k, srcs[:half])
        return jnp.concatenate([out] * -(-len(srcs) // half))[:len(srcs)]

    monkeypatch.setattr(backends.SingleDeviceBackend, "run",
                        altered if fault == "answer_altered" else half_batch)
    result = _run(tiny_root, cell)
    assert result["correct"] is False
    assert result["compared"]["mismatched_entries"]["value"] > 0


class _Snapshot:
    """A session whose metrics snapshot holds ``counters``."""

    def __init__(self, counters: dict):
        self.counters = counters

    def metrics(self):
        return self

    def snapshot(self):
        return {"counters": self.counters, "gauges": {}, "histograms": {}}


def test_kernel_labelled_counters_are_read_per_kernel():
    session = _Snapshot({
        "engine_kernel_steps_total": {"kernel=bfs": 14, "kernel=sssp": 13},
        "engine_lane_slots_total": {"kernel=bfs": 448, "kernel=sssp": 52},
        "engine_sources_total": {"backend=single": 36},
        "engine_launches_total": 3,
        "engine_other_total": {"backend=single,kernel=bfs": 2,
                               "backend=sharded,kernel=bfs": 5}})
    assert harness._kernel_counters(session) == {
        "bfs": {"engine_kernel_steps_total": 14,
                "engine_lane_slots_total": 448, "engine_other_total": 7},
        "sssp": {"engine_kernel_steps_total": 13,
                 "engine_lane_slots_total": 52}}
    # the whole-window counters still sum every label set
    assert harness._counters(session) == {
        "engine_kernel_steps_total": 27, "engine_lane_slots_total": 500,
        "engine_sources_total": 36, "engine_launches_total": 3,
        "engine_other_total": 7}
    before = {"bfs": {"engine_kernel_steps_total": 4}}
    assert harness._kernel_deltas(before, harness._kernel_counters(session))[
        "bfs"]["engine_kernel_steps_total"] == 10


def test_a_mix_reads_each_class_its_own_step_counters(tiny_root,
                                                      monkeypatch):
    seen = []
    monkeypatch.setattr(harness, "read_metrics",
                        lambda entries, ctx, bench_dir: seen.append(ctx)
                        or {})
    _run(tiny_root, add_mix(tiny_root))
    (ctx,) = seen
    steps = {k: c.counters.get("engine_kernel_steps_total", 0)
             for k, c in ctx.classes.items()}
    assert set(steps) == {"bfs", "sssp"} and all(steps.values())
    assert sum(steps.values()) == ctx.counters["engine_kernel_steps_total"]
    for kernel, cls in ctx.classes.items():
        assert cls.counters == ctx.kernel_counters[kernel]
