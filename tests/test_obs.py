"""Observability plane: registry mechanics, deterministic latency math,
trace export validation, failure counters, and the telemetry facades.

Latency/deadline tests advance a `ManualClock` instead of sleeping, so
the asserted numbers are exact, not approximate.
"""
import contextlib
import json

import numpy as np
import pytest

from repro.core.generators import powerlaw_community
from repro.engine import (EngineSession, ManualClock, MetricsRegistry,
                          ProfilerHook, SingleDeviceBackend, Tracer,
                          validate_chrome_trace)
from repro.engine.obs import (Histogram, log_boundaries,
                              merge_histogram_snapshots,
                              signed_log_boundaries)

HIST_SNAPSHOT_KEYS = {"count", "sum", "min", "max", "p50", "p90", "p99",
                      "boundaries", "bucket_counts"}
SCHEDULER_TELEMETRY_KEYS = [
    "requests_enqueued", "requests_served", "pending", "launches",
    "coalesced_requests", "dedup_hits", "flushes", "deadlines_missed",
    "launches_failed", "requests_failed", "max_batch_sources",
    "max_delay", "auto_flushes", "requests_expired", "admission",
    "admission_rejected", "admission_degraded", "admission_shed",
    "deadline_miss_rate", "result_cache"]


@pytest.fixture(scope="module")
def obs_graph():
    return powerlaw_community(600, avg_degree=8.0, seed=11, name="obsg")


# ---------------------------------------------------------------- registry
def test_counter_and_gauge_mechanics():
    m = MetricsRegistry()
    c = m.counter("hits_total", "help text")
    c.inc()
    c.inc(3)
    assert c.value == 4
    with pytest.raises(ValueError):
        c.inc(-1)
    assert m.counter("hits_total") is c        # same (name, labels) = same
    assert m.counter("hits_total", x="1") is not c
    g = m.gauge("pending")
    g.inc(5)
    g.dec(2)
    assert g.value == 3
    g.set(0)
    assert g.value == 0
    with pytest.raises(ValueError):            # kind drift must be loud
        m.gauge("hits_total")


def test_histogram_observe_and_quantiles():
    h = Histogram("lat", boundaries=log_boundaries(1e-3, 1.0))
    for v in (0.002, 0.002, 0.004, 0.5):
        h.observe(v)
    snap = h.snapshot()
    assert snap["count"] == 4
    assert snap["sum"] == pytest.approx(0.508)
    assert snap["min"] == 0.002 and snap["max"] == 0.5
    assert 0.001 <= snap["p50"] <= 0.008       # within the winning buckets
    assert snap["p99"] <= 0.5
    with pytest.raises(ValueError):
        h.quantile(1.5)
    empty = Histogram("e").snapshot()
    assert empty["count"] == 0 and empty["p50"] is None


def test_merge_histogram_snapshots():
    a = Histogram("x", boundaries=(1.0, 2.0))
    b = Histogram("x", boundaries=(1.0, 2.0))
    a.observe(0.5)
    b.observe(1.5)
    b.observe(10.0)
    merged = merge_histogram_snapshots([a.snapshot(), b.snapshot()])
    assert merged["count"] == 3
    assert merged["min"] == 0.5 and merged["max"] == 10.0
    other = Histogram("y", boundaries=(5.0,)).snapshot()
    with pytest.raises(ValueError):
        merge_histogram_snapshots([a.snapshot(), other])


def test_signed_log_boundaries_mirrored():
    b = signed_log_boundaries(1e-3, 8.0)
    assert list(b) == sorted(b)
    assert 0.0 in b
    assert b[0] == -b[-1]


def test_snapshot_and_prometheus_shapes():
    m = MetricsRegistry()
    m.counter("jobs_total").inc(2)
    m.counter("served_total", graph="g1", kernel="bfs").inc()
    m.histogram("wait_seconds", kernel="bfs").observe(0.25)
    snap = m.snapshot()
    assert set(snap) == {"counters", "gauges", "histograms"}
    assert snap["counters"]["jobs_total"] == 2          # unlabelled: bare
    assert snap["counters"]["served_total"] == {"graph=g1,kernel=bfs": 1}
    hist = snap["histograms"]["wait_seconds"]["kernel=bfs"]
    assert set(hist) == HIST_SNAPSHOT_KEYS
    json.dumps(snap, allow_nan=False)                   # strict-JSON safe
    text = m.to_prometheus()
    assert "# TYPE jobs_total counter" in text
    assert "# TYPE wait_seconds histogram" in text
    assert 'served_total{graph="g1",kernel="bfs"} 1' in text
    assert 'wait_seconds_bucket{kernel="bfs",le="+Inf"} 1' in text
    assert 'wait_seconds_count{kernel="bfs"} 1' in text


def test_manual_clock_is_monotonic():
    clk = ManualClock()
    assert clk.now() == 0.0
    clk.advance(1.5)
    assert clk.now() == 1.5
    with pytest.raises(ValueError):
        clk.advance(-1)


# ------------------------------------------------------------------ tracer
def test_tracer_spans_nest_and_export(tmp_path):
    clk = ManualClock()
    tr = Tracer(clock=clk)
    with tr.span("outer", graph_id="g"):
        clk.advance(0.5)
        with tr.span("inner") as args:
            clk.advance(0.25)
            args["fact"] = "learned-inside"
    tr.instant("tick", note="hi")
    p = tr.export(tmp_path / "trace.json")
    trace = json.loads(p.read_text())
    stats = validate_chrome_trace(trace)
    assert stats["complete_spans"] == 2
    assert stats["span_names"] == ["inner", "outer"]
    inner = next(e for e in trace["traceEvents"] if e["name"] == "inner")
    assert inner["args"]["fact"] == "learned-inside"
    assert inner["dur"] == pytest.approx(0.25e6)        # µs
    assert trace["otherData"]["dropped_events"] == 0


def test_tracer_bounded_buffer_counts_drops():
    tr = Tracer(clock=ManualClock(), max_events=2)
    for i in range(5):
        tr.instant(f"e{i}")
    assert len(tr.events) == 2
    assert tr.dropped == 3
    assert tr.to_chrome()["otherData"]["dropped_events"] == 3


def test_validate_rejects_overlapping_spans():
    tr = Tracer(clock=ManualClock())
    tr.emit("a", 0.0, 2.0)
    tr.emit("b", 1.0, 3.0)        # overlaps a without nesting
    with pytest.raises(AssertionError):
        validate_chrome_trace(tr.to_chrome())


# --------------------------------------------- deterministic latency math
def test_queue_wait_and_serve_histograms_exact(obs_graph):
    clk = ManualClock()
    session = EngineSession(clock=clk)
    gid = session.register(obs_graph, "g")
    fut = session.enqueue(gid, "bfs", [0, 1])
    clk.advance(0.5)                       # the request waits half a second
    session.flush()
    assert fut.telemetry["queue_seconds"] == pytest.approx(0.5)
    fam = session.metrics().family("engine_queue_wait_seconds")
    hist = fam["graph_id=g,kernel=bfs"]
    assert hist.count == 1
    assert hist.min == pytest.approx(0.5) and hist.max == pytest.approx(0.5)
    serve = session.metrics().family("engine_serve_seconds")
    assert serve["graph_id=g,kernel=bfs"].min == pytest.approx(0.5)


def test_deadline_slack_histogram_exact(obs_graph):
    clk = ManualClock()
    session = EngineSession(clock=clk)
    gid = session.register(obs_graph, "g")
    missed = session.enqueue(gid, "bfs", [0], deadline_seconds=0.2)
    met = session.enqueue(gid, "bfs", [1], deadline_seconds=2.0)
    clk.advance(0.5)
    session.flush()
    assert session.scheduler.deadlines_missed == 1
    assert missed.telemetry["deadline_missed"] is True
    assert met.telemetry["deadline_missed"] is False
    fam = session.metrics().family("engine_deadline_slack_seconds")
    slack = fam["graph_id=g,kernel=bfs"]
    assert slack.count == 2
    assert slack.min == pytest.approx(-0.3)     # missed by 0.3s
    assert slack.max == pytest.approx(1.5)      # met with 1.5s of room


# ------------------------------------------------------- failure counting
def test_launch_failure_counters_and_recovery(obs_graph):
    session = EngineSession(clock=ManualClock())
    gid = session.register(obs_graph, "g")
    real_launch = session._launch

    def boom(entry, kernel, sources):
        raise RuntimeError("device on fire")

    session._launch = boom
    f1 = session.enqueue(gid, "bfs", [0])
    f2 = session.enqueue(gid, "bfs", [1])
    with pytest.raises(RuntimeError, match="device on fire"):
        session.flush()
    assert f1.done() and f2.done()
    assert isinstance(f1.exception(), RuntimeError)
    with pytest.raises(RuntimeError, match="device on fire"):
        f2.result()
    t = session.scheduler.telemetry()
    assert t["launches_failed"] == 1      # one coalesced launch raised...
    assert t["requests_failed"] == 2      # ...failing both riders
    assert t["requests_served"] == 0
    assert t["pending"] == 0              # nothing stranded in the queues
    session._launch = real_launch         # the session serves again
    out = session.submit(gid, "bfs", [0])
    assert out.shape == (1, obs_graph.num_vertices)
    assert session.scheduler.telemetry()["requests_served"] == 1


# ------------------------------------------------- end-to-end trace + burst
def test_burst_trace_and_histogram_counts(obs_graph, tmp_path):
    session = EngineSession()
    gid = session.register(obs_graph, "g")
    rng = np.random.default_rng(3)
    kernels = ("bfs", "sssp", "bc", "pr", "cc", "ccsv")
    futs = []
    for i in range(64):
        k = kernels[i % len(kernels)]
        srcs = (rng.integers(0, obs_graph.num_vertices, size=2)
                if k in ("bfs", "sssp", "bc") else None)
        futs.append(session.enqueue(gid, k, srcs))
    session.drain()
    for f in futs:
        np.asarray(f.result())

    snap = session.metrics().snapshot()
    for name in ("engine_queue_wait_seconds", "engine_serve_seconds"):
        per_label = snap["histograms"][name]
        assert sum(s["count"] for s in per_label.values()) == 64
        merged = merge_histogram_snapshots(list(per_label.values()))
        assert merged["p50"] is not None and merged["p99"] >= merged["p50"]
    assert snap["counters"]["engine_requests_served_total"] == 64

    p = session.tracer.export(tmp_path / "burst_trace.json")
    trace = json.loads(p.read_text())
    stats = validate_chrome_trace(trace)
    for must in ("flush", "coalesce", "translate", "launch", "device_sync",
                 "queue_wait", "serve", "reorder", "register"):
        assert must in stats["span_names"], must
    served = {e["args"]["trace_id"] for e in trace["traceEvents"]
              if e.get("ph") == "X" and e["name"] == "serve"}
    assert served == {f.trace_id for f in futs}   # every future is traced
    assert all(f.trace_id == f.telemetry["trace_id"] for f in futs)


def test_launch_span_marks_compile_then_cache_hit(obs_graph):
    session = EngineSession()
    gid = session.register(obs_graph, "g")
    session.submit(gid, "bfs", [0])
    session.submit(gid, "bfs", [1])       # same shape: second is a hit
    launches = [e for e in session.tracer.to_chrome()["traceEvents"]
                if e.get("ph") == "X" and e["name"] == "launch"]
    assert [e["args"]["compile"] for e in launches] == \
        ["compile", "cache_hit"]


def test_sharded_run_emits_exchange_spans(obs_graph):
    session = EngineSession(device_budget_bytes=1024)   # force sharded
    gid = session.register(obs_graph, "g")
    entry = session.registry.get(gid)
    assert entry.backend == "sharded"
    fut = session.enqueue(gid, "bfs", [0, 1])
    session.flush()
    np.asarray(fut.result())
    assert fut.telemetry["exchange"] is not None
    trace = session.tracer.to_chrome()
    validate_chrome_trace(trace)
    exchanges = [e for e in trace["traceEvents"]
                 if e.get("ph") == "X" and e["name"] == "exchange"]
    assert len(exchanges) >= 1            # one span per traversal step
    launch = next(e for e in trace["traceEvents"]
                  if e.get("ph") == "X" and e["name"] == "launch")
    lo, hi = launch["ts"], launch["ts"] + launch["dur"]
    for ex in exchanges:                  # nested inside their launch
        assert lo - 1e-2 <= ex["ts"] <= ex["ts"] + ex["dur"] <= hi + 1e-2
        assert ex["args"]["mode"] in ("full", "hot")
    snap = session.metrics().snapshot()
    assert snap["counters"]["engine_exchange_steps_total"] == len(exchanges)


# ------------------------------------------------------------ golden schema
def test_scheduler_telemetry_golden_schema(obs_graph):
    session = EngineSession()
    gid = session.register(obs_graph, "g")
    session.submit(gid, "bfs", [0])
    t = session.scheduler.telemetry()
    assert list(t) == SCHEDULER_TELEMETRY_KEYS
    assert t["admission"] is None          # none configured by default
    assert set(t["result_cache"]) == {"entries", "pinned", "max_entries",
                                      "bytes", "max_bytes", "max_age_s",
                                      "hits", "misses", "evictions",
                                      "expired", "hit_rate"}
    top = session.telemetry()
    assert set(top) == {"executor", "scheduler", "policy", "calibration",
                        "redecisions", "mutations", "graphs"}
    assert set(top["mutations"]) == {"mutations", "edges_added",
                                     "edges_removed", "patch_reorders",
                                     "layout_swaps",
                                     "layout_swaps_discarded",
                                     "pending_swaps"}
    led = top["graphs"]["g"]["ledger"]
    assert "break_even_never" in led
    assert led["break_even_queries"] is None or \
        isinstance(led["break_even_queries"], float)
    json.dumps(top, allow_nan=False, default=float)     # strict-JSON safe

    snap = session.metrics().snapshot()
    for name in ("engine_requests_enqueued_total",
                 "engine_requests_served_total", "engine_launches_total",
                 "engine_flushes_total", "engine_graphs_registered_total",
                 "engine_reorders_total", "engine_queries_total",
                 "engine_compile_cache_misses_total",
                 "engine_auto_flushes_total",
                 "engine_requests_expired_total",
                 "engine_admission_rejected_total",
                 "engine_admission_degraded_total",
                 "engine_admission_shed_total",
                 "engine_result_cache_hits_total",
                 "engine_result_cache_misses_total",
                 "engine_result_cache_evictions_total"):
        assert name in snap["counters"], name
    assert "engine_pending_requests" in snap["gauges"]
    assert "engine_result_cache_entries" in snap["gauges"]
    assert "engine_result_cache_pinned" in snap["gauges"]
    for name in ("engine_queue_wait_seconds", "engine_serve_seconds",
                 "engine_launch_wall_seconds", "engine_reorder_seconds"):
        assert name in snap["histograms"], name
        for child in snap["histograms"][name].values():
            assert set(child) == HIST_SNAPSHOT_KEYS


def test_registry_adoption_chain(obs_graph):
    session = EngineSession()
    assert session.metrics() is session.executor.metrics
    assert session.metrics() is session.executor.single.metrics
    gid = session.register(obs_graph, "g")
    session.submit(gid, "bfs", [0])
    # backend-side counters land in the session's namespace
    assert session.metrics().snapshot()["counters"][
        "engine_queries_total"] == {"backend=single": 1}
    standalone = SingleDeviceBackend()    # built alone: private registry
    assert standalone.metrics is not session.metrics()


# ---------------------------------------------------------------- profiler
def test_profiler_hook_inert_without_log_dir(monkeypatch, obs_graph):
    hook = ProfilerHook(None)
    assert hook.enabled is False
    assert hook.start() is False
    assert hook.active is False
    assert hook.stop() is False
    import jax
    entered = []

    def annotation(name, **kw):          # any mirrored span lands here
        entered.append(name)
        return contextlib.nullcontext()

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", annotation)
    monkeypatch.setattr(jax.profiler, "StepTraceAnnotation", annotation)
    session = EngineSession()
    assert session.start_profiler() is False
    gid = session.register(obs_graph, "g")
    session.submit(gid, "bfs", [0, 1])
    names = {e["name"] for e in session.tracer.events}
    assert {"launch", "device_sync", "d2h", "unpermute",
            "cache_fill"} <= names            # the spans still record...
    assert entered == []                      # ...but none is mirrored


def _host_spans(log_dir, names) -> list[tuple[str, float, float]]:
    """(name, start_ns, end_ns) of the named events on the host plane of
    the profiler trace written under ``log_dir``."""
    import pathlib

    import jax
    path = sorted(pathlib.Path(log_dir).rglob("*.xplane.pb"))[-1]
    data = jax.profiler.ProfileData.from_file(str(path))
    return sorted((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                  for plane in data.planes
                  if plane.name.startswith("/host:")
                  for line in plane.lines for ev in line.events
                  if ev.name in names)


def test_active_profiler_mirrors_spans_on_the_host_plane(obs_graph,
                                                        tmp_path):
    session = EngineSession(profiler_dir=str(tmp_path / "prof"))
    gid = session.register(obs_graph, "g")
    session.submit(gid, "bfs", [0])          # compile outside the trace
    assert session.start_profiler(), session.profiler.error
    session.submit(gid, "bfs", [1, 2])
    assert session.stop_profiler() and session.profiler.error is None
    names = ("launch", "device_sync", "d2h", "unpermute", "cache_fill",
             "slice_out")
    got = {n: [(s, e) for m, s, e in _host_spans(tmp_path / "prof", names)
               if m == n] for n in names}
    assert all(len(got[n]) == 1 for n in names), got
    (launch,), (sync,), (d2h,) = got["launch"], got["device_sync"], got["d2h"]
    (unperm,), (fill,), (out,) = (got["unpermute"], got["cache_fill"],
                                  got["slice_out"])
    # nested as the engine track nests them:
    # launch ⊃ device_sync, d2h → unpermute → cache_fill → slice_out
    assert launch[0] <= sync[0] <= sync[1] <= d2h[0] <= d2h[1] <= launch[1]
    assert launch[1] <= unperm[0] <= unperm[1] <= fill[0]
    assert fill[1] <= out[0]
    # the tracer's own spans nest the same way
    track = {e["name"]: e for e in session.tracer.events
             if e["ph"] == "X" and e["name"] in names
             and e["args"].get("kernel") == "bfs"}
    assert track["launch"]["args"]["steps"] >= 1
    validate_chrome_trace(session.tracer.to_chrome())


def test_launch_is_the_profiler_step(monkeypatch, obs_graph):
    import jax
    steps = []

    class Step(contextlib.nullcontext):
        def __init__(self, name, step_num=None, **kw):
            steps.append((name, step_num))
            super().__init__()

    session = EngineSession(profiler_dir="unused")
    session.profiler.active = True           # as if start() had run
    monkeypatch.setattr(jax.profiler, "StepTraceAnnotation", Step)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                        lambda name, **kw: contextlib.nullcontext())
    gid = session.register(obs_graph, "g")
    session.submit(gid, "bfs", [0])
    session.submit(gid, "sssp", [0])
    assert steps == [("launch", 0), ("launch", 1)]   # numbered by launch


def test_profiler_hook_records_errors_not_raises(monkeypatch, tmp_path):
    hook = ProfilerHook(str(tmp_path / "prof"))
    assert hook.enabled is True
    import jax

    def blow_up(*a, **k):
        raise RuntimeError("profiler unavailable")

    monkeypatch.setattr(jax.profiler, "start_trace", blow_up)
    assert hook.start() is False          # swallowed, not raised
    assert "profiler unavailable" in hook.error
