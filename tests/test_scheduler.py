"""Request plane: futures, micro-batch coalescing, dedup, ordering.

The contract under test is the tentpole of the scheduler redesign: any
interleaving of enqueued requests — mixed kernels, priorities, duplicate
global-kernel requests — must yield results bit-identical (allclose for
the float kernels bc/pr, whose launch shape can differ under coalescing)
to serving the same requests one at a time through the blocking
``submit``. The hypothesis property test generates those interleavings;
the 4-forced-device leg re-runs this whole module with the sharded
backend on a genuine mesh, like tests/test_parity_matrix.py.
"""
from __future__ import annotations

import os

import numpy as np
import pytest

from conftest import run_forced_four_devices
from repro.core.baselines import cc_baseline
from repro.engine import (AdmissionPolicy, AdmissionRejected,
                          DeadlineExceeded, EngineSession, ManualClock,
                          QueryFuture, ReorderPolicy,
                          canonical_component_labels, estimate_device_bytes)
from repro.engine.backends import source_bucket

FLOAT_KERNELS = ("pr", "bc")


def _session(**kw) -> EngineSession:
    kw.setdefault("redecide_min_queries", 10**6)
    return EngineSession(**kw)


def _assert_matches(kernel: str, got, want) -> None:
    got, want = np.asarray(got), np.asarray(want)
    if kernel in FLOAT_KERNELS:
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    else:
        np.testing.assert_array_equal(got, want)


# ------------------------------------------------------------ future basics
def test_enqueue_returns_pending_future(plc_graph):
    session = _session()
    gid = session.register(plc_graph, expected_queries=256)
    fut = session.enqueue(gid, "bfs", [0, 1])
    assert isinstance(fut, QueryFuture)
    assert not fut.done()
    assert session.scheduler.pending() == 1
    served = session.flush()
    assert served == 1 and fut.done() and session.scheduler.pending() == 0
    assert fut.result().shape == (2, plc_graph.num_vertices)


def test_result_flushes_owning_graph(plc_graph):
    """A lone enqueue().result() behaves exactly like blocking submit."""
    session = _session()
    gid = session.register(plc_graph, expected_queries=256)
    fut = session.enqueue(gid, "bfs", [3])
    out = fut.result()          # no explicit flush
    assert fut.done() and session.scheduler.pending() == 0
    _assert_matches("bfs", out, _session_submit_reference(plc_graph, "bfs",
                                                          [3]))


def _session_submit_reference(graph, kernel, sources):
    ref = _session()
    rid = ref.register(graph, expected_queries=256)
    return ref.submit(rid, kernel, sources)


def test_enqueue_validates_eagerly(plc_graph):
    session = _session()
    gid = session.register(plc_graph, expected_queries=256)
    with pytest.raises(ValueError):
        session.enqueue(gid, "nope", [0])
    with pytest.raises(ValueError):
        session.enqueue(gid, "bfs", [])
    with pytest.raises(KeyError):
        session.enqueue("unregistered", "bfs", [0])
    # out-of-range ids fail the offending caller at enqueue — at launch
    # time they would poison every request coalesced alongside
    with pytest.raises(ValueError, match="sources must be in"):
        session.enqueue(gid, "bfs", [plc_graph.num_vertices])
    with pytest.raises(ValueError, match="sources must be in"):
        session.enqueue(gid, "bfs", [-1])
    assert session.scheduler.pending() == 0
    assert session.scheduler.requests_enqueued == 0


# ------------------------------------------------------------- coalescing
def test_multi_source_requests_coalesce_into_one_launch(plc_graph):
    session = _session()
    gid = session.register(plc_graph, expected_queries=256)
    rng = np.random.default_rng(0)
    batches = [rng.integers(0, plc_graph.num_vertices, size=n)
               for n in (3, 1, 4, 2)]
    futs = [session.enqueue(gid, "bfs", b) for b in batches]
    before = session.executor.queries_run
    session.flush(gid)
    assert session.executor.queries_run - before == 1   # one device launch
    assert session.scheduler.launches == 1
    assert session.scheduler.coalesced_requests == len(batches)
    for fut, batch in zip(futs, batches):
        assert fut.telemetry["coalesced_with"] == len(batches) - 1
        assert fut.telemetry["launch_batch_sources"] == 10
        _assert_matches("bfs", fut.result(),
                        _session_submit_reference(plc_graph, "bfs", batch))


def test_coalesced_batch_fills_source_bucket(plc_graph):
    """The combined launch pads to one power-of-two bucket, not per-request
    buckets: 3+1+4+2 = 10 distinct sources ride a 16-slot bucket in one
    launch."""
    session = _session()
    gid = session.register(plc_graph, expected_queries=256)
    base = 0
    for n in (3, 1, 4, 2):
        session.enqueue(gid, "bfs", np.arange(base, base + n))
        base += n
    session.flush()
    keys = session.executor.single.telemetry()["cached_keys"]
    assert len(keys) == 1  # one compiled shape for the whole burst
    assert source_bucket(10) == 16


def test_max_batch_sources_chunks_in_order(plc_graph):
    session = _session(max_batch_sources=4)
    gid = session.register(plc_graph, expected_queries=256)
    futs = [session.enqueue(gid, "bfs", np.arange(i * 3, i * 3 + 3))
            for i in range(3)]
    session.flush()
    # greedy packs r0 (3 sources), r1 would exceed the cap of 4 -> new
    # chunk [r1], then [r2]: 3 launches of 3 sources each
    assert session.scheduler.launches == 3
    idx = [f.telemetry["launch_index"] for f in futs]
    assert idx == sorted(idx)  # FIFO within equal priority


def test_device_memory_caps_coalesced_batches(plc_graph, monkeypatch):
    """A burst coalesces only up to the batch the device has memory for.
    The CPU reports no memory limit, so there the burst is one launch;
    under a device cap of 4 the same 12 sources take 3 launches and
    every row is still its own source's answer."""
    from repro.core.baselines import bfs_baseline
    session = _session()
    gid = session.register(plc_graph, expected_queries=256)
    assert session._source_cap(session.registry.get(gid), "bfs") is None
    monkeypatch.setattr(session.executor.single, "source_cap",
                        lambda handle, kernel: 4)
    futs = [session.enqueue(gid, "bfs", [s]) for s in range(12)]
    session.flush()
    assert session.scheduler.launches == 3
    assert {f.telemetry["launch_batch_sources"] for f in futs} == {4}
    for s, f in enumerate(futs):
        np.testing.assert_array_equal(f.result()[0],
                                      bfs_baseline(plc_graph, s))


def test_global_requests_dedup_into_one_run(plc_graph):
    session = _session()
    gid = session.register(plc_graph, expected_queries=256)
    futs = [session.enqueue(gid, "pr") for _ in range(5)]
    before = session.executor.queries_run
    session.flush()
    assert session.executor.queries_run - before == 1
    assert session.scheduler.dedup_hits == 4
    outs = [np.asarray(f.result()) for f in futs]
    for out in outs[1:]:
        np.testing.assert_array_equal(outs[0], out)
    _assert_matches("pr", outs[0],
                    _session_submit_reference(plc_graph, "pr", None))


# ------------------------------------------------------ ordering semantics
def test_priority_orders_launches(plc_graph):
    session = _session()
    gid = session.register(plc_graph, expected_queries=256)
    low = session.enqueue(gid, "bfs", [0], priority=0)
    high = session.enqueue(gid, "sssp", [1], priority=10)
    session.flush()
    assert high.telemetry["launch_index"] < low.telemetry["launch_index"]


def test_deadline_orders_and_flags(plc_graph):
    session = _session()
    gid = session.register(plc_graph, expected_queries=256)
    relaxed = session.enqueue(gid, "bfs", [0], deadline_seconds=3600.0)
    urgent = session.enqueue(gid, "sssp", [1], deadline_seconds=0.0)
    none = session.enqueue(gid, "bc", [2])
    session.flush()
    # earliest absolute deadline first; no deadline sorts last
    assert (urgent.telemetry["launch_index"]
            < relaxed.telemetry["launch_index"]
            < none.telemetry["launch_index"])
    assert urgent.telemetry["deadline_missed"] is True  # 0 s budget
    assert relaxed.telemetry["deadline_missed"] is False
    assert session.scheduler.deadlines_missed == 1


# --------------------------------------------------- submit compatibility
def test_submit_is_enqueue_flush_sugar(plc_graph):
    session = _session()
    gid = session.register(plc_graph, expected_queries=256)
    out = session.submit(gid, "bfs", [0, 5])
    t = session.scheduler.telemetry()
    assert t["requests_served"] == 1 and t["launches"] == 1
    entry = session.registry.get(gid)
    assert entry.ledger.queries_served == 1
    assert entry.ledger.sources_served == 2
    assert entry.queries_observed == 1
    assert out.shape == (2, plc_graph.num_vertices)


def test_submit_serves_pending_futures_on_same_graph(plc_graph):
    session = _session()
    gid = session.register(plc_graph, expected_queries=256)
    queued = session.enqueue(gid, "bfs", [7])
    session.submit(gid, "bfs", [9])     # flush boundary serves both
    assert queued.done()


# -------------------------------------------------- component-label space
def test_component_labels_canonicalized_to_original_ids(plc_graph):
    """PR 4 leaked served-space label values; the session boundary now
    canonicalizes to min-original-id per component — bit-identical to the
    numpy baseline regardless of the reorder the policy picked."""
    session = _session()
    gid = session.register(plc_graph, expected_queries=256)
    entry = session.registry.get(gid)
    assert entry.decision.scheme != "original"  # a real reorder happened
    want = cc_baseline(plc_graph)
    for kernel in ("cc", "ccsv"):
        np.testing.assert_array_equal(session.submit(gid, kernel), want)


def test_canonical_component_labels_helper():
    labels = np.array([5, 5, 2, 2, 5])   # arbitrary representative space
    np.testing.assert_array_equal(canonical_component_labels(labels),
                                  np.array([0, 0, 2, 2, 0]))
    stacked = np.stack([labels, np.array([1, 0, 0, 3, 3])])
    got = canonical_component_labels(stacked)
    np.testing.assert_array_equal(got[0], [0, 0, 2, 2, 0])
    np.testing.assert_array_equal(got[1], [0, 1, 1, 3, 3])


# ----------------------------------------------------- generations / flush
def test_generation_bumps_on_redecision_and_stamps_futures(plc_graph):
    session = EngineSession(redecide_factor=2.0, redecide_min_queries=4)
    gid = session.register(plc_graph, expected_queries=1)  # volume-gated
    entry = session.registry.get(gid)
    assert entry.generation == 1
    assert entry.decision.scheme == "original"
    rng = np.random.default_rng(2)
    futs = []
    for _ in range(12):
        futs.append(session.enqueue(
            gid, "bfs", rng.integers(0, plc_graph.num_vertices, size=2)))
    session.drain()
    # the whole burst was one flush: every future served by generation 1,
    # the re-decision fired only at the flush boundary
    assert {f.telemetry["generation"] for f in futs} == {1}
    assert entry.generation > 1
    assert entry.decision.scheme != "original"
    assert session.redecision_log
    # post-re-decision requests are served by — and stamped with — the
    # new layout, and still answer in original vertex ids
    fut = session.enqueue(gid, "bfs", [3])
    _assert_matches("bfs", fut.result(),
                    _session_submit_reference(plc_graph, "bfs", [3]))
    assert fut.telemetry["generation"] == entry.generation


# -------------------------------------------------- placement v2 (S term)
def test_estimate_device_bytes_gains_batch_state_term():
    base = estimate_device_bytes(1000, 8000)
    assert estimate_device_bytes(1000, 8000, batch_sources=0) == base
    assert estimate_device_bytes(1000, 8000, batch_sources=16) == \
        base + 8 * 16 * 1000


def test_policy_observes_scheduler_batches(plc_graph):
    session = _session()
    gid = session.register(plc_graph, expected_queries=256)
    assert session.policy.batch_sources_hint == 1
    for i in range(3):
        session.enqueue(gid, "bfs", np.arange(i * 8, i * 8 + 8))
    session.flush()   # one coalesced 24-source launch observed
    assert session.policy.batches_observed == 1
    assert session.policy.batch_sources_hint == source_bucket(24)


def test_batch_state_tips_placement_to_sharded(plc_graph):
    """A graph whose CSR fits the budget but whose observed batch state
    does not must be re-placed sharded (ROADMAP placement v2)."""
    from repro.engine import probe_graph
    probes = probe_graph(plc_graph)
    from repro.engine.backends import bucket_dims
    v_b, e_b = bucket_dims(probes.num_vertices, probes.num_edges)
    # budget covers the bucketed CSR plus one query's state (the S=1
    # default before any batches are observed), with no room for more
    policy = ReorderPolicy(
        device_budget_bytes=estimate_device_bytes(v_b, e_b,
                                                  batch_sources=1) + 1)
    assert policy.decide(probes, 256).backend == "single"
    for _ in range(8):
        policy.observe_batch_sources(64)
    d = policy.decide(probes, 256)
    assert d.backend == "sharded"
    assert "query state" in d.reason


# -------------------------------------------- per-request exchange stats
def test_sharded_requests_carry_exchange_deltas(plc_graph):
    session = _session(device_budget_bytes=1024)
    gid = session.register(plc_graph, expected_queries=256)
    assert session.registry.get(gid).backend == "sharded"
    f1 = session.enqueue(gid, "bfs", [0, 1])
    f2 = session.enqueue(gid, "cc")
    session.flush()
    for f in (f1, f2):
        ex = f.telemetry["exchange"]
        assert ex is not None and ex["steps"] > 0
    # deltas are per run, not cumulative: the backend aggregate is the sum
    agg = session.executor.sharded.exchange_stats
    assert (f1.telemetry["exchange"]["steps"]
            + f2.telemetry["exchange"]["steps"]) == agg.steps
    # single-device requests carry no exchange block
    single = _session()
    sid = single.register(plc_graph, expected_queries=256)
    fut = single.enqueue(sid, "bfs", [0])
    single.flush()
    assert fut.telemetry["exchange"] is None


# ------------------------------------------------- interleaving property
KERNELS = ("bfs", "sssp", "bc", "pr", "cc", "ccsv")


def _run_interleaving(graph, specs, session_factory=None):
    """Serve ``specs`` batched (enqueue-all + drain) and sequentially
    (fresh session, per-request submit); assert per-request parity."""
    session_factory = session_factory or _session
    batched = session_factory()
    sequential = session_factory()
    bid = batched.register(graph, graph_id="b", expected_queries=256)
    sid = sequential.register(graph, graph_id="s", expected_queries=256)
    futs = [batched.enqueue(bid, k, srcs, priority=pr)
            for k, srcs, pr in specs]
    batched.drain()
    for fut, (kernel, srcs, _) in zip(futs, specs):
        _assert_matches(kernel, fut.result(),
                        sequential.submit(sid, kernel, srcs))
    # unbounded coalescing in one flush: exactly one launch per distinct
    # kernel, however many requests rode it
    assert batched.scheduler.launches == len({k for k, _, _ in specs})


@pytest.mark.parametrize("config", ["exact", "bucketed", "sharded"])
def test_mixed_kernel_interleaving_matches_sequential(plc_graph, config):
    """Coalescing parity across every serving config: batched enqueue +
    drain vs per-request submit, all six kernels in one interleaving."""
    rng = np.random.default_rng(7)
    specs = []
    for i in range(12):
        kernel = KERNELS[i % len(KERNELS)]
        srcs = (rng.integers(0, plc_graph.num_vertices, size=1 + i % 3)
                if kernel in ("bfs", "sssp", "bc") else None)
        specs.append((kernel, srcs, int(rng.integers(0, 3))))
    if config == "exact":
        from repro.engine import BatchedExecutor

        def factory():
            return _session(executor=BatchedExecutor(bucketing=False))
    elif config == "sharded":
        def factory():
            return _session(device_budget_bytes=1024)
    else:
        factory = _session
    _run_interleaving(plc_graph, specs, session_factory=factory)


def test_interleaving_property_random(tiny_graph):
    """Hypothesis: any interleaving of requests — kernels, priorities,
    duplicate globals — is bit-identical to sequential submit."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    n = tiny_graph.num_vertices
    spec = st.tuples(
        st.sampled_from(KERNELS),
        st.lists(st.integers(min_value=0, max_value=n - 1),
                 min_size=1, max_size=4),
        st.integers(min_value=-2, max_value=2),
    )

    @settings(max_examples=10, deadline=None)
    @given(specs=st.lists(spec, min_size=1, max_size=8))
    def check(specs):
        prepared = [(k, np.asarray(srcs) if k in ("bfs", "sssp", "bc")
                     else None, pr) for k, srcs, pr in specs]
        _run_interleaving(tiny_graph, prepared)

    check()


def test_interleaving_sharded(plc_graph):
    """Same contract when the graph is served sharded (1 shard in the
    plain suite; a real mesh under the 4-device leg below)."""
    rng = np.random.default_rng(11)
    specs = [("bfs", rng.integers(0, plc_graph.num_vertices, 2), 1),
             ("sssp", rng.integers(0, plc_graph.num_vertices, 3), 0),
             ("cc", None, 0), ("ccsv", None, 2), ("pr", None, 0),
             ("bc", rng.integers(0, plc_graph.num_vertices, 2), 0)]
    _run_interleaving(plc_graph, specs,
                      session_factory=lambda: _session(
                          device_budget_bytes=1024))


# ------------------------------------------------------------ result cache
def test_result_cache_serves_across_flush_windows(plc_graph):
    """A repeat of already-served sources costs no launch: rows come out
    of the (graph, generation, kernel, source) cache, bit-identical and
    order-correct, and the serve is visible as a cache_hit span."""
    session = _session()
    gid = session.register(plc_graph, expected_queries=256)
    f1 = session.enqueue(gid, "bfs", [0, 1, 2])
    session.flush()
    assert session.scheduler.launches == 1
    f2 = session.enqueue(gid, "bfs", [2, 1, 0])
    session.flush()
    assert session.scheduler.launches == 1          # no second launch
    assert f2.telemetry["served_from_cache"] is True
    assert f2.telemetry["cache_hit_sources"] == 3
    assert f2.telemetry["launch_batch_sources"] == 0
    np.testing.assert_array_equal(np.asarray(f2.result()),
                                  np.asarray(f1.result())[[2, 1, 0]])
    assert session.result_cache.hits >= 3
    names = {e["name"] for e in session.tracer.to_chrome()["traceEvents"]}
    assert "cache_hit" in names


def test_result_cache_partial_hit_launches_only_missing(plc_graph):
    session = _session()
    gid = session.register(plc_graph, expected_queries=256)
    session.enqueue(gid, "bfs", [4, 5])
    session.flush()
    f = session.enqueue(gid, "bfs", [5, 6])        # 5 cached, 6 fresh
    session.flush()
    assert session.scheduler.launches == 2
    assert f.telemetry["launch_batch_sources"] == 1  # only source 6 launched
    assert f.telemetry["cache_hit_sources"] == 1
    assert f.telemetry["served_from_cache"] is False
    _assert_matches("bfs", f.result(),
                    _session_submit_reference(plc_graph, "bfs", [5, 6]))


def test_within_window_duplicate_sources_dedup(plc_graph):
    """Two requests asking the same sources in one flush share one launch
    of the *unique* sources — the within-window form of the cache."""
    session = _session()
    gid = session.register(plc_graph, expected_queries=256)
    f1 = session.enqueue(gid, "bfs", [0, 1])
    f2 = session.enqueue(gid, "bfs", [1, 0])
    session.flush()
    assert session.scheduler.launches == 1
    assert f1.telemetry["launch_batch_sources"] == 2   # unique, not 4
    np.testing.assert_array_equal(np.asarray(f1.result())[[1, 0]],
                                  np.asarray(f2.result()))
    _assert_matches("bfs", f1.result(),
                    _session_submit_reference(plc_graph, "bfs", [0, 1]))


def test_global_kernels_cache_across_windows(plc_graph):
    session = _session()
    gid = session.register(plc_graph, expected_queries=256)
    p1 = session.submit(gid, "pr")
    before = session.executor.queries_run
    p2 = session.submit(gid, "pr")                  # across flush windows
    assert session.executor.queries_run == before   # zero device work
    np.testing.assert_array_equal(p1, p2)


def test_result_cache_disabled_matches_legacy_plane(plc_graph):
    """``result_cache=False`` restores the PR 5 coalescing exactly:
    duplicate sources ride the launch and repeats re-launch."""
    session = _session(result_cache=False)
    assert session.result_cache is None
    gid = session.register(plc_graph, expected_queries=256)
    f1 = session.enqueue(gid, "bfs", [0, 1])
    f2 = session.enqueue(gid, "bfs", [1, 0])
    session.flush()
    assert session.scheduler.launches == 1
    assert f1.telemetry["launch_batch_sources"] == 4   # dupes included
    session.enqueue(gid, "bfs", [0, 1])
    session.flush()
    assert session.scheduler.launches == 2             # repeat re-launches
    assert session.scheduler.telemetry()["result_cache"] is None
    _assert_matches("bfs", f2.result(),
                    _session_submit_reference(plc_graph, "bfs", [1, 0]))


# ------------------------------------------------------- multi-graph fairness
def test_round_robin_across_graphs_with_chunking(plc_graph, tiny_graph):
    """With max_batch_sources chunking, launches alternate between graphs
    instead of one graph's burst monopolizing consecutive launches."""
    session = _session(max_batch_sources=2, result_cache=False)
    g1 = session.register(plc_graph, graph_id="g1", expected_queries=256)
    g2 = session.register(tiny_graph, graph_id="g2", expected_queries=256)
    futs1 = [session.enqueue(g1, "bfs", [i]) for i in range(4)]
    futs2 = [session.enqueue(g2, "bfs", [i]) for i in range(4)]
    session.flush()
    idx1 = sorted({f.telemetry["launch_index"] for f in futs1})
    idx2 = sorted({f.telemetry["launch_index"] for f in futs2})
    # two chunks per graph, interleaved: g1 -> {1, 3}, g2 -> {2, 4} (not
    # g1 taking 1-2 and starving g2 until 3-4)
    assert idx1 == [1, 3] and idx2 == [2, 4]


def test_flush_rotation_changes_leading_graph(plc_graph, tiny_graph):
    """The graph that leads a multi-graph flush rotates between flushes,
    so repeated bursts don't always pay graph-order latency to the same
    victim."""
    session = _session(result_cache=False)
    g1 = session.register(plc_graph, graph_id="g1", expected_queries=256)
    g2 = session.register(tiny_graph, graph_id="g2", expected_queries=256)

    def burst():
        f1 = session.enqueue(g1, "bfs", [0])
        f2 = session.enqueue(g2, "bfs", [0])
        session.flush()
        return (f1.telemetry["launch_index"], f2.telemetry["launch_index"])

    a1, b1 = burst()
    a2, b2 = burst()
    assert (a1 < b1) != (a2 < b2)    # lead alternates across flushes


# ------------------------------------------------------ auto-flush / polling
def test_poll_flushes_overdue_requests_on_enqueue(plc_graph):
    clock = ManualClock()
    session = _session(clock=clock, max_delay=0.1)
    gid = session.register(plc_graph, expected_queries=256)
    f1 = session.enqueue(gid, "bfs", [0])
    assert not f1._done
    clock.advance(0.2)               # f1 is now older than max_delay
    f2 = session.enqueue(gid, "bfs", [1])   # piggy-backed poll fires
    assert f1._done and f2._done
    assert session.scheduler.auto_flushes == 1


def test_done_polls_the_scheduler(plc_graph):
    clock = ManualClock()
    session = _session(clock=clock, max_delay=0.1)
    gid = session.register(plc_graph, expected_queries=256)
    fut = session.enqueue(gid, "bfs", [0])
    assert not fut.done()            # not overdue yet: still pending
    clock.advance(0.2)
    assert fut.done()                # done() ticked the auto-flush
    assert session.scheduler.auto_flushes == 1


def test_deadline_triggers_poll_before_max_delay(plc_graph):
    clock = ManualClock()
    session = _session(clock=clock, max_delay=60.0)
    gid = session.register(plc_graph, expected_queries=256)
    fut = session.enqueue(gid, "bfs", [0], deadline_seconds=0.05)
    clock.advance(0.06)              # way below max_delay, past deadline
    assert session.poll() == 1 and fut._done
    assert fut.telemetry["deadline_missed"] is True


def test_background_auto_flush_thread(plc_graph):
    import time
    session = _session(max_delay=0.05, auto_flush_interval=0.02)
    gid = session.register(plc_graph, expected_queries=256)
    fut = session.enqueue(gid, "bfs", [0])
    deadline = time.monotonic() + 10.0
    while not fut._done and time.monotonic() < deadline:
        time.sleep(0.01)             # no flush()/poll()/done() calls here
    assert fut._done, "background thread never served the request"
    assert session.scheduler.auto_flushes >= 1
    assert session.scheduler.auto_flush_error is None
    session.close()
    assert session.scheduler._flusher is None


# --------------------------------------------------- deadlines / admission
def test_result_raises_deadline_exceeded_when_expired(plc_graph):
    clock = ManualClock()
    session = _session(clock=clock, max_delay=None)
    gid = session.register(plc_graph, expected_queries=256)
    fut = session.enqueue(gid, "bfs", [0], deadline_seconds=0.5)
    clock.advance(1.0)
    with pytest.raises(DeadlineExceeded):
        fut.result()
    assert fut.exception() is not None
    assert session.scheduler.pending() == 0      # removed from the queue
    assert session.scheduler.requests_expired == 1
    assert session.scheduler.deadlines_missed == 1
    assert session.scheduler.requests_failed == 1
    assert session.scheduler.launches == 0       # no wasted device work


def test_admission_rejects_at_queue_cap(plc_graph):
    session = _session(admission=AdmissionPolicy(max_pending=2),
                       max_delay=None)
    gid = session.register(plc_graph, expected_queries=256)
    futs = [session.enqueue(gid, "bfs", [i]) for i in range(2)]
    with pytest.raises(AdmissionRejected) as exc_info:
        session.enqueue(gid, "bfs", [9])
    assert exc_info.value.pending == 2 and not exc_info.value.shed
    assert session.scheduler.admission_rejected == 1
    assert session.scheduler.requests_enqueued == 2
    session.drain()
    assert all(f.done() for f in futs)           # admitted traffic unharmed


def test_admission_degrades_to_best_effort(plc_graph):
    session = _session(
        admission=AdmissionPolicy(max_pending=1, overload="degrade"),
        max_delay=None)
    gid = session.register(plc_graph, expected_queries=256)
    first = session.enqueue(gid, "bfs", [0], priority=5)
    over = session.enqueue(gid, "bfs", [1], priority=5, deadline_seconds=9.0)
    assert over.request.degraded
    assert over.request.priority == -1 and over.request.deadline is None
    assert session.scheduler.admission_degraded == 1
    session.flush()
    # degraded request drains after the fully admitted one
    assert first.telemetry["launch_index"] <= over.telemetry["launch_index"]
    assert over.telemetry["degraded"] is True


def test_admission_sheds_best_effort_under_missed_deadlines(plc_graph):
    clock = ManualClock()
    adm = AdmissionPolicy(max_pending=8, soft_fraction=0.25,
                          shed_miss_rate=0.5, min_miss_samples=4)
    session = _session(clock=clock, admission=adm, max_delay=None)
    gid = session.register(plc_graph, expected_queries=256)
    # miss a batch of deadlines to arm the shed window
    for i in range(4):
        session.enqueue(gid, "bfs", [i], deadline_seconds=0.01)
    clock.advance(1.0)
    session.flush()
    assert session.scheduler.deadlines_missed == 4
    # queue depth at the soft limit + hot miss window: best-effort sheds,
    # deadline-carrying traffic still gets in
    keep = [session.enqueue(gid, "bfs", [i], deadline_seconds=30.0)
            for i in range(10, 12)]
    with pytest.raises(AdmissionRejected) as exc_info:
        session.enqueue(gid, "bfs", [20])        # best-effort arrival
    assert exc_info.value.shed
    assert session.scheduler.admission_shed == 1
    urgent = session.enqueue(gid, "bfs", [21], deadline_seconds=30.0)
    session.drain()
    assert urgent.done() and all(f.done() for f in keep)


def test_scheduler_four_forced_devices():
    """Re-run this module on 4 forced host devices, so the sharded
    interleavings exercise a genuine mesh (same recipe as the parity
    matrix's distributed leg)."""
    res = run_forced_four_devices(
        ["-m", "pytest", "-q", os.path.abspath(__file__),
         "-k", "not four_forced"], timeout=900)
    assert res.returncode == 0, \
        f"stdout={res.stdout[-4000:]}\nstderr={res.stderr[-2000:]}"
