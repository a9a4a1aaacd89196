"""Per-launch step and lane counters: the served BFS and SSSP programs
return each lane's loop trip count beside the rows, and the
single-device backend counts them exactly."""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.algos import kernels as K
from repro.algos.graph_arrays import to_device
from repro.core.csr import from_edges
from repro.engine import EngineSession, SingleDeviceBackend

COUNTERS = ("engine_kernel_steps_total", "engine_lane_steps_total",
            "engine_lane_slots_total")


def _paths(*lengths):
    """Disjoint undirected paths of ``lengths`` vertices each; returns
    the graph and the first vertex of every path."""
    src, dst, firsts, base = [], [], [], 0
    for n in lengths:
        firsts.append(base)
        src += list(range(base, base + n - 1))
        dst += list(range(base + 1, base + n))
        base += n
    return from_edges(base, src + dst, dst + src, name="paths"), firsts


def _counts(backend, kernel):
    snap = backend.metrics.snapshot()["counters"]
    return tuple(snap.get(name, {}).get(f"kernel={kernel}", 0)
                 for name in COUNTERS)


@pytest.mark.parametrize("kernel", ["bfs", "sssp"])
def test_path_graph_trip_count(kernel):
    # from one end of a 9-vertex path: 8 levels (or 8 rounds that each
    # settle one more vertex), plus the step that finds nothing new
    graph, (end,) = _paths(9)
    backend = SingleDeviceBackend()
    handle = backend.prepare(graph)
    backend.run(handle, kernel, [end])
    assert _counts(backend, kernel) == (9, 9, 9)
    assert backend.last_run_steps == {"steps": 9, "lanes": 1}


@pytest.mark.parametrize("kernel", ["bfs", "sssp"])
def test_lane_occupancy_of_unequal_lanes(kernel):
    # lanes of depth 5 and 2: 6 and 3 steps of the 6 x 2 the launch ran
    graph, (deep, shallow) = _paths(6, 3)
    backend = SingleDeviceBackend()
    backend.run(backend.prepare(graph), kernel, [deep, shallow])
    steps, lane_steps, slots = _counts(backend, kernel)
    assert (steps, lane_steps, slots) == (6, 9, 12)
    assert lane_steps / slots == pytest.approx(9 / 12)


def test_pad_lanes_count_as_slots_not_steps():
    # 3 sources pad to 4 lanes (the pad lane repeats the first source):
    # every step costs 4 slots, and only the 3 real lanes' steps count
    graph, (a, b, c) = _paths(4, 3, 2)
    backend = SingleDeviceBackend()
    rows = backend.run(backend.prepare(graph), "bfs", [a, b, c])
    assert rows.shape[0] == 3
    assert _counts(backend, "bfs") == (4, 4 + 3 + 2, 4 * 4)
    assert backend.last_run_steps == {"steps": 4, "lanes": 4}


def test_counted_programs_keep_the_public_results():
    graph, firsts = _paths(7, 4, 2)
    ga = to_device(graph)
    srcs = jnp.asarray(firsts, jnp.int32)
    for counted, public in ((K.bfs_multi_steps, K.bfs_multi),
                            (K.sssp_multi_steps, K.sssp_multi)):
        rows, trips, passes = counted(ga, srcs)
        np.testing.assert_array_equal(np.asarray(rows),
                                      np.asarray(public(ga, srcs)))
        np.testing.assert_array_equal(np.asarray(trips), [7, 4, 2])
        assert trips.dtype == jnp.int32
        # in-degree 2 inside every path: one pass of the segmented scan
        assert int(passes) == 1 and passes.dtype == jnp.int32


def test_session_launch_span_carries_steps_and_lanes():
    graph, (deep, shallow) = _paths(6, 3)
    session = EngineSession()
    gid = session.register(graph, "paths")
    session.submit(gid, "bfs", [deep, shallow, deep + 1])
    session.submit(gid, "pr")                 # counts no steps
    launches = [e["args"] for e in session.tracer.events
                if e["name"] == "launch"]
    assert launches[0]["steps"] == 6 and launches[0]["lanes"] == 4
    assert "steps" not in launches[1]
    snap = session.metrics().snapshot()["counters"]
    assert snap["engine_kernel_steps_total"] == {"kernel=bfs": 6}
    # lanes of 6, 3 and 5 steps (the third root sits one vertex in)
    assert snap["engine_lane_steps_total"] == {"kernel=bfs": 6 + 3 + 5}
    assert snap["engine_lane_slots_total"] == {"kernel=bfs": 6 * 4}


def _star(k):
    """A hub (vertex 0) linked both ways to 2**k leaves: in-degree 2**k."""
    leaves = list(range(1, 2**k + 1))
    return from_edges(2**k + 1, leaves + [0] * 2**k, [0] * 2**k + leaves,
                      name="star")


def _directed_path(n):
    return from_edges(n, range(n - 1), range(1, n), name="dipath")


@pytest.mark.parametrize("kernel", ["bfs", "sssp"])
@pytest.mark.parametrize("graph,passes,steps", [
    (_directed_path(9), 0, 9),    # in-degree <= 1: the runs need no pass
    (_star(3), 3, 2),             # in-degree 2**3: three passes a step
    (_star(4), 4, 2),
])
def test_segment_passes_are_passes_times_steps(kernel, graph, passes,
                                               steps):
    backend = SingleDeviceBackend()
    backend.run(backend.prepare(graph), kernel, [0])
    snap = backend.metrics.snapshot()["counters"]
    assert snap["engine_kernel_steps_total"] == {f"kernel={kernel}": steps}
    assert snap["engine_segment_passes_total"] == {
        f"kernel={kernel}": passes * steps}
