"""Reduce a JAX profiler trace to the numbers the benchmark reports.

A trace is a list of planes, each with lines of events ``[name, start_ns,
duration_ns]`` on one clock. `load_xplane` reads the ``.xplane.pb`` that
``jax.profiler`` writes into that plain form (the form the recorded test
traces under ``bench/testdata/`` keep too), and `reduce_trace` computes:

- ``window_s``: the length of the host annotation that brackets the
  measured window (``WINDOW``);
- ``busy_s``: the union of the intervals in which an operation ran on
  the device, inside the window, averaged over the device planes;
- ``programs``: device seconds and executions of each compiled program
  (the ``XLA Modules`` line), by program name without its ``(id)``;
- ``device_ops``: the device operations that took most time;
- ``idle_gaps``: the longest stretches of the window with no device
  operation, each named by the innermost host event around its middle,
  that is, by what the host was doing while the device waited.
"""
from __future__ import annotations

import re

import numpy as np

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
TOP = 10


def load_xplane(path) -> list[dict]:
    """The planes of an ``.xplane.pb`` as plain lists."""
    import jax
    data = jax.profiler.ProfileData.from_file(str(path))
    return [{"name": plane.name,
             "lines": [{"name": line.name,
                        "events": [[ev.name, float(ev.start_ns),
                                    float(ev.duration_ns)]
                                   for ev in line.events]}
                       for line in plane.lines]}
            for plane in data.planes]


def _line(plane: dict, name: str) -> list:
    return [ev for line in plane["lines"] if line["name"] == name
            for ev in line["events"]]


def _union(intervals: np.ndarray) -> np.ndarray:
    """Merge (k, 2) [start, end) intervals into disjoint sorted ones."""
    if len(intervals) == 0:
        return intervals.reshape(0, 2)
    iv = intervals[np.argsort(intervals[:, 0], kind="stable")]
    # an interval opens a new group where it starts after every earlier
    # one has ended
    reach = np.maximum.accumulate(iv[:, 1])
    first = np.flatnonzero(np.r_[True, iv[1:, 0] > reach[:-1]])
    return np.stack([iv[first, 0], np.maximum.reduceat(iv[:, 1], first)], 1)


def _window(planes: list[dict]) -> tuple[float, float]:
    for plane in planes:
        if not plane["name"].startswith("/host:"):
            continue
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                if name == WINDOW:
                    return start, start + dur
    raise ValueError(f"no {WINDOW!r} annotation on any host plane")


def _host_events(planes: list[dict]):
    """Host events with a duration, as (names, starts, ends) arrays."""
    names, starts, ends = [], [], []
    for plane in planes:
        if not plane["name"].startswith("/host:"):
            continue
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                if dur > 0 and name != WINDOW:
                    names.append(name)
                    starts.append(start)
                    ends.append(start + dur)
    return names, np.asarray(starts, np.float64), np.asarray(ends, np.float64)


def _clip(events: list, lo: float, hi: float) -> np.ndarray:
    iv = np.asarray([[s, s + d] for _, s, d in events], np.float64)
    if len(iv) == 0:
        return iv.reshape(0, 2)
    iv = np.clip(iv, lo, hi)
    return iv[iv[:, 1] > iv[:, 0]]


def reduce_trace(planes: list[dict], top: int = TOP) -> dict:
    lo, hi = _window(planes)
    devices = [p for p in planes if DEVICE_PLANE.match(p["name"])]
    if not devices:
        raise ValueError("the trace holds no device plane")
    busy, gaps = [], []
    op_seconds: dict[str, float] = {}
    programs: dict[str, dict] = {}
    for plane in devices:
        ops = _line(plane, OPS_LINE) or _line(plane, MODULES_LINE)
        merged = _union(_clip(ops, lo, hi))
        busy.append(float((merged[:, 1] - merged[:, 0]).sum()) * 1e-9)
        edges = np.concatenate([[lo], merged.ravel(), [hi]]).reshape(-1, 2)
        gaps.extend((s, e) for s, e in edges if e > s)
        for name, start, dur in ops:
            if lo <= start < hi:
                op_seconds[name] = op_seconds.get(name, 0.0) + dur * 1e-9
        for name, start, dur in _line(plane, MODULES_LINE):
            if lo <= start < hi:
                key = re.sub(r"\(\d+\)$", "", name)
                p = programs.setdefault(key, {"seconds": 0.0, "count": 0})
                p["seconds"] += dur * 1e-9
                p["count"] += 1
    names, starts, ends = _host_events(planes)
    idle = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        mid = 0.5 * (s + e)
        around = np.flatnonzero((starts <= mid) & (ends >= mid))
        what = (names[around[np.argmin(ends[around] - starts[around])]]
                if len(around) else WINDOW)
        idle.append([what, (e - s) * 1e-9])
    ops_top = sorted(op_seconds.items(), key=lambda kv: -kv[1])[:top]
    return {"window_s": (hi - lo) * 1e-9,
            "busy_s": float(np.mean(busy)),
            "programs": programs,
            "device_ops": [[k, v] for k, v in ops_top],
            "idle_gaps": idle}
