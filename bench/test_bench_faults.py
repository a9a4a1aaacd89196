"""Whole runs of each cell on the CPU at a tiny size, past the harness's
look for a chip: a sound run comes out correct, and a run whose timed
path is broken underneath comes out not correct, once for each fault a
served graph kernel can have."""
from __future__ import annotations

import time

import numpy as np
import pytest

import jax.numpy as jnp

from bench import harness

CELLS = ["kron20.bfs.burst32", "grid100.bfs.burst4", "kron20.sssp.burst4"]


def _run(root, cell: str, seed: int = 3000000007) -> dict:
    spec = harness.resolve(root, cell)
    return harness.run_cell(spec, seed, 0.3, False, time.perf_counter())


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(tiny_root, cell):
    result = _run(tiny_root, cell)
    assert result["correct"] and result["failed"] == 0
    assert result["compared"]["mismatched_entries"]["value"] == 0
    assert list(result)[-1] == "compared"
    assert set(result["metrics"]) == {"answers_per_s", "latency_p50_ms",
                                      "latency_p95_ms", "setup_s"}
    assert result["attempted"] >= 2 * int(harness.resolve(
        tiny_root, cell).traffic["burst"])


def _state_unchanged(kernel):
    """The relaxation loop returns its initial state: the source alone."""
    fill = -1 if kernel == "bfs" else 2**31 - 1

    def fn(g, sources):
        rows = jnp.full((sources.shape[0], g.num_vertices), fill, jnp.int32)
        return rows.at[jnp.arange(sources.shape[0]), sources].set(0)
    return fn


@pytest.mark.parametrize("fault", ["answer_altered", "half_batch",
                                   "state_unchanged"])
@pytest.mark.parametrize("cell", CELLS)
def test_broken_timed_path_is_not_correct(tiny_root, monkeypatch, cell,
                                          fault):
    from repro.engine import backends
    kernel = harness.resolve(tiny_root, cell).traffic["kernel"]
    run = backends.SingleDeviceBackend.run

    def altered(self, handle, k, sources=None):
        out = run(self, handle, k, sources)
        return out.at[:, -1].add(1)     # one entry of every answer

    def half_batch(self, handle, k, sources=None):
        # only the first half of the batch is computed; the rest is
        # filled from it
        srcs = np.atleast_1d(np.asarray(sources))
        half = max(len(srcs) // 2, 1)
        out = run(self, handle, k, srcs[:half])
        return jnp.concatenate([out] * -(-len(srcs) // half))[:len(srcs)]

    if fault == "state_unchanged":
        monkeypatch.setitem(backends._FNS, kernel, _state_unchanged(kernel))
    else:
        monkeypatch.setattr(backends.SingleDeviceBackend, "run",
                            altered if fault == "answer_altered"
                            else half_batch)
    result = _run(tiny_root, cell)
    assert result["correct"] is False
    assert result["compared"]["mismatched_entries"]["value"] > 0
