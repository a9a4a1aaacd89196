"""Ahead-of-time compiles of the served kernels for a TPU v5e chip.

Nothing runs here: each test lowers one kernel the chip smoke launches
(`chip_smoke.py`) at its serving shapes, compiles it with the TPU
compiler against a *described* v5e (no chip attached), and checks that
the program fits one chip's 16 GB of HBM by the compiler's own
``memory_analysis()``. That is what an interpret-mode or CPU run cannot
show: a kernel the TPU compiler refuses, or a coalesced batch that does
not fit. Graph shapes are the smoke's Graph500 Kronecker deployment, at
the largest scale whose compile takes a few seconds: scale 22
(V = 2**22, E = 16 V) for the multi-source kernels, whose batches the
device bound cuts there, and 21 for PR and CC. The smoke itself serves
scale 20, where the v5e compiler spends ~30 s on `bc_multi`.

The topology is described inside a module fixture and never at import
time: only one process at a time may load the TPU library, and the
tests run under several workers.
"""
from __future__ import annotations

import math
import os
import re

import pytest

import jax
import jax.numpy as jnp

from repro.algos import kernels as K
from repro.algos.graph_arrays import GraphArrays
from repro.engine.backends import (bucket_dims, estimate_device_bytes,
                                   launch_bytes, source_cap)
from repro.search.serve import SearchParams

HBM_BYTES = 16 * 10**9          # one v5e chip
SMOKE_SCALE = 22                # multi-source kernels
SMOKE_BURST = 32                # chip_smoke.BURST
# PR and CC take ~19 s each to compile at scale 22 on the v5e compiler
# and under a second at 21; their memory does not depend on a batch
GLOBAL_SCALE = 21
KNN_CORPUS, KNN_DIM, KNN_K_OUT, KNN_QUERIES = 20_000, 128, 16, 64


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip; keep these off it
    jax.config.update("jax_enable_compilation_cache", False)
    return SingleDeviceSharding(topo.devices[0])


def _graph(sharding, num_vertices: int, num_edges: int,
           masks: bool = False) -> GraphArrays:
    def arr(n, dtype=jnp.int32):
        return jax.ShapeDtypeStruct((n,), dtype, sharding=sharding)
    v, e = num_vertices, num_edges
    return GraphArrays(arr(v + 1), arr(e), arr(e), arr(v + 1), arr(e),
                       arr(e), arr(v), arr(v), arr(e),
                       arr(v, jnp.bool_) if masks else None,
                       arr(e, jnp.bool_) if masks else None, arr(e), arr(e))


def _kron(scale: int) -> tuple[int, int]:
    """(V, E) of a Graph500 Kronecker graph; E = 16 V is already on a
    bucket boundary, so the served upload is unpadded."""
    v, e = 1 << scale, 16 << scale
    assert bucket_dims(v, e) == (v, e)
    return v, e


def _fits(compiled) -> object:
    m = compiled.memory_analysis()
    total = (m.temp_size_in_bytes + m.argument_size_in_bytes
             + m.output_size_in_bytes)
    assert total <= HBM_BYTES, f"{total / 1e9:.2f} GB > one v5e chip"
    return m


def _multi_source(fn, kernel: str, sharding, scale: int, batch: int,
                  scopes: tuple[str, ...] = (),
                  loop_scatter_axes: tuple[int, ...] = ()):
    v, e = _kron(scale)
    sources = jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=sharding)
    compiled = jax.jit(fn).lower(_graph(sharding, v, e), sources).compile()
    m = _fits(compiled)
    # the per-step named scopes survive into the compiled program's op
    # metadata, which is how a device op of the trace is traced back
    hlo = compiled.as_text()
    for scope in scopes:
        assert f"/{scope}/" in hlo, scope
    if scopes:
        # the level / round loop reduces over the dst-sorted in-CSR: no
        # sort runs inside it, and no scatter but over as many indices as
        # one of ``loop_scatter_axes`` (BFS's push step, over its slots
        # and the vertices; the initial `.at[source]` scatter is outside
        # the loop)
        found = _loop_sorts_and_scatters(hlo)
        assert all(op == "scatter" and count in loop_scatter_axes
                   for op, _, count in found), found
        assert not loop_scatter_axes or found
    # the byte model the scheduler bounds batches with must cover what
    # the compiler actually allocates
    model = launch_bytes(kernel, batch, v, e)
    assert m.temp_size_in_bytes <= model, (
        f"{kernel} S={batch}: compiler temp {m.temp_size_in_bytes} B > "
        f"modelled {model} B")


def _loop_sorts_and_scatters(hlo: str) -> list[tuple[str, str, int]]:
    """The sort and scatter ops of a compiled program whose op_name puts
    them inside a while loop's body, as (op, op_name, number of scatter
    indices; 0 for a sort)."""
    shapes, found = {}, []
    for line in hlo.splitlines():
        defined = re.match(r"\s*(?:ROOT )?(%\S+) = \w+\[([\d,]*)\]", line)
        if defined:
            shapes[defined.group(1)] = [int(d) for d in
                                        defined.group(2).split(",") if d]
        op = re.search(r"= \S+ (sort|scatter)\(([^)]*)\)", line)
        name = re.search(r'op_name="([^"]*)"', line)
        if op and name and "/while/body/" in name.group(1):
            count = 0
            if op.group(1) == "scatter":
                index = op.group(2).split(",")[1].strip()
                vec = int(re.search(r"index_vector_dim=(\d+)",
                                    line).group(1))
                count = math.prod(d for i, d in enumerate(shapes[index])
                                  if i != vec)
            found.append((op.group(1), name.group(1), count))
    return found


def _smoke_batch(kernel: str, scale: int) -> int:
    """The batch the scheduler forms for a 32-source burst: the burst,
    cut to what one chip holds next to the uploaded graph."""
    v, e = _kron(scale)
    free = HBM_BYTES - estimate_device_bytes(v, e)
    return min(SMOKE_BURST, source_cap(kernel, v, e, free))


def test_compile_bfs_multi(one_chip):
    # the program the engine serves: the rows plus each lane's trip count
    # levels whose frontier's out-arcs fit E/16 slots push them: the
    # loop's only scatters, over those slots and over the vertices
    v, e = _kron(SMOKE_SCALE)
    _multi_source(K.bfs_multi_steps, "bfs", one_chip, SMOKE_SCALE,
                  _smoke_batch("bfs", SMOKE_SCALE),
                  ("bfs_frontier_gather", "bfs_segment_reduce"),
                  loop_scatter_axes=(e // 16, v))


def test_compile_sssp_multi(one_chip):
    _multi_source(K.sssp_multi_steps, "sssp", one_chip, SMOKE_SCALE,
                  _smoke_batch("sssp", SMOKE_SCALE),
                  ("sssp_candidate_gather", "sssp_segment_reduce"))


def test_compile_bc_multi_at_batch_bound(one_chip):
    """The device bound, not the burst, sets BC's batch at scale 22:
    S = 8 already takes ~9.7 GB of temporaries, so 32 would not fit."""
    v, e = _kron(SMOKE_SCALE)
    cap = source_cap("bc", v, e, HBM_BYTES - estimate_device_bytes(v, e))
    assert 1 <= cap < SMOKE_BURST
    _multi_source(K.bc_multi, "bc", one_chip, SMOKE_SCALE, cap)


def test_compile_pagerank(one_chip):
    v, e = _kron(GLOBAL_SCALE)
    _fits(K._pagerank.lower(_graph(one_chip, v, e), 20, 0.85,
                            1e-6).compile())


def test_compile_cc_labelprop(one_chip):
    v, e = _kron(GLOBAL_SCALE)
    _fits(K.cc_labelprop.lower(_graph(one_chip, v, e)).compile())


def test_compile_knn_search_multi(one_chip):
    """The smoke's corpus: 20k SIFT-width vectors, NSW out-degree 16,
    uploaded at its shape bucket (so with padding masks), 64 queries."""
    v, e = bucket_dims(KNN_CORPUS, KNN_CORPUS * KNN_K_OUT)
    p = SearchParams(k_out=KNN_K_OUT)

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn = jax.jit(lambda *a: K.knn_search_multi(
        *a, k_out=p.k_out, beam_width=p.beam_width, k_return=p.k_return,
        max_steps=p.max_steps))
    _fits(fn.lower(_graph(one_chip, v, e, masks=True),
                   arr((v, KNN_DIM), jnp.float32), arr((v,), jnp.int32),
                   arr((), jnp.int32), arr((KNN_QUERIES, KNN_DIM),
                                           jnp.float32),
                   arr((KNN_QUERIES,), jnp.bool_)).compile())
