"""Pluggable execution backends: where and in what shape a graph runs.

The executor used to be a single code path — exact-shape compile cache on
one device. Two scale gaps (ROADMAP "Engine") break that at serving
volume:

* **compile sharing** — XLA specializes on shapes, so a stream of graphs
  with *distinct* (V, E) recompiles every kernel per graph even though
  the programs are identical. `SingleDeviceBackend` pads CSR uploads to
  geometric (V_bucket, E_bucket) shapes with masked sentinel edges
  (graph_arrays.to_device ``pad_to``; kernels consult the masks), so all
  graphs in a bucket share one compiled executable per kernel and results
  on the real ``[:V]`` prefix stay exact.
* **single-device memory** — a graph whose CSR working set exceeds the
  per-device budget has no serving path. `ShardedBackend` routes queries
  through `core.dist`'s edge-partitioned kernels — all six (multi-source
  BFS/SSSP/BC, PageRank, CC, CC-SV) — across every visible device, with
  an optional **hot-prefix exchange** (`hot_prefix_fraction`, a policy
  decision derived from the hub-mass probe) that all-gathers only the
  hot id prefix every step and the cold suffix every ``cold_every``
  steps on the monotone kernels, exactness-preserving (core/dist.py).

Both present the same surface (`ExecutionBackend`): ``prepare`` turns a
host graph into a `GraphHandle`, ``run`` executes one query batch against
a handle. `engine.executor.BatchedExecutor` is the routing facade; the
*choice* of backend is a policy decision (`ReorderPolicy` places a graph
by comparing `estimate_device_bytes` against its device budget) recorded
in the policy record and the amortization ledger. docs/backends.md has
the full picture.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
from collections import OrderedDict
from typing import Protocol, runtime_checkable

import numpy as np

import jax
import jax.numpy as jnp

from ..algos import kernels as K
from ..algos.graph_arrays import GraphArrays, to_device
from ..core.csr import Graph
from ..search.serve import SearchSpec, pad_queries
from .obs import MetricsRegistry, Tracer

# kernels taking a batch of sources -> (S, V) per-source rows
MULTI_SOURCE = ("bfs", "sssp", "bc")
# source-independent kernels -> (V,)
GLOBAL = ("pr", "cc", "ccsv")
# kernels whose "source" is a float32 vector, not a vertex id -> the
# per-source row is a (k_return,) id vector; runs also return (V,)
# visit counts (the reorder policy's hotness telemetry)
VECTOR_SOURCE = ("knn",)

# All entries are already jitted in algos.kernels; jax's own cache
# specializes per CSR shape. The backend's key-level dict on top exists
# to *attribute* compiles to serving traffic (hit/miss telemetry).
# knn is the exception: its static beam/step knobs force the per-key
# jit wrapper pattern (_run_knn), mirroring the pr@spmv path.
# An entry returns its result, or ``(rows, trips, passes)`` where it also
# reports each lane's loop trip count and the passes of its segmented
# reduction per step (the step counters in `run_arrays`).
_FNS = {
    "bfs": K.bfs_multi_steps,
    "sssp": K.sssp_multi_steps,
    "bc": K.bc_multi,
    "pr": K.pagerank,
    "cc": K.cc_labelprop,
    "ccsv": K.cc_shiloach_vishkin,
    "knn": K.knn_search_multi,
}


def build_kernel(kernel: str):
    try:
        return _FNS[kernel]
    except KeyError:
        raise ValueError(
            f"unknown kernel {kernel!r}; "
            f"have {MULTI_SOURCE + GLOBAL + VECTOR_SOURCE}") from None


def source_bucket(n: int) -> int:
    """Next power-of-two source-batch bucket (>= 1)."""
    return 1 << max(0, (n - 1).bit_length())


def pad_sources(sources, kernel: str) -> tuple[np.ndarray, int]:
    """Validate + pad a source batch to its power-of-two bucket.

    Returns ``(padded_sources, real_count)``. Raises *before* any cache
    or device work for an empty batch — a zero-width vmap launch would
    still consult (and pollute) the compile-cache telemetry.
    """
    srcs = np.atleast_1d(np.asarray(sources, dtype=np.int32))
    if srcs.size == 0:
        raise ValueError(f"{kernel} needs at least one source")
    pad = source_bucket(srcs.size)
    padded = np.full(pad, srcs[0], np.int32)
    padded[:srcs.size] = srcs
    return padded, int(srcs.size)


# ------------------------------------------------------------------ buckets
def bucket_dims(num_vertices: int, num_edges: int, growth: float = 2.0,
                v_floor: int = 256, e_floor: int = 1024) -> tuple[int, int]:
    """Geometric (V_bucket, E_bucket) for compile sharing.

    Buckets grow by ``growth`` from the floors, so a stream of arbitrary
    graph sizes hits O(log V + log E) compiled shapes per kernel. When
    edges need padding the vertex bucket is forced strictly above V so
    sentinel self-loops land on a *padded* vertex — that keeps them out
    of every real adjacency list and off the real in-CSR rows.
    """
    if growth <= 1.0:
        raise ValueError(f"growth must be > 1, got {growth}")

    def up(x: int, floor: int) -> int:
        b = floor
        while b < x:
            b = int(math.ceil(b * growth))
        return b

    e_b = up(num_edges, e_floor)
    v_min = num_vertices + 1 if e_b > num_edges else num_vertices
    v_b = up(v_min, v_floor)
    return v_b, e_b


def estimate_device_bytes(num_vertices: int, num_edges: int,
                          batch_sources: int = 0) -> int:
    """Device footprint of serving one graph (the placement input).

    CSR upload: int32 fields — 2x indptr (V+1), 6x edge-sized (indices,
    src, t_indices, t_dst, weights, t_weights), 2x vertex-sized degrees
    — plus 1-byte bool masks.

    ``batch_sources`` adds the **query state** (placement v2): a
    multi-source launch of S sources holds an (S, V) int32 property
    matrix plus a same-shape relaxation/frontier buffer alive on the
    device, so at realistic batch sizes the working set is the CSR *and*
    ~8·S·V bytes. The policy feeds S from the micro-batch scheduler's
    observed launch sizes (`ReorderPolicy.observe_batch_sources`), so a
    graph that fits alone but not under its real traffic's batches is
    placed sharded.
    """
    return (4 * (2 * (num_vertices + 1) + 6 * num_edges + 2 * num_vertices)
            + num_vertices + num_edges
            + 8 * batch_sources * num_vertices)


# Device bytes one more source (or knn query) adds to a vmapped launch,
# as (bytes per edge, bytes per vertex). The edge term is what the
# vmapped bodies materialize per lane: BFS its gathered frontier flags
# and their segmented scan, SSSP its int32 relaxation candidates and
# their scan (a pass reads one buffer and writes another), BC the
# per-lane tree masks, depths and float gathers of both Brandes passes.
# Fitted to the TPU compiler's own `memory_analysis()` at Graph500 scale
# 22 on v5e (BFS 2.1, SSSP 8.0, BC 17.5 bytes per edge per source, plus
# ~4·E bytes per launch whatever S is), rounded up;
# tests/test_tpu_compile.py holds the compiler to them.
_SOURCE_BYTES = {"bfs": (2, 16), "sssp": (8, 16), "bc": (18, 32),
                 "knn": (0, 8)}
# the S-independent per-launch temporaries, in bytes per edge
_LAUNCH_BYTES_PER_EDGE = 4
# ...and the least a launch takes. Up to Graph500 scale 20 (E <= 2**24)
# the v5e compiler lowers the `bfs_multi` / `bc_multi` scatters through
# one workspace of 210-224 bytes per edge whatever S is (3.76 GB at scale
# 20), which the per-source temporaries then share; from scale 21 it
# does not. A floor covers both lowerings without cutting the batches
# the larger graphs fit.
_LAUNCH_FLOOR_BYTES = 4 << 30


def source_state_bytes(kernel: str, num_vertices: int,
                       num_edges: int) -> int:
    """Device bytes each source adds to one launch of ``kernel`` (0 for
    the source-independent kernels)."""
    per_edge, per_vertex = _SOURCE_BYTES.get(kernel, (0, 0))
    return per_edge * num_edges + per_vertex * num_vertices


def launch_bytes(kernel: str, batch: int, num_vertices: int,
                 num_edges: int) -> int:
    """Modelled device temporaries of one ``batch``-source launch."""
    return max(batch * source_state_bytes(kernel, num_vertices, num_edges)
               + _LAUNCH_BYTES_PER_EDGE * num_edges, _LAUNCH_FLOOR_BYTES)


def source_cap(kernel: str, num_vertices: int, num_edges: int,
               free_bytes: int) -> int:
    """Largest power-of-two source batch of ``kernel`` whose
    `launch_bytes` fit in ``free_bytes`` of device memory (at least 1: a
    lone source is always tried). Power of two because launches pad their
    batch up to one (`pad_sources`)."""
    per = source_state_bytes(kernel, num_vertices, num_edges)
    if per <= 0:
        raise ValueError(f"{kernel} takes no source batch")
    if _LAUNCH_FLOOR_BYTES > free_bytes:
        return 1
    room = free_bytes - _LAUNCH_BYTES_PER_EDGE * num_edges
    fit = max(room // per, 1)
    return 1 << (int(fit).bit_length() - 1)


def pr_path(pallas_pr: bool | str = "auto") -> str:
    """Resolve the ``pallas_pr`` setting to the PageRank relaxation served.

    ``"xla"`` is the segment-sum pull loop (`algos.kernels._pagerank`).
    ``"pallas"`` routes the relaxation through the packed CSR-SpMV kernel
    compiled for the TPU, ``"pallas-interpret"`` through the same kernel
    body in the Pallas interpreter (validation only, far slower).

    ``"auto"`` and ``False`` serve XLA on every platform: the TPU compiler
    rejects the Pallas kernel (its in-kernel ``jnp.take`` is a 1-D gather,
    and its whole-vector VMEM block caps V), so choosing it from the
    platform would fail every PR request on the chip. ``True`` asks for
    the compiled kernel and needs a TPU; ``"interpret"`` asks for the
    interpreter by name.
    """
    if pallas_pr in ("auto", False):
        return "xla"
    if pallas_pr == "interpret":
        return "pallas-interpret"
    if pallas_pr is True:
        if jax.default_backend() != "tpu":
            raise ValueError(
                "pallas_pr=True compiles the Pallas CSR-SpMV kernel for the "
                f"TPU; the backend here is {jax.default_backend()!r}. Pass "
                "pallas_pr='interpret' to run its interpreter instead")
        return "pallas"
    raise ValueError(f"pallas_pr must be 'auto', True, False or "
                     f"'interpret'; got {pallas_pr!r}")


# ------------------------------------------------------------------- handle
@dataclasses.dataclass
class PackedSpMV:
    """Pre-packed Pallas CSR-SpMV operands for one uploaded graph.

    `kernels.csr_spmv.pack_edges` output for the (possibly bucketed)
    in-CSR edge stream: dst-tiled edge blocks plus the static grid
    dimensions. ``val`` is 0 on sentinel edges, so bucketed uploads
    contribute nothing from padding. The grid dims are data-dependent
    (``blocks_per_tile`` follows the densest dst tile), so they are part
    of the compile-cache key — two graphs in the same (V, E) bucket may
    still need distinct pallas grids.
    """

    src: jnp.ndarray
    dst_local: jnp.ndarray
    val: jnp.ndarray
    blocks_per_tile: int
    num_tiles: int
    n_pad: int
    interpret: bool


@dataclasses.dataclass
class GraphHandle:
    """What ``prepare`` returns and ``run`` consumes — one served graph.

    ``num_vertices``/``num_edges`` are the *real* sizes; ``bucket`` is the
    padded upload shape (equal to the real sizes when bucketing is off or
    the graph already sits on a bucket boundary). ``arrays`` is the
    single-device upload; sharded handles carry backend state in
    ``shard_state`` instead.
    """

    backend: str
    num_vertices: int
    num_edges: int
    bucket: tuple[int, int]
    device_bytes: int
    arrays: GraphArrays | None = None
    shard_state: object | None = None
    hot_prefix_fraction: float | None = None  # sharded exchange policy
    spmv: PackedSpMV | None = None  # Pallas PR relaxation operands
    search: "DeviceSearch | None" = None  # knn operands (search graphs)


@dataclasses.dataclass
class DeviceSearch:
    """Device-resident knn operands for one uploaded search graph.

    ``vectors``/``canon`` are the `SearchSpec` payloads padded to the
    handle's vertex bucket (padded rows are unreachable: sentinel edges
    never land in a real adjacency list, so the kernel cannot gather
    them). ``params`` are the compile-static beam knobs.
    """

    vectors: jnp.ndarray   # (V_bucket, d) float32, served order
    canon: jnp.ndarray     # (V_bucket,) int32 served -> original
    entry: int             # served id of the entry vertex
    params: object         # search.serve.SearchParams
    dim: int


def _device_search(spec: SearchSpec, v_bucket: int) -> DeviceSearch:
    vecs = np.ascontiguousarray(spec.vectors, dtype=np.float32)
    canon = np.ascontiguousarray(spec.canon, dtype=np.int32)
    if v_bucket > len(vecs):
        vecs = np.concatenate(
            [vecs, np.zeros((v_bucket - len(vecs), vecs.shape[1]),
                            np.float32)])
        canon = np.concatenate(
            [canon, np.arange(len(canon), v_bucket, dtype=np.int32)])
    return DeviceSearch(jnp.asarray(vecs), jnp.asarray(canon),
                        int(spec.entry), spec.params, int(vecs.shape[1]))


@runtime_checkable
class ExecutionBackend(Protocol):
    """Uniform surface the executor routes through."""

    name: str

    def prepare(self, graph: Graph,
                canonical_ids: np.ndarray | None = None) -> GraphHandle: ...

    def run(self, handle: GraphHandle, kernel: str,
            sources=None) -> jnp.ndarray: ...

    def telemetry(self) -> dict: ...


def _backend_counters(metrics: MetricsRegistry, backend: str) -> dict:
    """The per-backend serving counters every backend keeps."""
    return {
        "queries": metrics.counter("engine_queries_total",
                                   "query batches executed",
                                   backend=backend),
        "sources": metrics.counter("engine_sources_total",
                                   "real (unpadded) sources executed",
                                   backend=backend),
        "prepared": metrics.counter("engine_graphs_prepared_total",
                                    "graphs uploaded/prepared",
                                    backend=backend),
        # host->device kernel launches. Single-device queries are one
        # launch each; sharded queries were one launch *per traversal
        # step* until the fused drivers (core/dist.py) collapsed them to
        # one per run — the collapse tests/test_fused_loops.py asserts
        # through this counter.
        "dispatches": metrics.counter("engine_dispatches_total",
                                      "host->device kernel launches",
                                      backend=backend),
    }


# ------------------------------------------------------------- single device
class SingleDeviceBackend:
    """Today's path plus shape bucketing: one device, shared compiles.

    The compiled-executable cache is **bounded**: with
    ``max_cached_executables`` set, entries are evicted LRU once the cap
    is hit. Each cache entry owns its own ``jax.jit`` wrapper (not the
    module-level jitted kernel), so evicting an entry genuinely releases
    its compiled executables — a long-lived session serving an unbounded
    stream of shapes stays bounded instead of accumulating one executable
    per (kernel, bucket) forever (the ROADMAP's eviction item). Evictions
    are counted in telemetry; an evicted shape that returns simply
    recompiles (a counted miss).
    """

    name = "single"

    def __init__(self, bucketing: bool = True, growth: float = 2.0,
                 v_floor: int = 256, e_floor: int = 1024,
                 max_cached_executables: int | None = None,
                 pallas_pr: bool | str = "auto",
                 metrics: MetricsRegistry | None = None):
        if max_cached_executables is not None and max_cached_executables < 1:
            raise ValueError("max_cached_executables must be >= 1 or None")
        self.bucketing = bucketing
        self.growth = growth
        self.v_floor = v_floor
        self.e_floor = e_floor
        self.max_cached_executables = max_cached_executables
        self.pr_path = pr_path(pallas_pr)
        self._cache: OrderedDict[tuple, object] = OrderedDict()
        # counters are registry instruments (obs.py); the legacy int
        # attributes below are read-through properties over them
        self.metrics = metrics or MetricsRegistry()
        self.tracer: Tracer | None = None   # set by the owning session
        self._counters = _backend_counters(self.metrics, self.name)
        self._c_hits = self.metrics.counter(
            "engine_compile_cache_hits_total",
            "executable cache hits", backend=self.name)
        self._c_misses = self.metrics.counter(
            "engine_compile_cache_misses_total",
            "executable cache misses (compiles)", backend=self.name)
        self._c_evictions = self.metrics.counter(
            "engine_cache_evictions_total",
            "LRU executable evictions", backend=self.name)
        self._bucket_counts: dict[tuple[int, int], int] = {}
        # {"steps", "lanes"} of the last launch whose program counted
        # its loop trips (None otherwise); the session puts them on the
        # launch span
        self.last_run_steps: dict | None = None

    @property
    def cache_hits(self) -> int:
        return self._c_hits.value

    @property
    def cache_misses(self) -> int:
        return self._c_misses.value

    @property
    def cache_evictions(self) -> int:
        return self._c_evictions.value

    @property
    def queries_run(self) -> int:
        return self._counters["queries"].value

    @property
    def sources_run(self) -> int:
        return self._counters["sources"].value

    @property
    def graphs_prepared(self) -> int:
        return self._counters["prepared"].value

    def _span(self, name: str, **args):
        return (self.tracer.span(name, **args) if self.tracer is not None
                else contextlib.nullcontext(args))

    # -------------------------------------------------------------- prepare
    def prepare(self, graph: Graph,
                canonical_ids: np.ndarray | None = None,
                search: SearchSpec | None = None) -> GraphHandle:
        n, e = graph.num_vertices, graph.num_edges
        bucket = (bucket_dims(n, e, self.growth, self.v_floor, self.e_floor)
                  if self.bucketing else (n, e))
        arrays = to_device(graph, canonical_ids=canonical_ids,
                           pad_to=bucket if bucket != (n, e) else None)
        self._counters["prepared"].inc()
        self._bucket_counts[bucket] = self._bucket_counts.get(bucket, 0) + 1
        spmv = self._pack_spmv(arrays) if self.pr_path != "xla" else None
        ds = _device_search(search, bucket[0]) if search is not None else None
        return GraphHandle(self.name, n, e, bucket,
                           estimate_device_bytes(*bucket), arrays=arrays,
                           spmv=spmv, search=ds)

    def _pack_spmv(self, arrays: GraphArrays) -> PackedSpMV:
        """Pack the (bucketed) in-CSR edge stream for the Pallas kernel.

        Edge values are the PR relaxation's coefficients: 1 for real
        edges, 0 for sentinels (`to_device` keeps real edges on the
        ``[:E]`` prefix of *both* CSR views, so ``edge_valid`` aligns
        with the in-CSR order too).
        """
        from ..kernels.csr_spmv.csr_spmv import pack_edges
        ev = arrays.edge_valid
        weights = None if ev is None else np.asarray(ev, np.float32)
        src, dst_local, val, bpt, ntiles, n_pad = pack_edges(
            np.asarray(arrays.t_indptr), np.asarray(arrays.t_indices),
            weights)
        return PackedSpMV(jnp.asarray(src), jnp.asarray(dst_local),
                          jnp.asarray(val), bpt, ntiles, n_pad,
                          self.pr_path == "pallas-interpret")

    def source_cap(self, handle: GraphHandle, kernel: str) -> int | None:
        """Largest batch of ``kernel`` sources that fits the free memory
        the handle's device reports now (``bytes_limit`` less
        ``bytes_in_use``). None where the device reports no limit — the
        CPU — so batches stay unbounded there."""
        device = next(iter(handle.arrays.indptr.devices()))
        stats = device.memory_stats()
        if not stats or "bytes_limit" not in stats:
            return None
        free = stats["bytes_limit"] - stats.get("bytes_in_use", 0)
        return source_cap(kernel, *handle.bucket, free)

    # ------------------------------------------------------------------ run
    def _cache_get(self, key: tuple, build):
        """Hit/miss-counted LRU lookup; ``build()`` makes the jit wrapper."""
        cached = self._cache.get(key)
        if cached is not None:
            self._c_hits.inc()
            self._cache.move_to_end(key)     # LRU: refresh recency
            return cached
        self._c_misses.inc()
        if self.tracer is not None:
            self.tracer.instant("compile_cache_miss", kernel=key[0],
                                key=str(key))
        # a per-key jit wrapper owns this key's executables, so LRU
        # eviction below actually frees them (the module-level jitted
        # kernel would pin every shape it ever compiled)
        cached = build()
        self._cache[key] = cached
        if (self.max_cached_executables is not None
                and len(self._cache) > self.max_cached_executables):
            self._cache.popitem(last=False)  # least recently used
            self._c_evictions.inc()
        return cached

    def _compiled(self, kernel: str, ga: GraphArrays):
        # validate the kernel name before touching any telemetry counter
        fn = build_kernel(kernel)
        # mask presence changes the pytree structure, so jax recompiles
        # even at equal shapes — the telemetry key must not conflate them
        key = (kernel, ga.num_vertices, ga.num_edges,
               ga.vertex_valid is not None)
        return self._cache_get(key, lambda: jax.jit(fn))

    def run_arrays(self, ga: GraphArrays, kernel: str,
                   sources=None) -> jnp.ndarray:
        """Execute against raw device arrays (no real-prefix slicing)."""
        build_kernel(kernel)  # unknown kernel: raise before anything counts
        self.last_run_steps = None
        if kernel in GLOBAL:
            fn = self._compiled(kernel, ga)
            self._counters["queries"].inc()
            self._counters["dispatches"].inc()
            out = fn(ga)
            with self._span("device_sync", kernel=kernel):
                return jax.block_until_ready(out)
        padded, real = pad_sources(sources, kernel)
        fn = self._compiled(kernel, ga)
        self._counters["queries"].inc()
        self._counters["dispatches"].inc()
        self._counters["sources"].inc(real)
        out = fn(ga, jnp.asarray(padded))
        trips = passes = None
        if isinstance(out, tuple):       # rows, trip counts, passes
            out, trips, passes = out
            # queued now, the copies land as the program ends instead of
            # costing a host round trip of their own after the sync
            trips.copy_to_host_async()
            passes.copy_to_host_async()
        with self._span("device_sync", kernel=kernel):
            rows = jax.block_until_ready(out)[:real]
        if trips is not None:
            self._count_steps(kernel, np.asarray(trips), real,
                              int(passes))
        return rows

    def _count_steps(self, kernel: str, trips: np.ndarray, real: int,
                     passes: int) -> None:
        """Step and lane counters of one launch from its lanes' loop trip
        counts: the launch ran ``max(trips)`` steps over every padded
        lane, of which the real lanes needed ``sum(trips[:real])``, and
        each step ran ``passes`` passes of the segmented reduction."""
        steps, lanes = int(trips.max()), len(trips)
        m = self.metrics
        m.counter("engine_kernel_steps_total",
                  "loop steps the kernel programs ran (per launch, the "
                  "max over its lanes)", kernel=kernel).inc(steps)
        m.counter("engine_lane_steps_total",
                  "loop steps the real lanes needed",
                  kernel=kernel).inc(int(trips[:real].sum()))
        m.counter("engine_lane_slots_total",
                  "loop steps x padded lanes per launch",
                  kernel=kernel).inc(steps * lanes)
        m.counter("engine_segment_passes_total",
                  "segmented-reduction passes the kernel programs ran "
                  "(per launch, passes per step x steps)",
                  kernel=kernel).inc(passes * steps)
        self.last_run_steps = {"steps": steps, "lanes": lanes}

    def _run_pr_spmv(self, handle: GraphHandle) -> jnp.ndarray:
        """PR with the relaxation on the Pallas CSR kernel (still one
        ``while_loop`` jit, one dispatch — only the segment-sum inside
        the loop body changes). The cache key carries the pallas grid
        dims: ``blocks_per_tile`` follows the densest destination tile,
        so graphs sharing a (V, E) bucket may still need separate
        executables."""
        ga, sp = handle.arrays, handle.spmv
        key = ("pr@spmv", ga.num_vertices, ga.num_edges,
               ga.vertex_valid is not None, sp.num_tiles,
               sp.blocks_per_tile)
        fn = self._cache_get(key, lambda: jax.jit(functools.partial(
            K.pagerank_spmv, blocks_per_tile=sp.blocks_per_tile,
            num_tiles=sp.num_tiles, n_pad=sp.n_pad,
            interpret=sp.interpret)))
        self._counters["queries"].inc()
        self._counters["dispatches"].inc()
        out = fn(ga, sp.src, sp.dst_local, sp.val)
        with self._span("device_sync", kernel="pr"):
            return jax.block_until_ready(out)

    def _run_knn(self, handle: GraphHandle, queries) -> tuple:
        """Beam search over the uploaded search graph: (S, d) queries ->
        ``((S, k_return) served ids, (V,) visit counts)``. The beam knobs
        are compile-static, so (like pr@spmv) each parameterization owns
        a per-key jit wrapper in the bounded executable cache."""
        ds = handle.search
        if ds is None:
            raise ValueError("knn_search needs a graph prepared with "
                             "search= (a SearchSpec); this handle has none")
        ga = handle.arrays
        p = ds.params
        padded, valid, real = pad_queries(queries)
        key = ("knn", ga.num_vertices, ga.num_edges, ds.dim, len(padded),
               p.k_out, p.beam_width, p.k_return, p.max_steps)
        fn = self._cache_get(key, lambda: jax.jit(functools.partial(
            K.knn_search_multi, k_out=p.k_out, beam_width=p.beam_width,
            k_return=p.k_return, max_steps=p.max_steps)))
        self._counters["queries"].inc()
        self._counters["dispatches"].inc()
        self._counters["sources"].inc(real)
        ids, visits = fn(ga, ds.vectors, ds.canon, jnp.int32(ds.entry),
                         jnp.asarray(padded), jnp.asarray(valid))
        with self._span("device_sync", kernel="knn"):
            ids = jax.block_until_ready(ids)
        return ids[:real], visits[:handle.num_vertices]

    def run(self, handle: GraphHandle, kernel: str,
            sources=None) -> jnp.ndarray:
        self.last_run_steps = None
        if kernel in VECTOR_SOURCE:
            # knn returns (ids, visits), already sliced to real shapes
            return self._run_knn(handle, sources)
        if kernel == "pr" and handle.spmv is not None:
            out = self._run_pr_spmv(handle)
        else:
            out = self.run_arrays(handle.arrays, kernel, sources)
        # slice the bucket padding back off: results live on [:V]
        return out[..., :handle.num_vertices]

    # ------------------------------------------------------------ telemetry
    def telemetry(self) -> dict:
        return {
            "compile_cache_hits": self.cache_hits,
            "compile_cache_misses": self.cache_misses,
            "cache_evictions": self.cache_evictions,
            "max_cached_executables": self.max_cached_executables,
            "cached_keys": sorted(str(k) for k in self._cache),
            "queries_run": self.queries_run,
            "sources_run": self.sources_run,
            "dispatches": self._counters["dispatches"].value,
            "pr_path": self.pr_path,
            "bucketing": {
                "enabled": self.bucketing,
                "graphs_prepared": self.graphs_prepared,
                "distinct_buckets": len(self._bucket_counts),
                "bucket_counts": {str(k): v
                                  for k, v in sorted(self._bucket_counts.items())},
            },
        }


# ----------------------------------------------------------------- sharded
def _make_sharded_bfs(st):
    from ..core import dist
    return dist.make_distributed_bfs(
        st.graph, st.mesh, st.axis,
        hot_prefix_fraction=st.hot_prefix_fraction,
        cold_every=st.cold_every, stats=st.stats, fused=st.fused)


def _make_sharded_sssp(st):
    from ..core import dist
    return dist.make_distributed_sssp(
        st.graph, st.mesh, st.axis, canonical_ids=st.canonical_ids,
        hot_prefix_fraction=st.hot_prefix_fraction,
        cold_every=st.cold_every, stats=st.stats, fused=st.fused)


def _make_sharded_pr(st):
    from ..core import dist
    # synchronous power iteration: always a full exchange (core/dist.py)
    run, _ = dist.make_distributed_pagerank(st.graph, st.mesh, st.axis,
                                            stats=st.stats, fused=st.fused)
    return run


def _make_sharded_cc(st):
    from ..core import dist
    return dist.make_distributed_cc(
        st.graph, st.mesh, st.axis,
        hot_prefix_fraction=st.hot_prefix_fraction,
        cold_every=st.cold_every, stats=st.stats, fused=st.fused)


def _make_sharded_bc(st):
    from ..core import dist
    # level-synchronous float accumulation: always a full exchange
    return dist.make_distributed_bc(st.graph, st.mesh, st.axis,
                                    stats=st.stats, fused=st.fused)


# Every served kernel has a sharded runner factory — full six-kernel
# parity with the single-device backend. CC-SV shares the min-label
# runner: both converge to the min-id-per-component labeling, and the
# alias makes cc/ccsv share one cached runner (one edge partition, one
# compile) instead of building two identical ones.
_RUNNER_FACTORIES = {
    "bfs": _make_sharded_bfs,
    "sssp": _make_sharded_sssp,
    "bc": _make_sharded_bc,
    "pr": _make_sharded_pr,
    "cc": _make_sharded_cc,
    "ccsv": _make_sharded_cc,
}
_RUNNER_ALIASES = {"ccsv": "cc"}

SHARDED_KERNELS = tuple(_RUNNER_FACTORIES)


class _ShardedGraphState:
    """Per-graph device state for `ShardedBackend` (lazy kernel factories)."""

    def __init__(self, graph: Graph, mesh, axis: str,
                 canonical_ids: np.ndarray | None,
                 hot_prefix_fraction: float | None, cold_every: int,
                 stats, fused: bool = True,
                 search: SearchSpec | None = None):
        self.graph = graph
        self.mesh = mesh
        self.axis = axis
        self.canonical_ids = canonical_ids
        self.hot_prefix_fraction = hot_prefix_fraction
        self.cold_every = cold_every
        self.stats = stats
        self.fused = fused
        self._runners: dict[str, object] = {}
        # knn (query-parallel GSPMD) state: the host SearchSpec, the
        # replicated device operands (built lazily on first knn run),
        # and per-batch-shape jit wrappers
        self.search = search
        self.knn_operands: tuple | None = None
        self.knn_fns: dict[tuple, object] = {}

    def runner(self, kernel: str):
        kernel = _RUNNER_ALIASES.get(kernel, kernel)
        fn = self._runners.get(kernel)
        if fn is None:
            # unknown kernel names are rejected by build_kernel before we
            # get here, so a miss in the factory table is a parity bug
            assert kernel in _RUNNER_FACTORIES, (
                f"kernel {kernel!r} is served but has no sharded runner "
                f"factory; SHARDED_KERNELS = {SHARDED_KERNELS}")
            fn = _RUNNER_FACTORIES[kernel](self)
            self._runners[kernel] = fn
        return fn


class ShardedBackend:
    """Serve graphs beyond one device through core/dist edge partitions.

    Edges are 1-D partitioned by destination range over ``mesh[axis]``
    (every visible device by default); vertex property state lives sharded
    and each traversal step all-gathers it — see core/dist.py for why
    reordering concentrates the *useful* payload of that collective.
    ``prepare``'s ``hot_prefix_fraction`` (a policy decision) turns on the
    hot-prefix exchange for the monotone kernels: only that fraction of
    each shard's slice is gathered per step, the cold suffix every
    ``cold_every`` steps. `telemetry()["hot_prefix"]` reports the
    exchanged-vs-full byte ledger and static prefix hit rates.
    """

    name = "sharded"

    def __init__(self, num_shards: int | None = None, axis: str = "data",
                 mesh=None, cold_every: int = 4,
                 metrics: MetricsRegistry | None = None,
                 fused: bool = True):
        if mesh is None:
            from ..core.dist import vertex_mesh
            mesh = vertex_mesh(num_shards, axis)
        self.mesh = mesh
        self.axis = axis
        self.num_shards = mesh.shape[axis]
        self.cold_every = cold_every
        # fused=True runs each traversal as one on-device XLA While
        # (one dispatch per query); False keeps the host step loop — the
        # differential reference for tests/test_fused_loops.py
        self.fused = fused
        self.metrics = metrics or MetricsRegistry()
        self.tracer: Tracer | None = None   # set by the owning session
        self._counters = _backend_counters(self.metrics, self.name)
        self._c_ex_steps = self.metrics.counter(
            "engine_exchange_steps_total",
            "sharded per-step collective exchanges")
        self._c_ex_bytes = self.metrics.counter(
            "engine_exchange_bytes_total",
            "bytes received per device across exchanges")
        from ..core.dist import ExchangeStats
        self.exchange_stats = ExchangeStats()
        # exchange delta of the most recent run(): runs are serial, so a
        # snapshot/delta pair attributes collective bytes per query — the
        # scheduler copies this into each request's telemetry
        self.last_run_exchange: dict | None = None
        self._prefix_info: list[dict] = []

    @property
    def queries_run(self) -> int:
        return self._counters["queries"].value

    @property
    def sources_run(self) -> int:
        return self._counters["sources"].value

    @property
    def graphs_prepared(self) -> int:
        return self._counters["prepared"].value

    def prepare(self, graph: Graph,
                canonical_ids: np.ndarray | None = None,
                hot_prefix_fraction: float | None = None,
                search: SearchSpec | None = None) -> GraphHandle:
        n, e = graph.num_vertices, graph.num_edges
        state = _ShardedGraphState(graph, self.mesh, self.axis,
                                   canonical_ids, hot_prefix_fraction,
                                   self.cold_every, self.exchange_stats,
                                   fused=self.fused, search=search)
        self._counters["prepared"].inc()
        return GraphHandle(self.name, n, e, (n, e),
                           self._per_device_bytes(graph),
                           shard_state=state,
                           hot_prefix_fraction=hot_prefix_fraction)

    def _per_device_bytes(self, graph: Graph) -> int:
        """Resident graph bytes per device, from the *actual* partition.

        `partition_edges` splits by dst range and pads every shard to the
        fullest shard's edge count, so on skewed graphs the per-device
        footprint is set by the hub-heaviest range — the true histogram
        is O(E) on the host and cheap next to the upload. Counts the
        edge arrays (src, dst, valid, weights) and one int32 vertex
        property slice; per-query (S × per) state is not included.
        """
        per = -(-graph.num_vertices // self.num_shards)
        counts = np.bincount(np.asarray(graph.indices) // per,
                             minlength=self.num_shards)
        emax = int(counts.max()) if len(counts) else 0
        return emax * (4 + 4 + 1 + 4) + per * 4

    def _run_knn(self, handle: GraphHandle, queries) -> tuple:
        """Query-parallel knn through GSPMD: queries are row-sharded over
        ``mesh[axis]``, the CSR arrays / vector corpus / canonical-id map
        replicated, and the same jitted kernel the single-device path
        compiles partitions its ``vmap`` across devices — each shard
        beam-searches its query rows and the visit-count reduction over
        lanes lowers to one psum. No per-step exchange (the graph is
        replicated), so ``last_run_exchange`` stays None for knn runs;
        bit-identity with the single path holds because every lane runs
        the identical per-query program on identical operands."""
        from jax.sharding import NamedSharding, PartitionSpec
        st = handle.shard_state
        sp = st.search
        if sp is None:
            raise ValueError("knn_search needs a graph prepared with "
                             "search= (a SearchSpec); this handle has none")
        replicated = NamedSharding(self.mesh, PartitionSpec())
        if st.knn_operands is None:
            ga = to_device(st.graph, canonical_ids=st.canonical_ids)
            st.knn_operands = (
                jax.device_put(ga, replicated),
                jax.device_put(jnp.asarray(sp.vectors, jnp.float32),
                               replicated),
                jax.device_put(jnp.asarray(sp.canon, jnp.int32), replicated),
            )
        ga, vecs, canon = st.knn_operands
        padded, valid, real = pad_queries(queries, multiple=self.num_shards)
        q = jax.device_put(
            jnp.asarray(padded),
            NamedSharding(self.mesh, PartitionSpec(self.axis, None)))
        vmask = jax.device_put(
            jnp.asarray(valid),
            NamedSharding(self.mesh, PartitionSpec(self.axis)))
        p = sp.params
        key = (len(padded), p.k_out, p.beam_width, p.k_return, p.max_steps)
        fn = st.knn_fns.get(key)
        if fn is None:
            fn = jax.jit(functools.partial(
                K.knn_search_multi, k_out=p.k_out, beam_width=p.beam_width,
                k_return=p.k_return, max_steps=p.max_steps))
            st.knn_fns[key] = fn
        self._counters["queries"].inc()
        self._counters["dispatches"].inc()
        self._counters["sources"].inc(real)
        ids, visits = jax.block_until_ready(
            fn(ga, vecs, canon, jnp.int32(int(sp.entry)), q, vmask))
        self.last_run_exchange = None
        return ids[:real], visits[:handle.num_vertices]

    def run(self, handle: GraphHandle, kernel: str,
            sources=None) -> jnp.ndarray:
        build_kernel(kernel)  # unknown kernel: raise before anything counts
        if kernel in VECTOR_SOURCE:
            return self._run_knn(handle, sources)
        canon = _RUNNER_ALIASES.get(kernel, kernel)
        new_runner = canon not in handle.shard_state._runners
        runner = handle.shard_state.runner(kernel)
        if new_runner and getattr(runner, "hot_prefix_fraction",
                                  None) is not None:
            self._prefix_info.append({
                "kernel": canon,
                "hot_prefix_fraction": runner.hot_prefix_fraction,
                "h_local": runner.h_local,
                "per_shard_vertices": runner.per,
                "prefix_hit_rate": round(runner.prefix_hit_rate, 4),
            })
        self._counters["queries"].inc()
        before = self.exchange_stats.snapshot()
        # per-step exchange spans: while this run is live, every
        # ExchangeStats record emits one engine-track span covering the
        # step that ended at the collective — nested under the launch
        # span the session wraps around executor.run
        if self.tracer is not None:
            tracer = self.tracer
            last = {"t": tracer.clock.now()}

            def _exchange_span(mode: str, nbytes: int,
                               full_nbytes: int) -> None:
                now = tracer.clock.now()
                tracer.emit("exchange", last["t"], now,
                            args={"mode": mode, "bytes": nbytes,
                                  "bytes_full_equivalent": full_nbytes,
                                  "kernel": canon})
                last["t"] = now

            self.exchange_stats.span_sink = _exchange_span
        try:
            if kernel in GLOBAL:
                out = jax.block_until_ready(runner())[:handle.num_vertices]
            else:
                padded, real = pad_sources(sources, kernel)
                self._counters["sources"].inc(real)
                out = jax.block_until_ready(
                    runner(jnp.asarray(padded)))[:real,
                                                 :handle.num_vertices]
        finally:
            self.exchange_stats.span_sink = None
        delta = self.exchange_stats.delta(before)
        self._c_ex_steps.inc(delta.steps)
        self._c_ex_bytes.inc(delta.bytes_exchanged)
        self._counters["dispatches"].inc(delta.dispatches)
        self.last_run_exchange = delta.as_dict()
        return out

    def telemetry(self) -> dict:
        return {
            "num_shards": self.num_shards,
            "graphs_prepared": self.graphs_prepared,
            "queries_run": self.queries_run,
            "sources_run": self.sources_run,
            "fused": self.fused,
            "dispatches": self._counters["dispatches"].value,
            "hot_prefix": {
                **self.exchange_stats.as_dict(),
                "cold_every": self.cold_every,
                "runners": list(self._prefix_info),
            },
        }
