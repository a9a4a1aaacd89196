"""Distributed graph engine: 1-D edge-partitioned kernels via shard_map.

Scales the paper's workload to cluster meshes: edges are partitioned by
destination range (each shard owns a contiguous dst range = its slice of
the property array); a traversal step is

    local gather (remote props via all-gather) -> local segment-reduce

which is the pull-mode pattern of the paper mapped onto jax collectives.
After LOrder, hot vertices are concentrated in low id ranges, so the
all-gather payload that every shard actually *uses* is concentrated in a
small prefix — the cluster-level analogue of cache-line locality.

The **hot-prefix exchange** (`hot_prefix_fraction` on the traversal
factories) exploits it: every step all-gathers only the first
``h_local = ceil(fraction * per)`` entries of each shard's property
slice; the cold remainder is refreshed by a full exchange every
``cold_every`` steps and read from a per-shard stale cache in between.
This is only applied to the *monotone min-relaxation* kernels (BFS as
unit-weight Bellman-Ford, SSSP, CC label propagation): their state only
ever decreases, so relaxing against stale — i.e. older, hence larger —
remote values can never commit a wrong result, only delay convergence.
Termination requires a **full** exchange step that changes nothing, so
the returned fixed point is exactly the single-device result. PageRank
and BC are level/iteration-synchronous and always exchange in full.
`ExchangeStats` accounts the per-step exchanged bytes either way.

**Fused drivers** (``fused=True``, the default): the whole traversal —
step loop, per-step collective, hot/cold cadence and the convergence
test — runs as one ``jax.lax.while_loop`` inside a single
``shard_map``-ped jit, so an entire BFS/SSSP/CC/PR/BC run compiles to
one ``XLA::While`` and costs **one** host→device dispatch instead of
one per step. Step counts come back in the loop carry and are replayed
into `ExchangeStats` on the host after the launch, so per-step byte
accounting and trace spans are unchanged. ``fused=False`` keeps the
original host-orchestrated loop (one jitted step per iteration) as the
differential reference — tests/test_fused_loops.py asserts the two are
bit-identical for all six kernels.

All six serving kernels have distributed entry points here: PR
(`make_distributed_pagerank`), multi-source BFS/SSSP
(`make_distributed_bfs` / `make_distributed_sssp`), CC by min-label
propagation (`make_distributed_cc`, also serving CC-SV: both converge to
the min-id-per-component labeling), and multi-source BC
(`make_distributed_bc`: BFS forward + sharded path counting + a
src-partitioned dependency-accumulation backward pass).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from .csr import Graph


def vertex_mesh(num_shards: int | None = None, axis: str = "data",
                devices=None) -> Mesh:
    """1-D mesh over ``num_shards`` devices (all visible by default).

    The axis is ``Auto``: the runners here place their own operands with
    `NamedSharding` and call `shard_map`, and the sharded knn path lets
    GSPMD partition a plain ``vmap``. Under JAX's default *Explicit* axes
    that knn program is ill-typed (a scatter into a replicated beam from
    a row-sharded query raises ``ShardingTypeError``).
    """
    devices = list(devices if devices is not None else jax.devices())
    n = num_shards or len(devices)
    return jax.make_mesh((n,), (axis,), axis_types=(AxisType.Auto,),
                         devices=devices[:n])


def _shard_map_norep(f, mesh, in_specs, out_specs):
    """shard_map with the replication check off — for steps returning an
    all-gathered (hence genuinely replicated) array under a P(None, ...)
    out_spec, which the static checker cannot infer. The fused drivers
    need it too: their while-carries mix sharded state with replicated
    caches/counters."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _partition_coo(src, dst, num_vertices: int, num_shards: int,
                   edge_values=None):
    """Split raw COO edges by dst range; pad shards to equal edge counts.

    Returns ``(src_pad, dst_pad, valid, per[, values_pad])`` where
    ``src_pad`` keeps *global* ids, ``dst_pad`` is localized to each
    shard's ``[i*per, (i+1)*per)`` range, and ``valid`` masks padding.
    Swapping the ``src``/``dst`` arguments partitions by source instead
    (used by the BC backward pass, which accumulates at src).
    """
    per = -(-num_vertices // num_shards)  # dst ids [i*per, (i+1)*per)
    src = np.asarray(src, dtype=np.int32)
    dst = np.asarray(dst, dtype=np.int32)
    shard_of = dst // per
    order = np.argsort(shard_of, kind="stable")
    src, dst = src[order], dst[order]
    counts = np.bincount(shard_of[order], minlength=num_shards)
    emax = int(counts.max()) if counts.size else 0
    s_pad = np.zeros((num_shards, emax), np.int32)
    d_pad = np.zeros((num_shards, emax), np.int32)
    valid = np.zeros((num_shards, emax), bool)
    if edge_values is not None:
        vals = np.asarray(edge_values)[order]
        v_pad = np.zeros((num_shards, emax), vals.dtype)
    off = 0
    for i, c in enumerate(counts):
        s_pad[i, :c] = src[off:off + c]
        d_pad[i, :c] = dst[off:off + c] - i * per  # local dst index
        valid[i, :c] = True
        if edge_values is not None:
            v_pad[i, :c] = vals[off:off + c]
        off += c
    if edge_values is not None:
        return s_pad, d_pad, valid, per, v_pad
    return s_pad, d_pad, valid, per


def partition_edges(g: Graph, num_shards: int, edge_values=None):
    """Split a graph's COO edges by dst range; pad shards equally.

    ``edge_values`` (optional, aligned with the graph's out-CSR edge
    order, e.g. SSSP weights) is partitioned identically and returned as
    a fifth array.
    """
    return _partition_coo(g.edge_src, g.indices, g.num_vertices, num_shards,
                          edge_values=edge_values)


# ---------------------------------------------------------- exchange stats
@dataclasses.dataclass
class ExchangeStats:
    """Per-step collective payload accounting for the sharded kernels.

    A "step" is one traversal iteration that all-gathers vertex property
    state. Bytes count what one device *receives* per step:
    ``(num_shards - 1) * slab_bytes`` — the remote share of the gathered
    array. ``bytes_full_equivalent`` books what the same step would have
    cost with a full exchange, so the hot-prefix saving is
    ``1 - bytes_exchanged / bytes_full_equivalent``.

    ``dispatches`` counts host→device launches: with host-loop drivers
    that is one per step (plus prep launches), with fused drivers one per
    run — the collapse the fused benchmark phase demonstrates.
    """

    steps_full: int = 0
    steps_hot: int = 0
    bytes_full: int = 0
    bytes_hot: int = 0
    bytes_full_equivalent: int = 0
    dispatches: int = 0
    # optional per-step observer ``(mode, nbytes, full_nbytes) -> None``:
    # the engine's sharded backend points this at its tracer while a run
    # is live, so every exchange becomes one trace span (engine/obs.py)
    # without dist growing an engine dependency. Fused runs replay their
    # device-side step counts through here right after the launch.
    span_sink: object = dataclasses.field(default=None, compare=False,
                                          repr=False)

    def record_full(self, nbytes: int) -> None:
        self.steps_full += 1
        self.bytes_full += nbytes
        self.bytes_full_equivalent += nbytes
        if self.span_sink is not None:
            self.span_sink("full", nbytes, nbytes)

    def record_hot(self, nbytes: int, full_nbytes: int) -> None:
        self.steps_hot += 1
        self.bytes_hot += nbytes
        self.bytes_full_equivalent += full_nbytes
        if self.span_sink is not None:
            self.span_sink("hot", nbytes, full_nbytes)

    def record_dispatch(self, n: int = 1) -> None:
        self.dispatches += n

    def record_run(self, steps_full: int, steps_hot: int,
                   full_nbytes: int, hot_nbytes: int) -> None:
        """Replay a fused run's device-side step counts one step at a
        time, so per-step accounting (and the span_sink) see the same
        sequence of records the host-loop driver would have produced."""
        for _ in range(int(steps_full)):
            self.record_full(full_nbytes)
        for _ in range(int(steps_hot)):
            self.record_hot(hot_nbytes, full_nbytes)

    def snapshot(self) -> tuple:
        """Counter tuple for per-run attribution (see ``delta``)."""
        return (self.steps_full, self.steps_hot, self.bytes_full,
                self.bytes_hot, self.bytes_full_equivalent, self.dispatches)

    def delta(self, since: tuple) -> "ExchangeStats":
        """Stats accumulated since ``snapshot()`` — the exchange cost of
        exactly one runner invocation when runs are serial, which is how
        the scheduler attributes collective bytes to individual requests
        instead of only the backend-level aggregate."""
        now = self.snapshot()
        return ExchangeStats(*(a - b for a, b in zip(now, since)))

    @property
    def steps(self) -> int:
        return self.steps_full + self.steps_hot

    @property
    def bytes_exchanged(self) -> int:
        return self.bytes_full + self.bytes_hot

    @property
    def bytes_per_step(self) -> float:
        return self.bytes_exchanged / max(self.steps, 1)

    @property
    def savings_fraction(self) -> float:
        if self.bytes_full_equivalent <= 0:
            return 0.0
        return 1.0 - self.bytes_exchanged / self.bytes_full_equivalent

    def as_dict(self) -> dict:
        return {
            "steps": self.steps,
            "steps_full": self.steps_full,
            "steps_hot": self.steps_hot,
            "bytes_full": self.bytes_full,
            "bytes_hot": self.bytes_hot,
            "bytes_exchanged": self.bytes_exchanged,
            "bytes_full_equivalent": self.bytes_full_equivalent,
            "bytes_per_step": round(self.bytes_per_step, 1),
            "savings_fraction": round(self.savings_fraction, 4),
            "dispatches": self.dispatches,
        }


def make_distributed_pagerank(g: Graph, mesh: Mesh, axis: str = "data",
                              damping: float = 0.85, num_iters: int = 20,
                              stats: ExchangeStats | None = None,
                              fused: bool = True):
    """Returns (step_fn, initial_rank) running PR over `axis` of `mesh`.

    ``fused=True`` runs all ``num_iters`` power iterations inside one
    ``lax.fori_loop`` under a single shard_map'd jit (one dispatch);
    ``fused=False`` is the host-loop reference (one dispatch per
    iteration).
    """
    num_shards = mesh.shape[axis]
    s_pad, d_pad, valid, per = partition_edges(g, num_shards)
    n = g.num_vertices
    n_pad = per * num_shards
    outdeg = np.maximum(np.asarray(g.out_degree, np.float32), 1.0)
    outdeg_pad = np.ones(n_pad, np.float32)
    outdeg_pad[:n] = outdeg
    dangling_pad = np.zeros(n_pad, np.float32)
    dangling_pad[:n] = (np.asarray(g.out_degree) == 0).astype(np.float32)

    espec = NamedSharding(mesh, P(axis, None))
    vspec = NamedSharding(mesh, P(axis))
    s_sh = jax.device_put(s_pad, espec)
    d_sh = jax.device_put(d_pad, espec)
    v_sh = jax.device_put(valid, espec)
    deg_sh = jax.device_put(outdeg_pad, vspec)
    dang_sh = jax.device_put(dangling_pad, vspec)

    def _iterate(rank, src_e, dst_e, val_e, deg, dang):
        # rank: (per,) local shard.  all-gather the full property array —
        # the collective whose *useful* payload LOrder concentrates.
        full = jax.lax.all_gather(rank, axis, tiled=True)       # (n_pad,)
        full_deg = jax.lax.all_gather(deg, axis, tiled=True)
        contrib = jnp.where(val_e[0], full[src_e[0]] / full_deg[src_e[0]], 0.0)
        summed = jax.ops.segment_sum(contrib, dst_e[0], num_segments=per)
        # dangling mass redistributed uniformly (GAP semantics)
        dangling = jax.lax.psum(jnp.sum(rank * dang), axis)
        return (1.0 - damping) / n + damping * (summed + dangling / n)

    def step(rank, src_e, dst_e, val_e, deg, dang):
        return _iterate(rank, src_e, dst_e, val_e, deg, dang)[None]

    sharded_step = jax.jit(jax.shard_map(
        step, mesh=mesh,
        in_specs=(P(axis), P(axis, None), P(axis, None), P(axis, None),
                  P(axis), P(axis)),
        out_specs=P(axis, None),
    ))

    def fused_run_fn(rank, src_e, dst_e, val_e, deg, dang):
        def body(_, r):
            return _iterate(r, src_e, dst_e, val_e, deg, dang)
        return jax.lax.fori_loop(0, num_iters, body, rank)

    sharded_fused = jax.jit(_shard_map_norep(
        fused_run_fn, mesh=mesh,
        in_specs=(P(axis), P(axis, None), P(axis, None), P(axis, None),
                  P(axis), P(axis)),
        out_specs=P(axis),
    ))

    # PR's power iteration is synchronous: every step needs a consistent
    # full view, so there is no hot-prefix variant — two f32 gathers
    # (rank + outdeg) per iteration, accounted in full.
    iter_bytes = 2 * (num_shards - 1) * per * 4

    def run(rank0=None):
        r = rank0 if rank0 is not None else jax.device_put(
            np.full(n_pad, 1.0 / n, np.float32), vspec)
        if fused:
            r = sharded_fused(r, s_sh, d_sh, v_sh, deg_sh, dang_sh)
            if stats is not None:
                stats.record_dispatch()
                stats.record_run(num_iters, 0, iter_bytes, 0)
            return r[:n]
        for _ in range(num_iters):
            r = sharded_step(r, s_sh, d_sh, v_sh, deg_sh,
                             dang_sh).reshape(n_pad)
            if stats is not None:
                stats.record_dispatch()
                stats.record_full(iter_bytes)
        return r[:n]

    return run, vspec


def lower_distributed_pagerank(g: Graph, mesh: Mesh, axis: str = "data"):
    """Lower+compile one sharded PR step (dry-run hook for the graph engine)."""
    run, _ = make_distributed_pagerank(g, mesh, axis, num_iters=1)
    return run


# ------------------------------------------------- multi-source traversals
#
# Serving parity with the single-device engine: batched BFS / SSSP / CC /
# BC where the (S, V) property matrix is sharded along the *vertex* axis
# and each level/relaxation step all-gathers it. The outer iteration is
# either a single on-device `lax.while_loop` (fused, one launch per run)
# or a host loop with a device-side convergence flag (the reference) —
# bounded by eccentricity (BFS) or V (Bellman-Ford) either way.

_INF_I32 = np.int32(2**31 - 1)


def _put_state(values: np.ndarray, mesh: Mesh, axis: str):
    """Upload an (S, n_pad) property matrix sharded over its vertex axis."""
    return jax.device_put(values, NamedSharding(mesh, P(None, axis)))


# ------------------------------------------- hot-prefix min-relaxation core
def _make_minrelax_runner(coo_src, coo_dst, edge_w, num_vertices: int,
                          mesh: Mesh, axis: str,
                          hot_prefix_fraction: float | None = None,
                          cold_every: int = 4,
                          stats: ExchangeStats | None = None,
                          fused: bool = True):
    """Generic monotone min-relaxation to a fixed point over shard_map.

    State is an int32 ``(S, n_pad)`` matrix sharded on the vertex axis;
    one step relaxes ``state[dst] = min(state[dst], state[src] + w)`` over
    the dst-partitioned edge set. With ``hot_prefix_fraction`` set, hot
    steps gather only each shard's first ``h_local`` entries and read the
    cold remainder from the cache left by the last full exchange; the
    shard's *own* slice is always read live. Because state is monotone
    non-increasing, stale (older = larger) remote values can only delay a
    relaxation, never commit a wrong one — and the loop terminates only
    when a **full**-exchange step changes nothing, i.e. at the exact
    global fixed point.

    ``fused=True`` puts the whole loop — including the full/hot cadence
    (``lax.cond`` over the two gather shapes) and the termination test —
    inside one ``lax.while_loop`` under a single shard_map'd jit: one
    XLA::While, one dispatch per run. The step sequence is identical to
    the ``fused=False`` host loop, so results are bit-identical.

    Returns ``run(state0) -> (S, n_pad) final state`` with
    ``run.h_local``, ``run.per``, ``run.hot_prefix_fraction`` and the
    static ``run.prefix_hit_rate`` (fraction of edge-source reads served
    fresh: local to the shard, or inside the gathered hot prefix).
    """
    num_shards = mesh.shape[axis]
    cold_every = max(int(cold_every), 1)
    s_pad, d_pad, valid, per, w_pad = _partition_coo(
        coo_src, coo_dst, num_vertices, num_shards,
        edge_values=np.asarray(edge_w, np.int32))
    n_pad = per * num_shards
    f = hot_prefix_fraction
    h_local = per if f is None else min(per, max(1, int(np.ceil(f * per))))
    # distance info crosses at least one hop per full exchange even in
    # the worst case, so the fixed point is reached well inside
    # V * cold_every steps; the bound is a backstop, not the driver
    max_iters = num_vertices * cold_every + cold_every + 2

    espec = NamedSharding(mesh, P(axis, None))
    s_sh = jax.device_put(s_pad, espec)
    d_sh = jax.device_put(d_pad, espec)
    v_sh = jax.device_put(valid, espec)
    w_sh = jax.device_put(w_pad, espec)

    def _relax(state, view, src_e, dst_e, val_e, w_e):
        du = view[:, src_e[0]]                               # (S, e_local)
        cand = jnp.where(val_e[0] & (du != _INF_I32), du + w_e[0], _INF_I32)
        relaxed = jax.vmap(
            lambda c: jax.ops.segment_min(c, dst_e[0], num_segments=per)
        )(cand)
        new = jnp.minimum(state, relaxed)
        # replicated convergence flag, as the P() out_spec requires
        changed = jax.lax.psum((new != state).any().astype(jnp.int32), axis)
        return new, changed > 0

    def _gather_full(state):
        return jax.lax.all_gather(state, axis, axis=1, tiled=True)

    def _hot_view(state, cache):
        # gather only the hot prefix of every shard's slice ...
        fresh = jax.lax.all_gather(state[:, :h_local], axis,
                                   axis=0, tiled=False)  # (shards, S, h)
        view = cache.reshape(cache.shape[0], num_shards, per)
        view = view.at[:, :, :h_local].set(jnp.transpose(fresh, (1, 0, 2)))
        # ... and read the shard's own slice live, not from the cache
        view = jax.lax.dynamic_update_slice_in_dim(
            view, state[:, None, :], jax.lax.axis_index(axis), axis=1)
        return view.reshape(cache.shape[0], n_pad)

    def step_full(state, src_e, dst_e, val_e, w_e):
        full = _gather_full(state)
        new, changed = _relax(state, full, src_e, dst_e, val_e, w_e)
        # the gathered view doubles as the cold cache until the next full
        # exchange; identical on every shard, hence the replicated spec
        return new, full, changed

    sharded_full = jax.jit(_shard_map_norep(
        step_full, mesh=mesh,
        in_specs=(P(None, axis), P(axis, None), P(axis, None),
                  P(axis, None), P(axis, None)),
        out_specs=(P(None, axis), P(None, None), P()),
    ))

    def step_hot(state, cache, src_e, dst_e, val_e, w_e):
        return _relax(state, _hot_view(state, cache),
                      src_e, dst_e, val_e, w_e)

    sharded_hot = jax.jit(jax.shard_map(
        step_hot, mesh=mesh,
        in_specs=(P(None, axis), P(None, None), P(axis, None),
                  P(axis, None), P(axis, None), P(axis, None)),
        out_specs=(P(None, axis), P()),
    ))

    # ---------------------------------------------------- fused driver
    def fused_fn(state, src_e, dst_e, val_e, w_e):
        # carry: (state, cache, it, full_due, done, steps_full, steps_hot)
        # — the exact control variables of the host loop below, moved
        # into the While carry so the cadence and the termination test
        # compile into the loop. `is_full`/`done` derive from psum'd
        # flags, hence replicated, so lax.cond may hold a collective in
        # each branch. With no hot prefix configured the cadence is
        # static — every step is full — so that case compiles without
        # the cond or the (S, n_pad) cache in the carry.
        if f is None:
            def cond(c):
                _, done, it, _ = c
                return ~done & (it < max_iters)

            def body(c):
                st, _, it, sf = c
                new, _, changed = step_full(st, src_e, dst_e, val_e, w_e)
                return new, ~changed, it + 1, sf + 1

            state, _, _, sf = jax.lax.while_loop(
                cond, body,
                (state, jnp.bool_(False), jnp.int32(0), jnp.int32(0)))
            return state, sf, jnp.int32(0)

        s_rows = state.shape[0]
        cache0 = jnp.zeros((s_rows, n_pad), jnp.int32)

        def full_branch(st, cache):
            new, full, changed = step_full(st, src_e, dst_e, val_e, w_e)
            return new, full, changed

        def hot_branch(st, cache):
            new, changed = step_hot(st, cache, src_e, dst_e, val_e, w_e)
            return new, cache, changed

        def cond(c):
            _, _, it, _, done, _, _ = c
            return ~done & (it < max_iters)

        def body(c):
            st, cache, it, full_due, _, sf, sh = c
            is_full = full_due | (it % cold_every == 0)
            st, cache, changed = jax.lax.cond(
                is_full, full_branch, hot_branch, st, cache)
            done = is_full & ~changed
            full_due = jnp.where(is_full, False, ~changed)
            return (st, cache, it + 1, full_due, done,
                    sf + is_full.astype(jnp.int32),
                    sh + (~is_full).astype(jnp.int32))

        init = (state, cache0, jnp.int32(0), jnp.bool_(True),
                jnp.bool_(False), jnp.int32(0), jnp.int32(0))
        state, _, _, _, _, sf, sh = jax.lax.while_loop(cond, body, init)
        return state, sf, sh

    sharded_fused = jax.jit(_shard_map_norep(
        fused_fn, mesh=mesh,
        in_specs=(P(None, axis), P(axis, None), P(axis, None),
                  P(axis, None), P(axis, None)),
        out_specs=(P(None, axis), P(), P()),
    ))

    def run(state0):
        s = int(np.asarray(state0).shape[0])
        state = _put_state(np.asarray(state0, np.int32), mesh, axis)
        full_b = (num_shards - 1) * per * 4 * s
        hot_b = (num_shards - 1) * h_local * 4 * s
        if fused:
            state, sf, sh = sharded_fused(state, s_sh, d_sh, v_sh, w_sh)
            if stats is not None:
                stats.record_dispatch()
                stats.record_run(int(sf), int(sh), full_b, hot_b)
            return state
        cache = None
        full_due = True
        for it in range(max_iters):
            if f is None or full_due or it % cold_every == 0:
                state, cache, changed = sharded_full(state, s_sh, d_sh,
                                                     v_sh, w_sh)
                if stats is not None:
                    stats.record_dispatch()
                    stats.record_full(full_b)
                full_due = False
                if not bool(changed):
                    break  # fixed point certified against the full view
            else:
                state, changed = sharded_hot(state, cache, s_sh, d_sh,
                                             v_sh, w_sh)
                if stats is not None:
                    stats.record_dispatch()
                    stats.record_hot(hot_b, full_b)
                if not bool(changed):
                    full_due = True  # locally quiesced: verify in full
        return state

    if f is None:
        run.prefix_hit_rate = 1.0
    else:
        own = (s_pad // per) == np.arange(num_shards)[:, None]
        hit = (own | ((s_pad % per) < h_local)) & valid
        nvalid = int(valid.sum())
        run.prefix_hit_rate = float(hit.sum() / nvalid) if nvalid else 1.0
    run.h_local, run.per, run.hot_prefix_fraction = h_local, per, f
    return run


def _copy_prefix_attrs(run, relax) -> None:
    run.prefix_hit_rate = relax.prefix_hit_rate
    run.h_local, run.per = relax.h_local, relax.per
    run.hot_prefix_fraction = relax.hot_prefix_fraction


# ------------------------------------------------------------------- BFS
def _make_bfs_frontier(g: Graph, mesh: Mesh, axis: str,
                       stats: ExchangeStats | None, fused: bool = True):
    """Level-synchronous frontier BFS; returns run(sources) -> sharded
    (S, n_pad) depth (the full-exchange path, also BC's forward pass)."""
    num_shards = mesh.shape[axis]
    s_pad, d_pad, valid, per = partition_edges(g, num_shards)
    n, n_pad = g.num_vertices, per * num_shards
    espec = NamedSharding(mesh, P(axis, None))
    s_sh = jax.device_put(s_pad, espec)
    d_sh = jax.device_put(d_pad, espec)
    v_sh = jax.device_put(valid, espec)

    def step(depth, front, level, src_e, dst_e, val_e):
        # depth/front: (S, per) local vertex slices; edges: (1, e_local)
        full_front = jax.lax.all_gather(front, axis, axis=1, tiled=True)
        active = full_front[:, src_e[0]] & val_e[0]           # (S, e_local)
        touched = jax.vmap(
            lambda a: jax.ops.segment_max(a, dst_e[0], num_segments=per)
        )(active)
        new = touched & (depth < 0)
        depth = jnp.where(new, level + 1, depth)
        # replicated scalar per the P() out_spec: the loop predicate (or
        # the host loop) reads one flag instead of reducing the whole
        # sharded frontier each level
        alive = jax.lax.psum(new.any().astype(jnp.int32), axis)
        return depth, new, alive > 0

    sharded_step = jax.jit(jax.shard_map(
        step, mesh=mesh,
        in_specs=(P(None, axis), P(None, axis), P(),
                  P(axis, None), P(axis, None), P(axis, None)),
        out_specs=(P(None, axis), P(None, axis), P()),
    ))

    def fused_fn(depth, front, src_e, dst_e, val_e):
        def cond(c):
            _, _, level, alive = c
            return alive & (level < n)

        def body(c):
            depth, front, level, _ = c
            depth, front, alive = step(depth, front, level,
                                       src_e, dst_e, val_e)
            return depth, front, level + 1, alive

        # do-while: the initial frontier is never empty (sources exist)
        depth, _, steps, _ = jax.lax.while_loop(
            cond, body, (depth, front, jnp.int32(0), jnp.bool_(True)))
        return depth, steps

    sharded_fused = jax.jit(_shard_map_norep(
        fused_fn, mesh=mesh,
        in_specs=(P(None, axis), P(None, axis),
                  P(axis, None), P(axis, None), P(axis, None)),
        out_specs=(P(None, axis), P()),
    ))

    def run_full(sources):
        srcs = np.atleast_1d(np.asarray(sources, np.int64))
        s = srcs.size
        depth0 = np.full((s, n_pad), -1, np.int32)
        depth0[np.arange(s), srcs] = 0
        front0 = np.zeros((s, n_pad), bool)
        front0[np.arange(s), srcs] = True
        depth = _put_state(depth0, mesh, axis)
        front = _put_state(front0, mesh, axis)
        level_bytes = (num_shards - 1) * per * 1 * s  # bool frontier
        if fused:
            depth, steps = sharded_fused(depth, front, s_sh, d_sh, v_sh)
            if stats is not None:
                stats.record_dispatch()
                stats.record_run(int(steps), 0, level_bytes, 0)
            return depth
        # do-while: the initial frontier is never empty (sources exist)
        for level in range(n):
            depth, front, alive = sharded_step(depth, front,
                                               jnp.int32(level),
                                               s_sh, d_sh, v_sh)
            if stats is not None:
                stats.record_dispatch()
                stats.record_full(level_bytes)
            if not bool(alive):
                break
        return depth

    run_full.per = per
    # the dst-partitioned edge uploads and the raw per-shard step body,
    # reusable by passes that share the same partition (BC's forward σ
    # pass, and BC's fully-fused driver) — one partition, one upload
    run_full.edge_shards = (s_sh, d_sh, v_sh)
    run_full.step_fn = step
    return run_full


def make_distributed_bfs(g: Graph, mesh: Mesh, axis: str = "data",
                         hot_prefix_fraction: float | None = None,
                         cold_every: int = 4,
                         stats: ExchangeStats | None = None,
                         fused: bool = True):
    """Returns run(sources) -> (S, V) BFS depths over `axis` of `mesh`.

    With ``hot_prefix_fraction`` set, BFS runs as unit-weight Bellman-Ford
    through the hot-prefix min-relaxation driver (exact depths; the level
    counter of the frontier formulation cannot tolerate stale frontiers,
    min-relaxation can). Without it, the level-synchronous frontier path
    exchanges the full frontier every step.
    """
    n = g.num_vertices
    if hot_prefix_fraction is None:
        run_full = _make_bfs_frontier(g, mesh, axis, stats, fused=fused)

        def run(sources):
            return run_full(sources)[:, :n]

        run.prefix_hit_rate, run.hot_prefix_fraction = 1.0, None
        run.per = run_full.per
        run.h_local = run_full.per
        return run

    unit = np.ones(g.num_edges, np.int32)
    relax = _make_minrelax_runner(g.edge_src, g.indices, unit, n, mesh, axis,
                                  hot_prefix_fraction, cold_every, stats,
                                  fused=fused)
    n_pad = relax.per * mesh.shape[axis]

    def run(sources):
        srcs = np.atleast_1d(np.asarray(sources, np.int64))
        state0 = np.full((srcs.size, n_pad), _INF_I32, np.int32)
        state0[np.arange(srcs.size), srcs] = 0
        dist = relax(state0)
        return jnp.where(dist == _INF_I32, -1, dist)[:, :n]

    _copy_prefix_attrs(run, relax)
    return run


def make_distributed_sssp(g: Graph, mesh: Mesh, axis: str = "data",
                          canonical_ids=None,
                          hot_prefix_fraction: float | None = None,
                          cold_every: int = 4,
                          stats: ExchangeStats | None = None,
                          fused: bool = True):
    """Returns run(sources) -> (S, V) Bellman-Ford distances.

    Weights are the engine's canonical per-edge hash
    (`algos.graph_arrays.edge_weights`, relabel-invariant through
    ``canonical_ids``), so sharded distances match the single-device
    executor exactly — with or without the hot-prefix exchange
    (Bellman-Ford is monotone, see `_make_minrelax_runner`). Both the
    full-exchange and hot-prefix paths run through the min-relaxation
    driver (with ``hot_prefix_fraction=None`` every step is a full
    exchange), so SSSP gets the fused single-dispatch loop for free.
    """
    from ..algos.graph_arrays import edge_weights

    n = g.num_vertices
    w = edge_weights(g.edge_src, g.indices, canonical_ids)
    relax = _make_minrelax_runner(g.edge_src, g.indices, w, n, mesh, axis,
                                  hot_prefix_fraction, cold_every, stats,
                                  fused=fused)
    n_pad = relax.per * mesh.shape[axis]

    def run(sources):
        srcs = np.atleast_1d(np.asarray(sources, np.int64))
        state0 = np.full((srcs.size, n_pad), _INF_I32, np.int32)
        state0[np.arange(srcs.size), srcs] = 0
        return relax(state0)[:, :n]

    _copy_prefix_attrs(run, relax)
    return run


# -------------------------------------------------- Connected Components
def make_distributed_cc(g: Graph, mesh: Mesh, axis: str = "data",
                        hot_prefix_fraction: float | None = None,
                        cold_every: int = 4,
                        stats: ExchangeStats | None = None,
                        fused: bool = True):
    """Returns run() -> (V,) min-label CC over the symmetrized edges.

    Min-label propagation is a monotone min-relaxation (weight 0 over the
    symmetrized edge set), so it runs through the same driver as the
    hot-prefix traversals — with ``hot_prefix_fraction`` unset every step
    is a full exchange. Converges to the min-vertex-id-per-component
    labeling, bit-identical to `algos.kernels.cc_labelprop`; CC-SV
    reaches the same labeling, so this runner serves both cc and ccsv.
    """
    n = g.num_vertices
    src = np.concatenate([np.asarray(g.edge_src), np.asarray(g.indices)])
    dst = np.concatenate([np.asarray(g.indices), np.asarray(g.edge_src)])
    relax = _make_minrelax_runner(src, dst, np.zeros(src.size, np.int32), n,
                                  mesh, axis, hot_prefix_fraction,
                                  cold_every, stats, fused=fused)
    n_pad = relax.per * mesh.shape[axis]

    def run():
        lab0 = np.arange(n_pad, dtype=np.int32)[None, :]
        return relax(lab0)[0, :n]

    _copy_prefix_attrs(run, relax)
    return run


# -------------------------------------------- Betweenness Centrality (BC)
def make_distributed_bc(g: Graph, mesh: Mesh, axis: str = "data",
                        stats: ExchangeStats | None = None,
                        fused: bool = True):
    """Returns run(sources) -> (S, V) per-source Brandes dependencies.

    Three sharded passes, mirroring `algos.kernels.bc_single_source`:

    1. **forward depths** — the frontier BFS above, kept sharded;
    2. **path counts** — per level, all-gather sigma and segment-sum the
       tree-edge contributions into local dst (edges partitioned by dst);
    3. **dependency accumulation** — per level backwards, all-gather
       delta and accumulate ``sigma[u]/sigma[v] * (1 + delta[v])`` into
       local src over a *source-partitioned* copy of the edges (the
       backward pass scatters to src, so dst-partitioned edges would
       need a cross-shard scatter).

    ``fused=True`` compiles all three passes — BFS While, σ While, δ
    While, with ``max_level`` carried as a traced pmax instead of a host
    round-trip — into **one** shard_map'd jit: a whole multi-source BC
    run is a single dispatch. ``fused=False`` keeps the per-level host
    loops as the reference.

    Level-synchronous float accumulation: no hot-prefix variant (the
    per-level sums need a consistent view), and results are numerically
    close — not bit-identical — to the single-device kernel because the
    segment-sum order differs.
    """
    num_shards = mesh.shape[axis]
    n = g.num_vertices
    bfs_full = _make_bfs_frontier(g, mesh, axis, stats, fused=fused)
    per = bfs_full.per
    n_pad = per * num_shards

    espec = NamedSharding(mesh, P(axis, None))
    # forward: dst-partitioned (sigma accumulates at dst) — the exact
    # partition the frontier BFS already uploaded, so reuse it
    s_sh, d_sh, v_sh = bfs_full.edge_shards
    bfs_step = bfs_full.step_fn
    # backward: src-partitioned (delta accumulates at src); swapping the
    # COO roles localizes src and keeps dst global
    bd_pad, bs_pad, bvalid, per_b = _partition_coo(g.indices, g.edge_src, n,
                                                   num_shards)
    assert per_b == per
    bd_sh = jax.device_put(bd_pad, espec)   # global dst ids
    bs_sh = jax.device_put(bs_pad, espec)   # local src indices
    bv_sh = jax.device_put(bvalid, espec)

    def fwd_prep(depth, src_e, dst_e, val_e):
        full_depth = jax.lax.all_gather(depth, axis, axis=1, tiled=True)
        du = full_depth[:, src_e[0]]                      # (S, e_local)
        dv = depth[:, dst_e[0]]                           # dst is local
        tree = (dv == du + 1) & (du >= 0) & val_e[0]
        return du, tree

    sharded_fwd_prep = jax.jit(jax.shard_map(
        fwd_prep, mesh=mesh,
        in_specs=(P(None, axis), P(axis, None), P(axis, None),
                  P(axis, None)),
        out_specs=(P(None, axis), P(None, axis)),
    ))

    def fwd_step(sigma, du, tree, src_e, dst_e, level):
        full_sigma = jax.lax.all_gather(sigma, axis, axis=1, tiled=True)
        add_e = jnp.where(tree & (du == level),
                          full_sigma[:, src_e[0]], 0.0)
        add = jax.vmap(
            lambda c: jax.ops.segment_sum(c, dst_e[0], num_segments=per)
        )(add_e)
        return sigma + add

    sharded_fwd_step = jax.jit(jax.shard_map(
        fwd_step, mesh=mesh,
        in_specs=(P(None, axis), P(None, axis), P(None, axis),
                  P(axis, None), P(axis, None), P()),
        out_specs=P(None, axis),
    ))

    def bwd_prep(depth, sigma, bsrc_e, bdst_e, bval_e):
        full_depth = jax.lax.all_gather(depth, axis, axis=1, tiled=True)
        du = depth[:, bsrc_e[0]]                          # src is local
        dv = full_depth[:, bdst_e[0]]
        tree = (dv == du + 1) & (du >= 0) & bval_e[0]
        # sigma is fixed during the backward pass: each tree edge's
        # sigma[u]/sigma[v] is taken once here, not per level. Read per
        # level inside the fused While, the same ratios gave dependencies
        # off by up to 1e33 across four v5e chips.
        sig_full = jax.lax.all_gather(sigma, axis, axis=1, tiled=True)
        sig_v = jnp.maximum(sig_full[:, bdst_e[0]], 1e-30)
        ratio = jnp.where(tree, sigma[:, bsrc_e[0]] / sig_v, 0.0)
        return du, tree, ratio

    sharded_bwd_prep = jax.jit(jax.shard_map(
        bwd_prep, mesh=mesh,
        in_specs=(P(None, axis), P(None, axis), P(axis, None),
                  P(axis, None), P(axis, None)),
        out_specs=(P(None, axis), P(None, axis), P(None, axis)),
    ))

    def bwd_step(delta, ratio, du, tree, bsrc_e, bdst_e, level):
        full_delta = jax.lax.all_gather(delta, axis, axis=1, tiled=True)
        mask = tree & (du == level)
        contrib = jnp.where(
            mask, ratio * (1.0 + full_delta[:, bdst_e[0]]), 0.0)
        add = jax.vmap(
            lambda c: jax.ops.segment_sum(c, bsrc_e[0], num_segments=per)
        )(contrib)
        return delta + add

    sharded_bwd_step = jax.jit(jax.shard_map(
        bwd_step, mesh=mesh,
        in_specs=(P(None, axis), P(None, axis), P(None, axis),
                  P(None, axis), P(axis, None), P(axis, None), P()),
        out_specs=P(None, axis),
    ))

    # ---------------------------------------------------- fused driver
    def fused_fn(depth, front, sigma, src_e, dst_e, val_e,
                 bsrc_e, bdst_e, bval_e):
        # pass 1: forward BFS — the same While as _make_bfs_frontier's
        def bfs_cond(c):
            _, _, level, alive = c
            return alive & (level < n)

        def bfs_body(c):
            depth, front, level, _ = c
            depth, front, alive = bfs_step(depth, front, level,
                                           src_e, dst_e, val_e)
            return depth, front, level + 1, alive

        depth, _, bfs_steps, _ = jax.lax.while_loop(
            bfs_cond, bfs_body, (depth, front, jnp.int32(0),
                                 jnp.bool_(True)))
        # the host reference reads max_level back between passes; fused,
        # it is a traced replicated scalar (padded vertices sit at -1, and
        # the source row guarantees a max >= 0)
        max_level = jax.lax.pmax(jnp.max(depth), axis)

        # pass 2: path counts, level-synchronous up to max_level
        du_f, tree_f = fwd_prep(depth, src_e, dst_e, val_e)

        def fwd_body(c):
            sigma, level = c
            return (fwd_step(sigma, du_f, tree_f, src_e, dst_e, level),
                    level + 1)

        sigma, _ = jax.lax.while_loop(
            lambda c: c[1] <= max_level, fwd_body, (sigma, jnp.int32(0)))

        # pass 3: dependency accumulation, levels max_level-1 .. 0
        du_b, tree_b, ratio = bwd_prep(depth, sigma, bsrc_e, bdst_e, bval_e)

        def bwd_body(c):
            delta, level = c
            return (bwd_step(delta, ratio, du_b, tree_b, bsrc_e, bdst_e,
                             level), level - 1)

        delta, _ = jax.lax.while_loop(
            lambda c: c[1] >= 0, bwd_body,
            (jnp.zeros_like(sigma), max_level - 1))
        return delta, bfs_steps, max_level

    sharded_fused = jax.jit(_shard_map_norep(
        fused_fn, mesh=mesh,
        in_specs=(P(None, axis), P(None, axis), P(None, axis),
                  P(axis, None), P(axis, None), P(axis, None),
                  P(axis, None), P(axis, None), P(axis, None)),
        out_specs=(P(None, axis), P(), P()),
    ))

    def run(sources):
        srcs = np.atleast_1d(np.asarray(sources, np.int64))
        s = srcs.size
        step_bytes = (num_shards - 1) * per * 4 * s
        level_bytes = (num_shards - 1) * per * 1 * s  # bool frontier
        sigma0 = np.zeros((s, n_pad), np.float32)
        sigma0[np.arange(s), srcs] = 1.0
        if fused:
            depth0 = np.full((s, n_pad), -1, np.int32)
            depth0[np.arange(s), srcs] = 0
            front0 = np.zeros((s, n_pad), bool)
            front0[np.arange(s), srcs] = True
            delta, bfs_steps, max_level = sharded_fused(
                _put_state(depth0, mesh, axis),
                _put_state(front0, mesh, axis),
                _put_state(sigma0, mesh, axis),
                s_sh, d_sh, v_sh, bs_sh, bd_sh, bv_sh)
            max_level = int(max_level)
            if stats is not None:
                # replay the host reference's per-step accounting from
                # the device-side counters: BFS frontier gathers, one
                # fwd_prep, max_level+1 σ gathers, depth+sigma bwd_prep,
                # max_level δ gathers — all in one dispatch
                stats.record_dispatch()
                stats.record_run(int(bfs_steps), 0, level_bytes, 0)
                stats.record_full(step_bytes)
                stats.record_run(max_level + 1, 0, step_bytes, 0)
                stats.record_full(2 * step_bytes)
                stats.record_run(max_level, 0, step_bytes, 0)
        else:
            depth = bfs_full(srcs)                    # (S, n_pad) sharded
            max_level = int(np.asarray(depth[:, :n]).max())
            du_f, tree_f = sharded_fwd_prep(depth, s_sh, d_sh, v_sh)
            sigma = _put_state(sigma0, mesh, axis)
            if stats is not None:
                stats.record_dispatch()
                stats.record_full(step_bytes)         # fwd_prep depth gather
            for level in range(max_level + 1):
                sigma = sharded_fwd_step(sigma, du_f, tree_f, s_sh, d_sh,
                                         jnp.int32(level))
                if stats is not None:
                    stats.record_dispatch()
                    stats.record_full(step_bytes)
            du_b, tree_b, ratio = sharded_bwd_prep(depth, sigma, bs_sh,
                                                   bd_sh, bv_sh)
            if stats is not None:
                stats.record_dispatch()
                stats.record_full(2 * step_bytes)     # depth + sigma gathers
            delta = _put_state(np.zeros((s, n_pad), np.float32), mesh, axis)
            for level in range(max_level - 1, -1, -1):
                delta = sharded_bwd_step(delta, ratio, du_b, tree_b,
                                         bs_sh, bd_sh, jnp.int32(level))
                if stats is not None:
                    stats.record_dispatch()
                    stats.record_full(step_bytes)
        out = np.array(delta)[:, :n]
        out[np.arange(s), srcs] = 0.0
        return jnp.asarray(out)

    run.prefix_hit_rate, run.hot_prefix_fraction = 1.0, None
    run.per = per
    run.h_local = per
    return run
